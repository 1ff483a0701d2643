"""The entry point refuses to report without a TPU, and cannot run from a
directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

from chipbench.cells import ROOT

ARGS = ["--workload", "minicpm-2b.decode-backlog", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_exits_nonzero_without_a_tpu():
    proc = subprocess.run([sys.executable, "chipbench/run.py", *ARGS], cwd=ROOT,
                          capture_output=True, text=True, timeout=300, env=_env())
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = subprocess.run([sys.executable, "chipbench/run.py", *ARGS], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300, env=_env())
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_unknown_workload_is_refused():
    proc = subprocess.run([sys.executable, "chipbench/run.py", *ARGS[:-6],
                           "--workload", "nope", "--seed", "1", "--seconds", "1"],
                          cwd=ROOT, capture_output=True, text=True, timeout=300,
                          env=_env())
    assert proc.returncode != 0 and proc.stdout.strip() == ""
