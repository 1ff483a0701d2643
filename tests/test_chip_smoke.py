"""Rehearsal of ``chip_smoke.py`` on the CPU: its phases at ``reduced()``
sizes (the serving phase in this process, the training phase on 4 virtual
devices in a child), its refusal to report success without a TPU, and the
compile-cache directory the entry points choose."""

import importlib.util
import json
import subprocess
import sys
import textwrap

import pytest

from conftest import REPO_ROOT, cpu_child_env
from repro.configs import get_config
from repro.serve import EngineConfig


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO_ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_reduced():
    out = _chip_smoke().serve_phase(
        get_config("minicpm-2b").reduced(),
        EngineConfig(max_batch=4, page_size=8, n_pages=64, max_blocks=8),
        n_requests=6, prompt_lens=(8, 12, 24), new_tokens=(4, 8),
    )
    assert out["requests"] == 6
    assert out["prefill_prompt_len"] == 24   # padded most: 24 -> bucket 32
    assert out["prefill_logit_rel_err"] <= 5e-2, out


def test_train_phase_reduced_on_four_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import json, pathlib
        import chip_smoke
        out = chip_smoke.train_phase("minicpm-2b", full=False, steps=3,
                                     seq_len=32, out_dir=pathlib.Path({str(tmp_path)!r}))
        print(json.dumps(out))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=REPO_ROOT,
        env=cpu_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "Arnold placement" in proc.stdout
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(out["losses"]["arnold"]) == len(out["losses"]["plain"]) == 3
    assert out["max_loss_diff"] <= 2e-2
    assert not any(tmp_path.iterdir())   # checkpoints removed after each run


@pytest.mark.parametrize("env_dir", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The entry points' cache: ``$JAX_COMPILATION_CACHE_DIR`` where set
    (and nothing set over it), else ``<checkout>/.jax_cache``."""
    code = textwrap.dedent("""
        import json, jax
        from repro.launch.compile_cache import enable_compile_cache
        print(json.dumps([enable_compile_cache(),
                          jax.config.jax_compilation_cache_dir]))
    """)
    env = cpu_child_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, cwd=REPO_ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = str(tmp_path) if env_dir else str(REPO_ROOT / ".jax_cache")
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == [want, want]


def test_main_fails_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, cwd=REPO_ROOT, env=cpu_child_env(),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr
