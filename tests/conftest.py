"""Shared fixtures.  NOTE: XLA_FLAGS / device-count overrides are deliberately
NOT set here -- smoke tests and benches must see the single real CPU device.
Multi-device tests spawn subprocesses with their own XLA_FLAGS."""

import os
import pathlib

import numpy as np
import pytest

from repro.core import Cluster, JobSpec, ModelSpec, build_comm_matrix

#: root of the checkout these tests belong to (children run from here)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def cpu_child_env(**extra) -> dict:
    """Environment for a child python process: this one's, held to the CPU,
    with this checkout's ``src`` first on ``PYTHONPATH``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    path = [str(REPO_ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p]
    env["PYTHONPATH"] = os.pathsep.join(path)
    env.update(extra)
    return env


@pytest.fixture
def model7b():
    # 7B GPT-style reference model (paper Appendix C sanity numbers).
    return ModelSpec(
        name="gpt-7b", hidden=4096, layers=32, vocab=50304, seq_len=2048,
        global_batch=1024, micro_batch=1, d_ff=16384,
    )


@pytest.fixture
def small_job(model7b):
    return JobSpec(n_gpus=96, tp=4, pp=2, model=model7b)


@pytest.fixture
def small_comm(small_job):
    return build_comm_matrix(small_job)


@pytest.fixture
def cluster_i():
    return Cluster.paper_setting("i")


@pytest.fixture
def cluster_iii():
    return Cluster.paper_setting("iii")
