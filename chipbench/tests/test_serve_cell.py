"""Rehearsal of each serve cell end to end on the CPU at ``reduced()`` sizes
(the chip check skipped), and the same run with the timed path broken
underneath, which must come out not correct, also in the test-only cell
with sparse experts."""

import time

import jax
import jax.numpy as jnp
import pytest

import repro.serve.engine as engine_mod
from chipbench import serve
from chipbench.tests.helpers import MOE_CELL, SERVE_CELLS, reduced_cell

SECONDS = 3.0
SEED = 2**31 + 99
ENGINE_STEPS = engine_mod.engine_steps   # the program's own, unbroken


def _run(name, trace=False):
    return serve.run(reduced_cell(name), SEED, SECONDS, trace, time.perf_counter())


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_cell_runs_and_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert list(out)[-1] == "checks"


def test_traced_run_reads_engine_metrics_and_leaves_device_ones_out():
    out = _run("minicpm-2b.decode-backlog", trace=True)
    assert out["correct"], out["checks"]
    assert "lane_occupancy.decode" in out["metrics"]
    assert "decode_mfu" not in out["metrics"]     # no device plane on the CPU
    assert out["device"]["window_s"] >= SECONDS


def _token_altered(model):
    decode, prefill = ENGINE_STEPS(model)

    def altered(*args):
        tokens, pages = decode(*args)
        return (tokens + 1) % model.cfg.vocab, pages

    return altered, prefill


def _state_unchanged(model):
    _, prefill = ENGINE_STEPS(model)

    def decode_fn(params, pages, tables, lengths, tokens, active):
        logits, _ = model.decode_step_paged(params, pages, tables, lengths,
                                            tokens, active)
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), pages

    return jax.jit(decode_fn), prefill


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
@pytest.mark.parametrize("name", SERVE_CELLS + [MOE_CELL])
def test_broken_path_is_not_correct(name, fault, monkeypatch):
    monkeypatch.setattr(engine_mod, "engine_steps", fault)
    out = _run(name)
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]
