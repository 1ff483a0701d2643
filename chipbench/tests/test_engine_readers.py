"""The readers of the engine's own spans and counters, on hand-built runs:
values computed by hand, and None where the engine keeps nothing to read
(an engine without spans, a run without a decode tick)."""

import types

import pytest

from chipbench import cells
from chipbench.serve import Run
from repro.serve.engine import EngineStats

HOST = {
    "engine.evict": [11, 0.002],
    "engine.admit.prefill": [2, 0.030],
    "engine.admit.wait": [2, 0.400],
    "engine.tick.prepare": [10, 0.010],
    "engine.tick.dispatch": [10, 0.020],
    "engine.tick.wait": [10, 3.500],
    "engine.tick.commit": [10, 0.005],
    "engine.gc": [3, 0.0125],
}


def _run(stats) -> Run:
    return Run(cfg=None, seconds=4.0, setup_s=1.0, results=[], stats=stats,
               close_s=4.0, trace=None, device_kind="cpu")


def _read(metric, stats):
    return cells.reader(metric)(_run(stats))


def test_readers_on_a_hand_built_run():
    stats = EngineStats(decode_steps=10, prefills=2, host=HOST, compiles=2,
                        compile_s=0.25)
    # (0.002 + 0.030 + 0.010 + 0.020 + 0.005) s over 10 ticks = 6.7 ms
    assert _read("host_ms_per_tick.decode", stats) == pytest.approx(6.7)
    assert _read("compiles_in_window.decode", stats) == 2
    assert _read("gc_ms_in_window.decode", stats) == pytest.approx(12.5)


def test_no_collection_reads_zero():
    host = {k: v for k, v in HOST.items() if k != "engine.gc"}
    stats = EngineStats(decode_steps=10, host=host)
    assert _read("gc_ms_in_window.decode", stats) == 0.0
    assert _read("compiles_in_window.decode", stats) == 0


def test_no_tick_reads_none():
    stats = EngineStats(decode_steps=0, prefills=1,
                        host={"engine.admit.prefill": [1, 0.01]})
    assert _read("host_ms_per_tick.decode", stats) is None


@pytest.mark.parametrize("metric", ["host_ms_per_tick.decode",
                                    "compiles_in_window.decode",
                                    "gc_ms_in_window.decode"])
def test_an_engine_without_spans_reads_none(metric):
    # the counters an engine kept before it had spans
    stats = types.SimpleNamespace(decode_steps=10, prefills=2, tokens_generated=160,
                                  timeouts=0, elapsed_s=4.0, occupancy=[16] * 10,
                                  peak_pages_in_use=40)
    assert _read(metric, stats) is None


def test_host_summary_finds_the_stalls_inside_the_window():
    from chipbench.serve import host_summary

    stats = EngineStats(decode_steps=10, host=HOST)
    results = [types.SimpleNamespace(token_times_s=[0.0, 0.02, 1.02, 1.04]),
               types.SimpleNamespace(token_times_s=[0.02, 1.04, 1.06, 2.5, 4.5])]
    # tokens at 0, 0.02, 1.02, 1.04, 1.06, 2.5 inside a window closed at 4.0
    out = host_summary(stats, results, close_s=4.0)
    assert out["tick_wait_s"] == 3.5 and out["ticks"] == 10
    assert out["stalls"] == 2
    assert out["stall_s"] == pytest.approx(1.0 + 1.44)
    assert out["longest_gap_s"] == pytest.approx(1.44)
