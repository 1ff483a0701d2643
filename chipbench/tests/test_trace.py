"""The trace reduction: interval arithmetic, and the whole reduction on a
small trace of the decode-backlog cell recorded on a TPU v5e."""

import json
import pathlib

import numpy as np

import pytest

from chipbench import trace as tr

RECORDED = pathlib.Path(__file__).with_name("data") / "decode_backlog_trace.json"


def test_union_merges_and_clips():
    got = tr.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 0, 25)
    assert got == [(0, 3), (5, 12), (20, 25)]


def test_gaps_are_the_complement():
    busy = [(0, 3), (5, 12), (20, 25)]
    assert tr.gaps(busy, 0, 30) == [(3, 5), (12, 20), (25, 30)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_reduce_on_a_made_up_trace():
    dev = {"ops": [["a", 0, 10], ["b", 5, 10], ["a", 30, 10], ["c", 95, 20]],
           "modules": [["jit_decode_fn(1)", 0, 15], ["jit_prefill_fn(2)", 30, 10]]}
    events = {"devices": {"/device:TPU:0": dev, "/device:TPU:1": {
                  "ops": [["a", 0, 50]], "modules": []}},
              "host": [["engine.tick", 10, 30], ["engine.admit", 14, 2],
                       ["program.decode", 45, 40]]}
    s = tr.reduce(events, window_ns=100)
    assert s.window_s == pytest.approx(100e-9)
    # chip 0 busy 15 + 10 + 5 (clipped), chip 1 busy 50: mean 40
    assert s.busy_s == pytest.approx(40e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.programs["decode_fn"] == (pytest.approx(15e-9), 1)
    assert s.programs["prefill_fn"] == (pytest.approx(10e-9), 1)
    assert s.device_ops[0] == ("a", pytest.approx(35e-9))
    # chip 0's gaps: 15-30 (tick covers most), 40-95 (decode dispatch)
    assert s.idle_gaps == [("program.decode", pytest.approx(55e-9)),
                           ("engine.tick", pytest.approx(15e-9))]


def _raster_busy_ns(ops, window_ns, step=100):
    """Busy time counted the slow way: 100 ns cells covered by any op."""
    cells = np.zeros(int(window_ns // step), bool)
    for _, s, d in ops:
        lo, hi = max(0, int(s // step)), min(len(cells), int(-(-(s + d) // step)))
        cells[lo:hi] = True
    return cells.sum() * step


def test_reduce_on_a_recorded_trace():
    rec = json.loads(RECORDED.read_text())
    ev, window = rec["events"], rec["window_ns"]
    s = tr.reduce(ev, window)
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    assert s.busy_s * 1e9 == pytest.approx(_raster_busy_ns(ops, window), rel=2e-3)
    assert 0.5 < s.busy_s / s.window_s < 1.0
    # three prefill programs overlap the window, the first two clipped
    assert s.programs["prefill_fn"][1] == 3
    assert s.programs["decode_fn"] == (0.0, 0)
    inside = sum(min(st + d, window) - max(st, 0)
                 for n, st, d in ev["devices"]["/device:TPU:0"]["modules"]
                 if "prefill_fn" in n)
    assert s.programs["prefill_fn"][0] == pytest.approx(inside * 1e-9)
    assert not any(label.startswith("while") for label, _ in s.device_ops)
    assert s.idle_gaps[0][0] in ("engine.admit", "program.prefill")
