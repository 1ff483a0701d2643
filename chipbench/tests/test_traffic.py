"""The generator: the same seed gives the same requests; every seed gives
the same lengths block by block, in another order; the lengths follow the
source the mix names."""

import collections

import numpy as np
import pytest

from chipbench import cells, traffic
from chipbench.tests.helpers import SERVE_CELLS


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_same_seed_same_requests(name):
    mix = cells.load(name).traffic
    a = traffic.requests(mix, 1000, 2**31 + 17, 30.0)
    b = traffic.requests(mix, 1000, 2**31 + 17, 30.0)
    assert a == b
    c = traffic.requests(mix, 1000, 2**31 + 18, 30.0)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_every_block_holds_the_same_lengths(name):
    mix = cells.load(name).traffic
    k = mix["strata"]
    blocks = collections.Counter()
    for seed in (3, 4, 2**40):
        reqs = traffic.requests(mix, 1000, seed, 400.0)
        full = len(reqs) // k * k
        for i in range(0, full, k):
            block = reqs[i:i + k]
            blocks[(tuple(sorted(len(r.prompt) for r in block)),
                    tuple(sorted(r.max_new_tokens for r in block)))] += 1
        for r in reqs:
            assert mix["prompt_tokens"]["min"] <= len(r.prompt) <= mix["prompt_tokens"]["max"]
            assert mix["output_tokens"]["min"] <= r.max_new_tokens <= mix["output_tokens"]["max"]
            assert all(0 <= t < 1000 for t in r.prompt)
    assert len(blocks) == 1


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_lengths_keep_the_published_means_and_fit_the_context(name):
    mix = cells.load(name).traffic
    eng = mix["engine"]
    for key in ("prompt_tokens", "output_tokens"):
        spec = mix[key]
        got = traffic.length_quantiles(spec, mix["strata"]).mean()
        assert abs(got - spec["published_mean"]) <= 0.01 * spec["published_mean"]
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= eng["max_blocks"] * eng["page_size"])


def test_truncation_drops_the_tail_rather_than_piling_it_on_the_bound():
    spec = {"median": 100.0, "sigma": 1.0, "min": 4, "max": 200}
    q = traffic.length_quantiles(spec, 16)
    assert list(q) == sorted(q) and q.max() < 200 and len(set(q)) == 16
    assert np.all(np.diff(q) > 0)


def test_backlog_waits_at_zero_with_the_window_as_deadline():
    mix = cells.load("minicpm-2b.decode-backlog").traffic
    reqs = traffic.requests(mix, 1000, 5, 12.0)
    assert len(reqs) == mix["arrivals"]["requests"]
    assert {r.arrival_s for r in reqs} == {0.0}
    assert {r.deadline_s for r in reqs} == {12.0}
