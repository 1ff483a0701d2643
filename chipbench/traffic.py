"""The one traffic generator: turns a traffic mix (``traffic/<name>.json``)
and a seed into the program's requests.

Lengths are lognormal (``median``, ``sigma``) truncated to ``[min, max]``:
lengths outside are dropped, as a dataset sampler drops them, not piled on
the bounds.  Arrivals are a backlog queued at t=0.  Lengths are stratified:
every block of ``strata`` requests holds the same ``strata`` quantiles of
each distribution, in an order drawn from the seed.  So every seed offers
the same work, in another order, and a window that serves k blocks serves
the same lengths on every seed.  Token ids are uniform over the vocabulary.
"""

from __future__ import annotations

import statistics

import numpy as np

NORMAL = statistics.NormalDist()


def _seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def length_quantiles(spec: dict, strata: int) -> np.ndarray:
    """The ``strata`` mid-quantiles of the truncated lognormal ``spec``."""
    lo, hi = (NORMAL.cdf(np.log(spec[k] / spec["median"]) / spec["sigma"])
              for k in ("min", "max"))
    z = [NORMAL.inv_cdf(lo + (i + 0.5) / strata * (hi - lo)) for i in range(strata)]
    vals = np.round(spec["median"] * np.exp(spec["sigma"] * np.asarray(z)))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def _stratified(rng, base: np.ndarray, n: int) -> np.ndarray:
    blocks = -(-n // len(base))
    return np.concatenate([rng.permutation(base) for _ in range(blocks)])[:n]


def requests(traffic: dict, vocab: int, seed: int, seconds: float):
    """The seeded requests of one run, all due at t=0 with the window's
    close as deadline (greedy, no EOS)."""
    from repro.serve import GenerationRequest

    if traffic["arrivals"]["process"] != "backlog":
        raise ValueError(f"unknown arrival process {traffic['arrivals']['process']!r}")
    strata, n = traffic["strata"], traffic["arrivals"]["requests"]
    prompts = _stratified(_seed_rng(seed, 2),
                          length_quantiles(traffic["prompt_tokens"], strata), n)
    outputs = _stratified(_seed_rng(seed, 3),
                          length_quantiles(traffic["output_tokens"], strata), n)
    ids = _seed_rng(seed, 4).integers(0, vocab, size=int(prompts.sum()))
    starts = np.concatenate([[0], np.cumsum(prompts)])
    return [
        GenerationRequest(
            request_id=i, prompt=tuple(int(t) for t in ids[starts[i]:starts[i + 1]]),
            max_new_tokens=int(outputs[i]), arrival_s=0.0, deadline_s=seconds,
        )
        for i in range(n)
    ]
