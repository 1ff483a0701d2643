"""What decides ``correct`` for a served model.

After the window, a sample of the requests the engine finished (drawn from
the seed, the longest always in it) is run through the plain float32
reference that the configuration file names (``cells.reference``) over its
prompt and served tokens.  The number compared is the widest gap by which
a served token's reference logit lies below the reference's best logit at
that position (greedy decoding serves the program's best).  The control,
the reference computed in fp8 in the program's place, is read the same way
from the token it puts first.
"""

from __future__ import annotations

import numpy as np


def sample(results, seed: int, want_tokens: int) -> list:
    """Finished requests: the longest, then others in an order drawn from
    the seed, until ``want_tokens`` served tokens are in the sample."""
    done = [r for r in results if r.finish_reason == "length"]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.request_id))
    rest = [r for r in done if r is not longest]
    chosen, n = [longest], len(longest.tokens)
    for i in np.random.default_rng([5, seed]).permutation(len(rest)):
        if n >= want_tokens:
            break
        chosen.append(rest[i])
        n += len(rest[i].tokens)
    return chosen


def bad_results(results, max_new: dict, vocab: int) -> int:
    """Requests whose tokens leave the vocabulary, exceed what they asked
    for (``max_new`` by request id), or that finished with fewer."""
    bad = 0
    for r in results:
        want = max_new[r.request_id]
        if (any(not 0 <= t < vocab for t in r.tokens) or len(r.tokens) > want
                or (r.finish_reason == "length" and len(r.tokens) != want)):
            bad += 1
    return bad


def logit_gaps(params, conf: dict, chosen, seq_len: int, n_out: int,
               control: bool = False) -> dict:
    """Widest gap of the served tokens against the reference over the
    ``chosen`` results (and of the control's first choices, if asked)."""
    from chipbench import cells

    ref = cells.reference(conf)
    ctl = cells.reference(conf, quant="fp8") if control else None
    out = {"max_logit_gap": 0.0, "tokens": 0}
    if control:
        out["control_max_logit_gap"] = 0.0
    for r in chosen:
        n, m = len(r.prompt), len(r.tokens)
        seq = np.zeros(seq_len, np.int32)
        seq[:n + m - 1] = list(r.prompt) + list(r.tokens[:-1])
        pos = np.full(n_out, n - 1, np.int32)
        pos[:m] = np.arange(n - 1, n - 1 + m)
        logits = np.asarray(ref(params, seq, pos))[:m]
        best = logits.max(-1)
        served = logits[np.arange(m), np.asarray(r.tokens)]
        out["max_logit_gap"] = max(out["max_logit_gap"], float((best - served).max()))
        out["tokens"] += m
        if control:
            first = np.asarray(ctl(params, seq, pos))[:m].argmax(-1)
            gap = float((best - logits[np.arange(m), first]).max())
            out["control_max_logit_gap"] = max(out["control_max_logit_gap"], gap)
    return out
