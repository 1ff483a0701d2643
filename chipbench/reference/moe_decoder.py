"""Plain float32 reference of a decoder-only LM whose feed-forward block is a
token-choice top-k mixture of experts, importing nothing of the program.
Embedding, attention, norms and head are ``decoder.py``'s; per layer, in
place of the SwiGLU MLP:

    h = rmsnorm(x) * g2;  p = softmax(h Wr)              (E experts)
    T = the top_k largest p;  w_e = p_e / sum_T p  for e in T, else 0
    x = x + sum_e w_e (silu(h Wg_e) * (h Wi_e)) Wout_e

Every expert runs on every token and ``w`` picks: no token is dropped, so a
program compared with it has to keep every token it routes (a capacity of
at least E / top_k).  ``top_k`` is the configuration file's (``"program"``);
the number and width of the experts are those of the weights.
``quant="fp8"`` rounds every matrix product's operands as ``decoder.py``
does, the router's included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import decoder

F32 = jnp.float32


def make_logits_fn(conf: dict, quant=None, q_block: int = 512):
    """``decoder.make_logits_fn`` with the experts as the feed-forward block."""
    top_k = conf["program"]["top_k"]

    def experts(lp, h, q):
        m = {k: v.astype(F32) for k, v in lp["moe"].items()}
        p = jax.nn.softmax(jnp.dot(q(h), q(m["router"])), -1)          # (S, E)
        top, idx = jax.lax.top_k(p, top_k)
        rows = jnp.arange(h.shape[0])[:, None]
        w = jnp.zeros_like(p).at[rows, idx].set(top / top.sum(-1, keepdims=True))
        hq = q(h)
        g = jnp.einsum("sd,edf->sef", hq, q(m["w_gate"]))
        i = jnp.einsum("sd,edf->sef", hq, q(m["w_in"]))
        act = jax.nn.silu(g) * i * w[:, :, None]
        # the weighted sum over experts is one contraction over (e, f)
        return jnp.einsum("sef,efd->sd", q(act), q(m["w_out"]))

    return decoder.make_logits_fn(conf, quant, q_block, ffn=experts)
