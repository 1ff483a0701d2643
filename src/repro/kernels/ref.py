"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

These are the ground truth the kernels are validated against in
``interpret=True`` mode across shape/dtype sweeps (tests/test_kernels.py),
and the implementations the XLA path uses on non-TPU backends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention_ref(q, k, v, causal: bool = True):
    """q: (b, hq, sq, hd); k/v: (b, hkv, skv, hd); GQA by head grouping.
    fp32 softmax, output in q.dtype."""
    b, hq, sq, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, kq, preferred_element_type=jnp.float32
    ) / np.sqrt(hd)
    skv = k.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, layer, block_table, lengths,
                               active):
    """The XLA path of paged decode attention (kernels/paged_decode.py has
    the signature): gather every lane's whole block table, ``max_blocks *
    page_size`` slots, then attend with fp32 scores and softmax, bf16
    probabilities against V, positions ``<= lengths[i]`` of allocated pages.
    Inactive lanes return zeros."""
    b, H, hd = q.shape
    _, n_pages, ps, kd = k_pool.shape
    K = kd // hd
    cd = q.dtype
    safe = jnp.where(block_table >= 0, block_table, 0)
    idx = (layer * n_pages * ps + safe[:, :, None] * ps
           + jnp.arange(ps)[None, None, :]).reshape(b, -1)
    k = k_pool.reshape(-1, K, hd)[idx]  # (b, L, K, hd)
    v = v_pool.reshape(-1, K, hd)[idx]
    kpos = jnp.arange(idx.shape[1])
    valid = (kpos[None, :] <= lengths[:, None]) & jnp.repeat(block_table >= 0, ps, axis=1)
    k = jnp.repeat(k.astype(cd), H // K, axis=2)
    v = jnp.repeat(v.astype(cd), H // K, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q[:, None], k, preferred_element_type=jnp.float32
    ) / np.sqrt(hd)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(cd), v, preferred_element_type=jnp.float32,
    ).astype(cd)
    return jnp.where(active[:, None], out.reshape(b, H * hd), 0).astype(cd)


def rmsnorm_ref(x, scale, eps: float = 1e-5):
    """x: (..., d); fp32 statistics."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def ssd_chunk_ref(x, B, C, dt, loga, S0):
    """One Mamba2 SSD chunk, single (batch, head):
    x: (cs, P), B/C: (cs, N), dt/loga: (cs,), S0: (P, N) carried state.
    Returns (y (cs, P), S1 (P, N)).  All fp32."""
    cs, P = x.shape
    cum = jnp.cumsum(loga)                       # (cs,)
    decay = cum[:, None] - cum[None, :]          # (t, u)
    tri = jnp.tril(jnp.ones((cs, cs), bool))
    gate = jnp.where(tri, jnp.exp(decay), 0.0)
    cb = C @ B.T                                 # (t, u)
    w = gate * cb * dt[None, :]
    y_intra = w @ x                              # (cs, P)
    y_state = (C @ S0.T) * jnp.exp(cum)[:, None]  # (cs, P)
    w_state = jnp.exp(cum[-1] - cum) * dt        # (cs,)
    S1 = S0 * jnp.exp(cum[-1]) + jnp.einsum("u,up,un->pn", w_state, x, B)
    return y_intra + y_state, S1
