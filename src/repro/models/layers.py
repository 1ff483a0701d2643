"""Foundational layers: RMSNorm, RoPE, GQA attention (train / prefill /
decode-with-KV-cache), SwiGLU MLP, MoE FFN with top-k routing.

Pure-JAX functional style: every layer is an ``init_*`` returning a param
pytree + an ``apply`` function.  Activations carry logical sharding
annotations (:func:`repro.parallel.sharding.lshard`) so the same code runs
single-device (no-op) and under the production meshes.

Dtype policy (mixed precision): parameters live in ``param_dtype`` (fp32 by
default), compute runs in ``compute_dtype`` (bf16), softmax/normalizers and
the loss in fp32.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.parallel.sharding import active_mesh, lshard


@dataclasses.dataclass(frozen=True)
class DTypes:
    param: jnp.dtype = jnp.float32
    compute: jnp.dtype = jnp.bfloat16


DEFAULT_DTYPES = DTypes()


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LLM pretrain setups)."""
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


# ------------------------------------------------------------------- RMSNorm
def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"norm_scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * params["norm_scale"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------- RoPE
def rope_angles(positions, head_dim: int, theta: float):
    """(..., hd/2) rotation angles for integer positions."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    return positions[..., None].astype(jnp.float32) * freqs  # (..., hd/2)


def apply_rope(x, positions, theta: float):
    """x: (b, s, h, hd); positions: (b, s) or (s,)."""
    hd = x.shape[-1]
    ang = rope_angles(positions, hd, theta)  # (b, s, hd/2) or (s, hd/2)
    if ang.ndim == 2:
        ang = ang[None, :, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    xr = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return xr.reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------------------- attention
def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                   dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, n_heads * head_dim), dtype=dtype),
        "wk": dense_init(kk, (d_model, n_kv_heads * head_dim), dtype=dtype),
        "wv": dense_init(kv, (d_model, n_kv_heads * head_dim), dtype=dtype),
        "wo": dense_init(ko, (n_heads * head_dim, d_model), dtype=dtype),
    }


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(
        b, s, kv * n_rep, hd
    )


def attention_scores(q, k, v, mask, compute_dtype=jnp.bfloat16):
    """q: (b, sq, H, hd), k/v: (b, sk, H, hd); mask broadcastable to
    (b, H, sq, sk) (True = attend).  fp32 softmax."""
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / np.sqrt(hd)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(compute_dtype), v.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return out.astype(compute_dtype)


def attention_fwd(
    params,
    x,
    positions,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e4,
    causal: bool = True,
    kv_override=None,          # cross-attention: (k_src, v_src) already projected
    attn_impl: str = "xla",    # "xla" | "chunked" (sub-quadratic memory)
    chunk: int = 1024,
    use_rope: bool = True,     # False: absolute-position models (whisper)
):
    """Full-sequence attention (training / prefill)."""
    b, s, _ = x.shape
    cd = x.dtype
    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(cd)), n_heads, head_dim)
    if kv_override is None:
        k = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(cd)), n_kv_heads, head_dim)
        v = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(cd)), n_kv_heads, head_dim)
        if use_rope:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
    else:
        k, v = kv_override
    q = lshard(q, "batch", "seq", "heads", "head_dim")
    k = lshard(k, "batch", None, "kv_heads", "head_dim")
    v = lshard(v, "batch", None, "kv_heads", "head_dim")
    k = _repeat_kv(k, n_heads // k.shape[2])
    v = _repeat_kv(v, n_heads // v.shape[2])

    sk = k.shape[1]
    if attn_impl == "chunked" and s > chunk:
        out = _chunked_attention(q, k, v, causal, chunk)
    else:
        if causal:
            mask = jnp.tril(jnp.ones((s, sk), dtype=bool), k=sk - s)[None, None]
        else:
            mask = jnp.ones((1, 1, s, sk), dtype=bool)
        out = attention_scores(q, k, v, mask, compute_dtype=cd)
    out = lshard(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(b, s, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd))


def _chunked_attention(q, k, v, causal: bool, chunk: int):
    """Flash-style O(s) memory attention: scan over KV chunks with an online
    softmax; the XLA counterpart of the Pallas kernel (kernels/flash_attention)."""
    b, s, h, hd = q.shape
    sk = k.shape[1]
    n_chunks = (sk + chunk - 1) // chunk
    pad = n_chunks * chunk - sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kp.reshape(b, n_chunks, chunk, h, hd)
    vc = vp.reshape(b, n_chunks, chunk, h, hd)
    q32 = q.astype(jnp.float32) / np.sqrt(hd)
    qpos = jnp.arange(s) + (sk - s)  # align to causal offset

    def body(carry, inp):
        m, l, acc = carry
        kci, vci, ci = inp
        kpos = ci * chunk + jnp.arange(chunk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, kci.astype(jnp.float32))
        valid = (kpos < sk)[None, None, None, :]
        if causal:
            valid = valid & (qpos[None, None, :, None] >= kpos[None, None, None, :])
        scores = jnp.where(valid, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vci.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, h, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, acc0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 2).astype(q.dtype)  # (b, s, h, hd)


# --------------------------------------------------------------- KV caching
def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
    }


def attention_decode(
    params,
    x,                 # (b, 1, d)
    cache,             # {"k","v"} (b, L, K, hd)
    index,             # scalar int32: write position (= current length)
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e4,
    update_cache: bool = True,
    window: int = 0,   # sliding window size (0 = full)
    use_rope: bool = True,
):
    """Single-token decode with KV cache; O(L) compute, O(1) state growth."""
    b = x.shape[0]
    cd = x.dtype
    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(cd)), n_heads, head_dim)
    pos = jnp.full((b, 1), index, dtype=jnp.int32)
    if use_rope:
        q = apply_rope(q, pos, rope_theta)
    if update_cache:
        k_new = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(cd)), n_kv_heads, head_dim)
        v_new = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(cd)), n_kv_heads, head_dim)
        if use_rope:
            k_new = apply_rope(k_new, pos, rope_theta)
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, index, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, index, 0, 0)),
        }
    k, v = cache["k"], cache["v"]
    L = k.shape[1]
    kpos = jnp.arange(L)
    valid = kpos <= index
    if window:
        valid = valid & (kpos > index - window)

    # flash-decoding path: when GQA heads do not divide the TP axis, the KV
    # cache is sharded on the *sequence* dim; computing scores against a
    # heads-sharded q would force XLA to all-gather the whole cache (GBs per
    # token).  Instead keep scores seq-sharded (partial attention per shard)
    # -- the softmax/normalizer all-reduces and the (b,1,H,hd) output
    # reduction move only KBs.
    mesh = active_mesh()
    seq_flash = (
        mesh is not None
        and "model" in mesh.axis_names
        and n_kv_heads % mesh.shape["model"] != 0
        and L % mesh.shape["model"] == 0
    )
    if seq_flash:
        from repro.parallel.sharding import data_axis_names, pshard

        da = data_axis_names()
        k = pshard(k, da, "model", None, None)
        v = pshard(v, da, "model", None, None)
        k = _repeat_kv(k.astype(cd), n_heads // n_kv_heads)
        v = _repeat_kv(v.astype(cd), n_heads // n_kv_heads)
        q_r = pshard(q, da, None, None, None)  # replicate q heads
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q_r, k, preferred_element_type=jnp.float32
        ) / np.sqrt(head_dim)
        scores = jnp.where(valid[None, None, None, :], scores, -1e30)
        scores = pshard(scores, da, None, None, "model")  # seq-sharded
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", probs.astype(cd), v, preferred_element_type=jnp.float32
        ).astype(cd)
        out = pshard(out, da, None, None, None)
    else:
        k = lshard(k, "batch", None, "kv_heads", "head_dim")
        v = lshard(v, "batch", None, "kv_heads", "head_dim")
        k = _repeat_kv(k.astype(cd), n_heads // n_kv_heads)
        v = _repeat_kv(v.astype(cd), n_heads // n_kv_heads)
        mask = valid[None, None, None, :]
        out = attention_scores(q, k, v, mask, compute_dtype=cd)  # (b,1,H,hd)
    out = out.reshape(b, 1, n_heads * head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd)), cache


# ------------------------------------------------------- paged KV attention
#
# Serving variant of the cache (DESIGN.md §7): instead of one dense
# (batch, max_len, ...) buffer per layer, KV lives in fixed-size *pages*
# shared by all sequences -- {"k","v"}: (n_layers, n_pages, page_size, K*hd),
# every kv head merged into the minor dimension so a page is one unpadded
# (page_size, K*hd) block -- and each sequence owns an ordered *block table*
# of page ids.  Logical position ``p`` of a sequence maps to physical slot
# ``table[p // ps] * ps + p % ps`` of each layer.  The allocator/bookkeeping
# lives in :mod:`repro.serve.kv_cache`; these functions are the compute:
# scatter new KV rows into a layer's pages (in place in the pool the layer
# scan carries), and attend through :func:`repro.kernels.ops.paged_decode_attention`
# -- the Pallas kernel on a TPU, which reads only each lane's live pages;
# elsewhere the XLA gather path with the same fp32-softmax math as the dense
# path (so paged and dense decode are token-identical -- the engine
# equivalence tests rely on it).


def init_paged_kv(n_pages: int, page_size: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16):
    shape = (n_pages, page_size, n_kv_heads * head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _paged_scatter(pool, layer, rows, slots):
    """Write ``rows`` (n, K*hd) at layer ``layer``'s flat slots (n,) of
    ``pool`` (n_layers, n_pages, ps, K*hd); out-of-range slots (inactive
    lanes / padding) are dropped, not clamped."""
    n_layers, n_pages, ps, kd = pool.shape
    flat = pool.reshape(n_layers, n_pages * ps, kd)
    flat = flat.at[layer, slots].set(rows.astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def attention_decode_paged(
    params,
    x,                 # (b, 1, d) -- one new token per lane
    pages,             # {"k","v"}: (n_layers, n_pages, page_size, K*hd)
    layer,             # scalar int32: this layer's index into the pool
    block_table,       # (b, max_blocks) int32 page ids, -1 = unallocated
    lengths,           # (b,) int32: tokens already cached per lane
    active,            # (b,) bool: lane holds a live sequence
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e4,
    use_rope: bool = True,
):
    """Single-token decode against a paged KV cache.

    Unlike :func:`attention_decode` (one scalar write index for the whole
    batch) every lane carries its own length, which is what lets the engine
    admit requests mid-flight: lane i writes at logical position
    ``lengths[i]`` and attends positions ``<= lengths[i]``.  Inactive lanes
    are masked out of the scatter entirely (their block tables are empty).
    """
    b = x.shape[0]
    cd = x.dtype
    n_pages, ps = pages["k"].shape[1:3]
    max_blocks = block_table.shape[1]
    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(cd)), n_heads, head_dim)
    k_new = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(cd)), n_kv_heads, head_dim)
    v_new = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(cd))
    pos = lengths[:, None]  # (b, 1)
    if use_rope:
        q = apply_rope(q, pos, rope_theta)
        k_new = apply_rope(k_new, pos, rope_theta)

    write_block = jnp.take_along_axis(
        block_table, (lengths // ps)[:, None] % max_blocks, axis=1
    )[:, 0]
    slots = write_block * ps + lengths % ps
    slots = jnp.where(active & (write_block >= 0), slots, n_pages * ps)  # drop
    new_pages = {
        "k": _paged_scatter(pages["k"], layer, k_new.reshape(b, -1), slots),
        "v": _paged_scatter(pages["v"], layer, v_new[:, 0], slots),
    }
    out = ops.paged_decode_attention(
        q[:, 0], new_pages["k"], new_pages["v"], layer, block_table, lengths, active)
    proj = jnp.einsum("bsh,hd->bsd", out[:, None], params["wo"].astype(cd))
    return proj, new_pages


def attention_prefill_paged(
    params,
    x,                 # (1, S, d) -- padded prompt for one sequence
    pages,             # {"k","v"}: (n_layers, n_pages, page_size, K*hd)
    layer,             # scalar int32: this layer's index into the pool
    block_table,       # (max_blocks,) int32 page ids, -1 = unallocated
    length,            # scalar int32: true prompt length (<= S)
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e4,
    use_rope: bool = True,
):
    """Full-prompt prefill for one sequence, scattering its KV into pages.

    The prompt is padded to a bucketed S (bounding jit retraces); causal
    masking means padding positions never influence positions < ``length``,
    and their KV is dropped from the scatter, so pages hold exactly the
    ``length`` real tokens afterwards.
    """
    _, s, _ = x.shape
    cd = x.dtype
    n_pages, ps = pages["k"].shape[1:3]
    positions = jnp.arange(s)[None, :]
    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(cd)), n_heads, head_dim)
    k = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(cd)), n_kv_heads, head_dim)
    v = _split_heads(jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(cd)), n_kv_heads, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    pos = jnp.arange(s)
    blocks = block_table[(pos // ps) % block_table.shape[0]]
    slots = blocks * ps + pos % ps
    slots = jnp.where((pos < length) & (blocks >= 0), slots, n_pages * ps)
    new_pages = {
        "k": _paged_scatter(pages["k"], layer, k[0].reshape(s, -1), slots),
        "v": _paged_scatter(pages["v"], layer, v[0].reshape(s, -1), slots),
    }

    kr = _repeat_kv(k, n_heads // n_kv_heads)
    vr = _repeat_kv(v, n_heads // n_kv_heads)
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None]
    out = attention_scores(q, kr, vr, mask, compute_dtype=cd)
    out = out.reshape(1, s, n_heads * head_dim)
    proj = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd))
    return proj, new_pages


# -------------------------------------------------------------------- SwiGLU
def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    kg, ki, ko = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(kg, (d_model, d_ff), dtype=dtype),
        "w_in": dense_init(ki, (d_model, d_ff), dtype=dtype),
        "w_out": dense_init(ko, (d_ff, d_model), dtype=dtype),
    }


def mlp_fwd(params, x):
    cd = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(cd))
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"].astype(cd))
    g = lshard(g, "batch", "seq", "ffn")
    act = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * h
    return jnp.einsum("bsf,fd->bsd", act, params["w_out"].astype(cd))


# ----------------------------------------------------------------------- MoE
def init_moe(key, d_model: int, n_experts: int, d_expert: int, dtype=jnp.float32):
    kr, kg, ki, ko = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, (d_model, n_experts), dtype=jnp.float32),
        "w_gate": dense_init(kg, (n_experts, d_model, d_expert), in_axis=1, dtype=dtype),
        "w_in": dense_init(ki, (n_experts, d_model, d_expert), in_axis=1, dtype=dtype),
        "w_out": dense_init(ko, (n_experts, d_expert, d_model), in_axis=1, dtype=dtype),
    }


def moe_fwd(params, x, *, top_k: int, capacity_factor: float = 1.25,
            group_size: int = 512, return_aux: bool = False):
    """Token-choice top-k MoE with *grouped* capacity-based dense dispatch
    (the GSPMD-canonical formulation).

    Tokens are blocked into groups of ``group_size``; capacity and the
    one-hot dispatch/combine tensors are per-group, so their footprint is
    O(groups * group_size * E * capacity) instead of O(total_tokens^2 / E).
    Under pjit with experts sharded over `model` and groups over the data
    axes, XLA lowers dispatch/combine einsums to all-to-all -- the EP
    traffic modeled by ``v_e`` in the comm matrix.
    """
    b, s, d = x.shape
    E = params["router"].shape[-1]
    n_tokens = b * s
    gs = min(group_size, n_tokens)
    while n_tokens % gs:
        gs //= 2  # shapes in this framework are powers of two
    G = n_tokens // gs
    xt = x.reshape(G, gs, d)
    xt = lshard(xt, "batch", None, "embed")

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)          # (G, gs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = max(4, int(np.ceil(top_k * gs / E * capacity_factor)))

    # position of each (token, k) within its expert's per-group queue
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)      # (G, gs, k, E)
    flat = onehot.reshape(G, gs * top_k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(G, gs, top_k, E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)               # (G, gs, k)
    keep = pos < capacity

    pos_oh = jax.nn.one_hot(pos, capacity, dtype=xt.dtype) * keep[..., None].astype(xt.dtype)
    sel = onehot.astype(xt.dtype)[..., None] * pos_oh[:, :, :, None, :]  # (G,gs,k,E,C)
    dispatch = sel.sum(axis=2)                                    # (G, gs, E, C)
    combine = jnp.einsum("gtk,gtkec->gtec", gate_vals.astype(xt.dtype), sel)

    xe = jnp.einsum("gtd,gtec->gecd", xt, dispatch)               # (G, E, C, d)
    xe = lshard(xe, "batch", "experts", None, "embed")
    g = jnp.einsum("gecd,edf->gecf", xe, params["w_gate"].astype(xt.dtype))
    h = jnp.einsum("gecd,edf->gecf", xe, params["w_in"].astype(xt.dtype))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * h
    ye = jnp.einsum("gecf,efd->gecd", act, params["w_out"].astype(xt.dtype))
    ye = lshard(ye, "batch", "experts", None, "embed")
    out = jnp.einsum("gecd,gtec->gtd", ye, combine).reshape(b, s, d)

    if return_aux:
        # load-balancing auxiliary loss (Switch-style), over all tokens
        me = probs.reshape(n_tokens, E).mean(axis=0)
        ce = onehot.reshape(n_tokens, top_k, E).sum(axis=1).mean(axis=0).astype(jnp.float32)
        aux = E * jnp.sum(me * ce)
        return out, aux
    return out
