"""Mamba2 SSD chunk-scan Pallas kernel (the zamba2 hot spot).

One grid step processes one (batch, head, chunk) cell: the intra-chunk
quadratic-in-chunk attention-like matmuls run on the MXU from VMEM tiles of
(chunk x P) and (chunk x N), while the inter-chunk recurrent state S (P x N,
fp32) is carried across the sequential chunk axis in VMEM scratch --
exactly the chunkwise decomposition of ``models/zamba.mamba2_fwd``, with the
(t, u, H) gate tensor never materialized in HBM.

Grid: (batch, heads, n_chunks); chunk axis is "arbitrary" (carries S).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, y_ref, s_final_ref,
                s_scr, *, n_chunks: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (cs, P)
    B = b_ref[0, 0].astype(jnp.float32)          # (cs, N)
    C = c_ref[0, 0].astype(jnp.float32)          # (cs, N)
    rows = rows_ref[0, 0]                        # (2, cs): dt, loga as rows
    cols = cols_ref[0, 0]                        # (cs, 2): the same as columns
    dt_row, loga_row = rows[0:1, :], rows[1:2, :]
    dt_col, loga_col = cols[:, 0:1], cols[:, 1:2]
    S = s_scr[...]                               # (P, N) carried fp32 state

    # inclusive cumsum of loga along the chunk, as a column (over t) and as
    # a row (over u), by masked reductions (no in-kernel transpose)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = t_idx >= u_idx
    cum_col = jnp.sum(jnp.where(tri, loga_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(t_idx <= u_idx, loga_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(loga_row, axis=1, keepdims=True)      # (1, 1)

    gate = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    cb = jnp.dot(C, B.T, preferred_element_type=jnp.float32)
    w = gate * cb * dt_row
    y_intra = jnp.dot(w, x, preferred_element_type=jnp.float32)
    y_state = jnp.dot(C, S.T, preferred_element_type=jnp.float32) * jnp.exp(cum_col)
    y_ref[0, 0] = (y_intra + y_state).astype(y_ref.dtype)

    w_state = jnp.exp(total - cum_col) * dt_col  # (cs, 1)
    S_new = S * jnp.exp(total) + jnp.dot(
        (x * w_state).T, B, preferred_element_type=jnp.float32
    )
    s_scr[...] = S_new

    @pl.when(ic == n_chunks - 1)
    def _final():
        s_final_ref[0, 0] = S_new.astype(s_final_ref.dtype)


def ssd_chunk_scan(x, B, C, dt, loga, chunk: int = 128, interpret: bool = False):
    """x: (b, H, s, P); B/C: (b, H, s, N); dt/loga: (b, H, s).
    Returns (y (b, H, s, P), S_final (b, H, P, N))."""
    b, H, s, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    # dt/loga go in twice, as rows (b, H, 2, s) and as columns (b, H, s, 2):
    # a (1, 1, chunk) block of a rank-3 array breaks the TPU's (8, 128)
    # tiling rule, while these two blocks satisfy it.
    gates = jnp.stack([dt, loga], axis=-1).astype(jnp.float32)
    rows = jnp.swapaxes(gates, -1, -2)

    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks, chunk=chunk)
    grid = (b, H, n_chunks)
    y, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 2, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, 2), lambda ib, ih, ic: (ib, ih, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, s, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, B, C, rows, gates)
    return y, s_final
