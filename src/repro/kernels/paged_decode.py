"""Paged decode attention Pallas TPU kernel: one query token per lane against
the serve engine's paged KV pool, reading only each lane's live pages.

Pool layout (DESIGN.md §7): ``(n_layers, n_pages, page_size, K*hd)`` with
all kv heads merged into the minor dimension, so one page is one unpadded
``(page_size, K*hd)`` tile block.  For lane ``i`` the kernel DMAs pages
``j < ceil((lengths[i] + 1) / page_size)`` of its block table, K and V,
whole pages with every head at once, double-buffered across compute blocks
and across lanes; an inactive lane reads nothing and returns zeros.

Heads stay merged in VMEM too.  The lane's queries become a block-diagonal
``(H, K*hd)`` matrix (row ``r*K + g`` holds q head ``g*G + r`` in the
columns of kv head ``g``, ``G = H/K``), so one MXU pass gives every head's
scores against a block of pages and one more gives ``p @ V`` for every
head; the block-diagonal part of the ``(H, K*hd)`` accumulator is the
output.  GQA is that row mapping: K and V are never repeated.  Scores,
the online softmax (``m``, ``l``) and the accumulator are float32.

Grid: one step per lane ("arbitrary": the DMA of the next lane's first
block is started inside the previous lane's last one).  The wrapper works
out which pages each lane reads and which positions it attends, so the
kernel body, traced on the host at every set-up, stays small.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: positions per compute block: one full MXU tile of keys
BLOCK_POSITIONS = 128


def _kernel(layer_ref, table_ref, n_blocks_ref, next_ref,
            q_ref, ok_ref, tile_ref, mask_ref, sel_ref, k_hbm, v_hbm,
            o_ref,
            k_buf, v_buf, sems, qbd_ref, m_ref, l_ref, acc_ref, slot_ref,
            *, page_size: int, pages_per_block: int, blocks_per_lane: int,
            n_lanes: int, sm_scale: float):
    lane = pl.program_id(0)
    layer = layer_ref[0]
    ps, ppb = page_size, pages_per_block
    rows = ps * ppb

    def dma(ln, blk, slot, wait: bool):
        """Start (or wait for) the K and V copies of block ``blk`` of lane
        ``ln``: one per page the table marks as read (id >= 0)."""
        base = (ln * blocks_per_lane + blk) * ppb

        def page(p, carry):
            pid = table_ref[base + p]

            @pl.when(pid >= 0)
            def _():
                rows_p = pl.ds(pl.multiple_of(p * ps, ps), ps)
                for kv, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                    c = pltpu.make_async_copy(pool.at[layer, pid],
                                              buf.at[slot, rows_p],
                                              sems.at[kv, slot])
                    c.wait() if wait else c.start()
            return carry

        jax.lax.fori_loop(0, ppb, page, 0)

    @pl.when(lane == 0)
    def _first_fetch():
        # rows of pages that are not read keep stale values: make those
        # finite, so masked probabilities (exact zeros) cancel them
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        first = next_ref[0]

        @pl.when(first < n_lanes)
        def _():
            dma(first, 0, 0, wait=False)

    n_blocks = n_blocks_ref[lane]

    @pl.when(n_blocks == 0)
    def _inactive():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _active():
        # block-diagonal queries: (q tiled across kv heads) * head mask
        tiled = jnp.dot(q_ref[0], tile_ref[...],
                        preferred_element_type=jnp.float32)
        qbd_ref[...] = (tiled * mask_ref[...].astype(jnp.float32)).astype(
            qbd_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        slot0 = slot_ref[0]
        nxt = next_ref[lane + 1]

        def body(blk, carry):
            slot = (slot0 + blk) % 2
            last = blk + 1 == n_blocks
            # the next block to fetch: this lane's, else the next lane's first

            @pl.when(jnp.logical_not(last) | (nxt < n_lanes))
            def _():
                dma(jnp.where(last, nxt, lane), jnp.where(last, 0, blk + 1),
                    1 - slot, wait=False)

            dma(lane, blk, slot, wait=True)
            ok = ok_ref[0, :, pl.ds(pl.multiple_of(blk * rows, rows), rows)] > 0
            s = jax.lax.dot_general(
                qbd_ref[...], k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # (H, rows)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jnp.dot(
                p.astype(v_buf.dtype), v_buf[slot],
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(0, n_blocks, body, 0)
        slot_ref[0] = (slot0 + n_blocks) % 2

        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = out.astype(o_ref.dtype) * mask_ref[...]
        # each output element is one nonzero product: exact in bf16 x bf16
        o_ref[0] = jnp.dot(sel_ref[...], out,
                           preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _head_constants(n_heads: int, n_kv_heads: int, head_dim: int, dtype):
    """0/1 matrices for the row order ``i = r*K + g`` (q head ``g*G + r``):
    ``tile`` (hd, K*hd) copies a head across every kv head's columns,
    ``mask`` (H, K*hd) keeps row i's own kv head, ``sel`` (G, H) sums the
    rows of one ``r``."""
    K, hd = n_kv_heads, head_dim
    G = n_heads // K
    col = np.arange(K * hd)
    row = np.arange(n_heads)
    tile = (col[None, :] % hd == np.arange(hd)[:, None])
    mask = (col[None, :] // hd == (row % K)[:, None])
    sel = (row[None, :] // K == np.arange(G)[:, None])
    return tuple(jnp.asarray(a, dtype) for a in (tile, mask, sel))


def paged_decode_attention(q, k_pool, v_pool, layer, block_table, lengths,
                           active, *, interpret: bool = False):
    """q: (b, H, hd) one query token per lane (RoPE applied); k_pool/v_pool:
    (n_layers, n_pages, page_size, K*hd); layer: int32 scalar;
    block_table: (b, max_blocks) page ids, -1 unallocated; lengths: (b,)
    tokens cached before this one, which is already written at position
    ``lengths[i]``; active: (b,) bool.  Returns (b, H*hd) in q's dtype:
    attention over positions ``<= lengths[i]``, zeros for inactive lanes."""
    b, H, hd = q.shape
    _, _, ps, kd = k_pool.shape
    K = kd // hd
    G = H // K
    if K * hd != kd or G * K != H:
        raise ValueError(f"q {q.shape} does not match the pool {k_pool.shape}")
    max_blocks = block_table.shape[1]
    ppb = max(1, BLOCK_POSITIONS // ps)
    blocks_per_lane = -(-max_blocks // ppb)
    width = blocks_per_lane * ppb

    # the pages each lane reads: j < ceil((lengths + 1) / ps), allocated;
    # the rest of its (padded) table reads -1
    n_live = jnp.where(active, jnp.minimum(lengths // ps + 1, max_blocks), 0)
    j = jnp.arange(width)
    table = jnp.pad(block_table, ((0, 0), (0, width - max_blocks)),
                    constant_values=-1)
    table = jnp.where(j[None, :] < n_live[:, None], table, -1)
    # positions attended: <= lengths[i], on a page that is read
    pos = jnp.arange(width * ps)
    ok = (pos[None, :] <= lengths[:, None]) & jnp.repeat(table >= 0, ps, axis=1)
    n_blocks = -(-n_live // ppb)
    # next_lane[i]: the first lane >= i with pages to read (b if none)
    lanes = jnp.arange(b + 1)
    has = jnp.concatenate([n_blocks > 0, jnp.ones((1,), bool)])
    next_lane = jax.lax.cummin(jnp.where(has, lanes, b), axis=0, reverse=True)
    # rows in the order i = r*K + g
    q_rm = q.reshape(b, K, G, hd).swapaxes(1, 2).reshape(b, H, hd)
    tile, mask, sel = _head_constants(H, K, hd, q.dtype)

    kernel = functools.partial(
        _kernel, page_size=ps, pages_per_block=ppb,
        blocks_per_lane=blocks_per_lane, n_lanes=b,
        sm_scale=1.0 / float(np.sqrt(hd)))
    const = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, width * ps), lambda i, *_: (i, 0, 0)),
            const(tile.shape), const(mask.shape), const(sel.shape),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, kd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * ps, kd), k_pool.dtype),   # K, two slots
            pltpu.VMEM((2, ppb * ps, kd), v_pool.dtype),   # V, two slots
            pltpu.SemaphoreType.DMA((2, 2)),               # (K|V, slot)
            pltpu.VMEM((H, kd), q.dtype),                  # block-diagonal q
            pltpu.VMEM((H, 1), jnp.float32),               # m: running max
            pltpu.VMEM((H, 1), jnp.float32),               # l: running denom
            pltpu.VMEM((H, kd), jnp.float32),              # acc
            pltpu.SMEM((1,), jnp.int32),                   # slot of the next block
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, G, kd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), n_blocks.astype(jnp.int32),
      next_lane.astype(jnp.int32), q_rm, ok.astype(jnp.int32)[:, None, :],
      tile, mask, sel, k_pool, v_pool)
    # (b, G, K, hd) -> q head order g*G + r
    return out.reshape(b, G, K, hd).swapaxes(1, 2).reshape(b, H * hd)
