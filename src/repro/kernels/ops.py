"""jit'd public wrappers over the Pallas kernels.  ``impl`` picks the
implementation: ``"auto"`` runs the compiled Pallas kernel on a TPU and the
pure-jnp reference on any other backend; ``"pallas"`` is the compiled kernel
and raises without a TPU; ``"interpret"`` runs the kernel in Pallas
interpret mode (tests); ``"ref"`` is the reference.

These are the entry points model code / hillclimbing configs call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _fa_pallas
from repro.kernels.paged_decode import paged_decode_attention as _pd_pallas
from repro.kernels.rmsnorm import rmsnorm as _rms_pallas
from repro.kernels.ssd_chunk import ssd_chunk_scan as _ssd_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(impl: str) -> bool:
    """Interpret mode for ``impl="interpret"``; the compiled kernel for
    ``"pallas"`` (or ``"auto"`` on a TPU), which needs a TPU."""
    if impl == "interpret":
        return True
    if impl not in ("pallas", "auto"):
        raise ValueError(f"unknown impl {impl!r}")
    if not _on_tpu():
        raise RuntimeError(
            f'impl="pallas" needs a TPU, JAX backend is {jax.default_backend()!r} '
            '(impl="interpret" runs the kernel in interpret mode)')
    return False


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "impl"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, impl: str = "auto"):
    """Batched GQA flash attention.  impl: auto|pallas|interpret|ref."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _fa_pallas(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                      interpret=_interpret(impl))


def paged_decode_impl(page_size: int, kv_width: int, impl: str = "auto") -> str:
    """What ``impl="auto"`` runs for a pool of ``page_size`` tokens a page
    and ``kv_width = K*hd``: the kernel on a TPU where a page is whole
    (16, 128) tiles, else the XLA gather path."""
    if impl != "auto":
        return impl
    fits = page_size % 16 == 0 and kv_width % 128 == 0
    return "pallas" if _on_tpu() and fits else "ref"


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_decode_attention(q, k_pool, v_pool, layer, block_table, lengths,
                           active, impl: str = "auto"):
    """One decode token per lane against the paged pool (signature in
    kernels/paged_decode.py).  impl: auto|pallas|interpret|ref."""
    impl = paged_decode_impl(k_pool.shape[2], k_pool.shape[3], impl)
    if impl == "ref":
        return ref.paged_decode_attention_ref(q, k_pool, v_pool, layer,
                                              block_table, lengths, active)
    return _pd_pallas(q, k_pool, v_pool, layer, block_table, lengths, active,
                      interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "impl"))
def rmsnorm(x, scale, eps: float = 1e-5, block_rows: int = 256, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.rmsnorm_ref(x, scale, eps)
    return _rms_pallas(x, scale, eps=eps, block_rows=block_rows,
                       interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_chunk_scan(x, B, C, dt, loga, chunk: int = 128, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        # vmap the per-(b,h) reference over batch and heads, scan over chunks
        b, H, s, P = x.shape
        N = B.shape[-1]
        cs = min(chunk, s)
        n = s // cs

        def per_bh(xbh, Bbh, Cbh, dtbh, logabh):
            def body(S, inp):
                xc, Bc, Cc, dtc, lac = inp
                y, S = ref.ssd_chunk_ref(xc, Bc, Cc, dtc, lac, S)
                return S, y

            S0 = jnp.zeros((P, N), jnp.float32)
            S, ys = jax.lax.scan(
                body, S0,
                (xbh.reshape(n, cs, P).astype(jnp.float32),
                 Bbh.reshape(n, cs, N).astype(jnp.float32),
                 Cbh.reshape(n, cs, N).astype(jnp.float32),
                 dtbh.reshape(n, cs).astype(jnp.float32),
                 logabh.reshape(n, cs).astype(jnp.float32)),
            )
            return ys.reshape(s, P).astype(x.dtype), S

        return jax.vmap(jax.vmap(per_bh))(x, B, C, dt, loga)
    return _ssd_pallas(x, B, C, dt, loga, chunk=chunk, interpret=_interpret(impl))
