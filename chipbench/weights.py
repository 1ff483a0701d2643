"""The served weights, made by the benchmark from the seed.

One jitted call builds every leaf on the device, in the type the model is
served in, in the parameter layout the program's ``DecoderLM`` takes.  The
plain reference reads these same arrays; nothing of them comes from the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed (``jax.random.key`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


#: row blocks of a 2-D leaf generated one at a time
ROW_BLOCKS = 16


def _normal(key, shape, std, dtype):
    """Normal values of ``shape`` generated block by block along the leading
    axis (``lax.map``: one layer, or one of ``ROW_BLOCKS`` row blocks of a
    matrix), so the float32 temporaries stay one block."""
    if len(shape) == 2:
        if shape[0] % ROW_BLOCKS:
            raise ValueError(f"{shape[0]} rows are not a multiple of {ROW_BLOCKS}")
        lead, block = ROW_BLOCKS, (shape[0] // ROW_BLOCKS, shape[1])
    else:
        lead, block = shape[0], tuple(shape[1:])

    def one(k):
        return (jax.random.normal(k, block, jnp.float32) * std).astype(dtype)

    return jax.lax.map(one, jax.random.split(key, lead)).reshape(shape)


def make_params(cfg, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights of ``cfg`` (a ``repro`` ``ArchConfig``): matrices
    normal with std 1/sqrt(fan_in), embeddings std 1/sqrt(d_model), norm
    scales 1 + 0.1 normal."""
    d, hd, h, kv = cfg.d_model, cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    f, n, vp = cfg.d_ff, cfg.n_layers, cfg.padded_vocab

    def build(key):
        keys = iter(jax.random.split(key, 12))

        def normal(shape, fan_in):
            return _normal(next(keys), shape, fan_in ** -0.5, dtype)

        def scale(shape):
            x = jax.random.normal(next(keys), shape, jnp.float32)
            return {"norm_scale": (1.0 + 0.1 * x).astype(dtype)}

        params = {
            "embed": {"tokens": normal((vp, d), d)},
            "layers": {
                "attn": {
                    "wq": normal((n, d, h * hd), d),
                    "wk": normal((n, d, kv * hd), d),
                    "wv": normal((n, d, kv * hd), d),
                    "wo": normal((n, h * hd, d), h * hd),
                },
                "attn_norm": scale((n, d)),
                "ffn_norm": scale((n, d)),
                "mlp": {
                    "w_gate": normal((n, d, f), d),
                    "w_in": normal((n, d, f), d),
                    "w_out": normal((n, f, d), f),
                },
            },
            "final_norm": scale((d,)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = normal((d, vp), d)
        return params

    return jax.jit(build)(seed_key(seed))
