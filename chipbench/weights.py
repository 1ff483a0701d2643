"""The served weights, made by the benchmark from the seed.

The layout, every leaf's path, shape and type, is the program's own
parameter tree: ``jax.eval_shape`` of its ``init``, so any decoder the
program builds (dense, experts, a patch projection) gets every leaf it
takes.  The values come only from the seed, by rules on the leaf's path and
shape, in one jitted call on the device.  The plain reference reads these
same arrays; no value of them comes from the program.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed (``jax.random.key`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


#: row blocks of a 2-D leaf generated one at a time
ROW_BLOCKS = 16

#: the dense decoder's leaves and their fixed places in ``split(key, 12)``,
#: so that a dense model's weights do not depend on the rest of the tree;
#: any other leaf draws from the key folded with its path
DENSE_KEYS = {
    "embed/tokens": 0,
    "layers/attn/wq": 1, "layers/attn/wk": 2, "layers/attn/wv": 3,
    "layers/attn/wo": 4,
    "layers/attn_norm/norm_scale": 5, "layers/ffn_norm/norm_scale": 6,
    "layers/mlp/w_gate": 7, "layers/mlp/w_in": 8, "layers/mlp/w_out": 9,
    "final_norm/norm_scale": 10,
    "lm_head": 11,
}


def _normal(key, shape, std, dtype):
    """Normal values of ``shape`` generated block by block (``lax.map``: one
    of ``ROW_BLOCKS`` row blocks of a matrix, or one matrix of a stack), so
    the float32 temporaries stay one block."""
    if len(shape) == 2:
        if shape[0] % ROW_BLOCKS:
            raise ValueError(f"{shape[0]} rows are not a multiple of {ROW_BLOCKS}")
        lead, block = ROW_BLOCKS, (shape[0] // ROW_BLOCKS, shape[1])
    else:
        lead, block = math.prod(shape[:-2]), tuple(shape[-2:])

    def one(k):
        return (jax.random.normal(k, block, jnp.float32) * std).astype(dtype)

    return jax.lax.map(one, jax.random.split(key, lead)).reshape(shape)


def _leaf(key, name: str, leaf):
    """One leaf's values: norm scales 1 + 0.1 normal, the embedding std
    1/sqrt(d_model), any other matrix or stack of matrices std
    1/sqrt(fan_in), its second-to-last axis."""
    shape, dtype = leaf.shape, leaf.dtype
    if name.endswith("norm_scale"):
        x = jax.random.normal(key, shape, jnp.float32)
        return (1.0 + 0.1 * x).astype(dtype)
    if name == "embed/tokens":
        return _normal(key, shape, shape[-1] ** -0.5, dtype)
    if len(shape) >= 2:
        return _normal(key, shape, shape[-2] ** -0.5, dtype)
    raise ValueError(f"no rule makes the weights of leaf {name!r} {shape} {dtype}")


def make_params(cfg, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights of ``cfg`` (a ``repro`` ``ArchConfig``) in the program's
    parameter tree, its matrices in ``dtype`` (a leaf the program keeps in
    another type, as the experts' router, keeps that type)."""
    from repro.models import ModelOptions, build_model

    model = build_model(cfg, ModelOptions(param_dtype=jnp.dtype(dtype).name))
    layout = jax.eval_shape(lambda: model.init(jax.random.key(0)))

    def build(key):
        dense = jax.random.split(key, len(DENSE_KEYS))

        def one(path, leaf):
            name = "/".join(str(p.key) for p in path)
            k = (dense[DENSE_KEYS[name]] if name in DENSE_KEYS
                 else jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF))
            return _leaf(k, name, leaf)

        return jax.tree_util.tree_map_with_path(one, layout)

    return jax.jit(build)(seed_key(seed))
