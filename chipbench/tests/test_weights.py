"""The served weights: every leaf of the program's own parameter tree for each
decoder it builds, values from the seed alone, and minicpm-2b's weights as
they have always been drawn."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.transformer as transformer
from chipbench import cells
from chipbench.tests.helpers import reduced_cell
from chipbench.weights import make_params
from repro.configs import ARCHS
from repro.models import ModelOptions, build_model

#: sha256 of reduced minicpm-2b's bfloat16 weights (paths, types, shapes and
#: bytes), taken when the weights' layout was still written out by hand
MINICPM_DIGESTS = {
    7: "2710339e0b3002d16d4cfd9d26736cd2ab8429e024f31e54ccc51aa62442c26a",
    2**32 + 5: "f8e0d74c5d0c28dfca64323c605eec519e484aa480bac20efe1b7d85f087a181",
}
DECODERS = sorted(n for n, c in ARCHS.items() if c.family in ("dense", "moe", "vlm"))


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(MINICPM_DIGESTS))
def test_minicpm_weights_are_bit_identical(seed):
    cfg = cells.arch_config(reduced_cell("minicpm-2b.decode-backlog").conf)
    assert _digest(make_params(cfg, seed)) == MINICPM_DIGESTS[seed]


@pytest.mark.parametrize("arch", DECODERS)
def test_weights_are_the_programs_tree(arch):
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg, ModelOptions(param_dtype="bfloat16"))
    want = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    got = make_params(cfg, 2**32 + 9)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype), jax.tree_util.keystr(path)
        g = np.asarray(g, np.float32)
        assert np.isfinite(g).all() and g.std() > 0, jax.tree_util.keystr(path)
    # values from the seed alone: the same seed repeats, another differs
    again, other = make_params(cfg, 2**32 + 9), make_params(cfg, 2**32 + 10)
    for g, a, o in zip(jax.tree.leaves(got), jax.tree.leaves(again), jax.tree.leaves(other)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(a))
        assert not np.array_equal(np.asarray(g), np.asarray(o))


def test_a_leaf_no_rule_covers_is_refused(monkeypatch):
    init = transformer.DecoderLM.init

    def with_bias(self, key):
        params = init(self, key)
        params["head_bias"] = jnp.zeros((self.cfg.padded_vocab,), self.opts.pdt)
        return params

    monkeypatch.setattr(transformer.DecoderLM, "init", with_bias)
    with pytest.raises(ValueError, match="head_bias"):
        make_params(ARCHS["minicpm-2b"].reduced(), 3)
