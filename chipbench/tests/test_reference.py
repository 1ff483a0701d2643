"""The plain reference against the program's own full forward, both in
float32, at reduced sizes: two independent writings of the same equations
must agree, and the fp8 control must not.  The test-only cell with sparse
experts is compared with its own reference, as its file names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells
from chipbench.tests.helpers import MOE_CELL, SERVE_CELLS, reduced_cell
from chipbench.weights import make_params


@pytest.mark.parametrize("name", SERVE_CELLS + [MOE_CELL])
def test_reference_matches_the_program_forward(name):
    from repro.models import ModelOptions, build_model

    cell = reduced_cell(name)
    cfg = cells.arch_config(cell.conf)
    model = build_model(cfg, ModelOptions(param_dtype="float32",
                                          compute_dtype="float32", remat=False))
    params = make_params(cfg, 2**33 + 3, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (96,), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        prog, _ = jax.jit(model.forward)(params, {"tokens": tokens[None]})
    pos = jnp.arange(40, 96)
    ref = np.asarray(cells.reference(cell.conf)(params, tokens, pos))
    prog = np.asarray(prog[0, 40:96, :cfg.vocab])
    scale = np.abs(ref).max()
    assert scale > 1.0                       # logits of a realistic spread
    assert np.abs(prog - ref).max() <= 1e-4 * scale
    ctl = np.asarray(cells.reference(cell.conf, quant="fp8")(params, tokens, pos))
    assert np.abs(ctl - ref).max() >= 1e-2 * scale


def test_reference_is_causal_over_padding():
    cell = reduced_cell("minicpm-2b.decode-backlog")
    cfg = cells.arch_config(cell.conf)
    params = make_params(cfg, 7)
    fn = cells.reference(cell.conf)
    a = np.zeros(64, np.int32)
    a[:30] = np.arange(30) + 5
    b = a.copy()
    b[30:] = 99                               # padding after the last position
    pos = np.arange(20, 30)
    np.testing.assert_array_equal(np.asarray(fn(params, a, pos)),
                                  np.asarray(fn(params, b, pos)))
