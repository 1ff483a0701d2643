"""The control, the reference computed in fp8 in the program's place, read
the way the chip readings that set each limit were (``control.py``), at a
size a test run can hold: it must fail the cell's committed limit, and the
program must pass it."""

import pytest

from chipbench import cells, control, serve
from chipbench.tests.helpers import SERVE_CELLS, reduced_cell
from chipbench.weights import make_params


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_control_fails_the_limit_and_the_program_passes(name):
    from repro.models import ModelOptions, build_model

    cell = reduced_cell(name)
    cfg = cells.arch_config(cell.conf)
    model = build_model(cfg, ModelOptions(param_dtype="bfloat16",
                                          compute_dtype="bfloat16"))
    seed = 2**32 + 5
    params = make_params(cfg, seed)
    requests, results, _ = control.serve_once(cell, model, params, seed, 3.0)
    checks = serve.verify(cell, params, requests, results, seed, control=True)
    assert checks["max_logit_gap"]["pass"], checks
    assert not checks["control_max_logit_gap"]["pass"], checks
