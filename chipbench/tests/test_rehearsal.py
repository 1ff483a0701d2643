"""A model with sparse experts added by files alone: a configuration in
``tests/data`` that names its own reference (``reference/moe_decoder.py``)
and a cell in a file shaped like ``BENCHMARK.json``.  Served end to end on
the CPU at ``reduced()`` sizes it is correct against that reference
(``test_reference.py`` compares the reference with the program's forward,
``test_serve_cell.py`` breaks its decode underneath)."""

from chipbench import cells
from chipbench.tests.helpers import MOE_CELL
from chipbench.tests.test_serve_cell import _run


def test_moe_cell_is_correct_against_the_reference_it_names(monkeypatch):
    named = []
    real = cells.reference

    def spy(conf, quant=None):
        named.append(conf["reference"])
        return real(conf, quant)

    monkeypatch.setattr(cells, "reference", spy)
    out = _run(MOE_CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["checks"]["sampled_tokens"]["value"] > 0
    assert named == ["chipbench/reference/moe_decoder.py"]
