"""A configuration file states what the program runs: each ``ARCH_KEYS`` key
it has, ``ArchConfig`` fields by their own names under ``"program"``, and
for a key it leaves out and names under ``"registered"`` the registered
value; the tests' cut to ``reduced()`` sizes reaches every field the file
states."""

import copy
import dataclasses
import json

import pytest

from chipbench import cells
from chipbench.tests.helpers import MOE_CELL, reduced_cell
from repro.configs import get_config

MOE_CONF = json.loads((cells.HERE / "tests" / "data" / "qwen3-moe-235b-a22b.json").read_text())


def test_program_fields_apply_and_keys_left_out_keep_the_registered_value():
    cfg = cells.arch_config(MOE_CONF)
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.capacity_factor) == (128, 8, 1536, 2.0)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)     # stated by the file
    assert "intermediate_size" not in MOE_CONF
    assert MOE_CONF["registered"] == ["intermediate_size"]
    assert cfg.d_ff == get_config(MOE_CONF["repo_config"]).d_ff


def test_a_key_left_out_and_not_named_registered_is_refused():
    conf = copy.deepcopy(MOE_CONF)
    del conf["registered"]
    with pytest.raises(ValueError, match="intermediate_size"):
        cells.arch_config(conf)


@pytest.mark.parametrize("program", [{"n_expert": 4}, {"d_model": 64}],
                         ids=["not-a-field", "stated-twice"])
def test_a_program_field_that_is_no_field_or_stated_twice_is_refused(program):
    conf = copy.deepcopy(MOE_CONF)
    conf["program"] = program
    with pytest.raises(TypeError, match=next(iter(program))):
        cells.arch_config(conf)


def test_reduced_cell_cuts_every_field_the_file_states():
    cell = reduced_cell(MOE_CELL)
    assert cell.conf["program"] == {"n_experts": 4, "top_k": 2, "d_expert": 64,
                                    "capacity_factor": 2.0}
    assert (cell.conf["hidden_size"], cell.conf["num_hidden_layers"],
            cell.conf["vocab_size"], cell.conf["head_dim"]) == (64, 4, 512, 16)
    small = cells.arch_config(MOE_CONF).reduced()
    # d_ff, which the file leaves out, keeps its registered value
    assert cells.arch_config(cell.conf) == dataclasses.replace(
        small, d_ff=get_config(MOE_CONF["repo_config"]).d_ff)
