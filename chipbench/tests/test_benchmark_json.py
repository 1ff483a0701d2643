"""``BENCHMARK.json`` against the rules every later PR is held to: names,
files found by name, a reader for every metric, and each per-layer metric
moving an end-to-end metric its cells report."""

import json
import re

import pytest

from chipbench import cells

BENCH = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    n = len(BENCH["workloads"])
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, n // 2)


def test_names_and_units():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_and_every_metric_has_a_reader(cell):
    c = cells.load(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(cells.reader(m["name"]))
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
    assert c.traffic["check"]["max_logit_gap"] is not None


def test_configurations_state_what_is_run():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        conf = json.loads((cells.ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        for key in c["reduced"]:
            assert key in conf["published"] and conf[key] != conf["published"][key]
        cfg = cells.arch_config(conf)
        assert cfg.vocab == conf["vocab_size"] and cfg.d_model == conf["hidden_size"]
