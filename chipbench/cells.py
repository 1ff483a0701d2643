"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` names each cell's configuration (a file under
``configs/``) and traffic mix (``traffic/<traffic>.json``).  The mix's
``kind`` names the module that runs it (``serve``).  Every metric is read
by ``metrics/<name>.py``, whose ``read(run)`` returns a number, or None
where it finds nothing to read.  Adding a configuration, a mix or a metric
is adding a file and an entry.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

#: configuration-file keys -> ``repro`` ``ArchConfig`` fields
ARCH_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict              # the configuration file
    traffic: dict           # the traffic mix file
    end_to_end: list[dict]  # BENCHMARK.json metric entries this cell reports
    per_layer: list[dict]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load(name: str, bench_path: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"],
        conf=json.loads((ROOT / conf_file).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def arch_config(conf: dict):
    """The program's ``ArchConfig`` with every size the file states."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(conf["repo_config"]),
                               **{f: conf[k] for k, f in ARCH_KEYS.items()})


def reader(metric: str):
    """``metrics/<metric>.py``'s ``read``."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric}", HERE / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list[dict], run) -> dict:
    """``{name: {"value", "unit"}}`` for every entry whose reader finds
    something to read."""
    out = {}
    for m in entries:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def kind_module(cell: Cell):
    return importlib.import_module(f"chipbench.{cell.traffic['kind']}")
