"""Cells, configurations, traffic mixes and metric readers, found by name.

``BENCHMARK.json`` names each cell's configuration (a file under
``configs/``) and traffic mix (``traffic/<traffic>.json``); the
configuration names its plain reference (``reference/``).  The mix's
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
    """The program's ``ArchConfig``: the registered ``repo_config`` with each
    ``ARCH_KEYS`` key the file states, and the ``"program"`` object's
    ``ArchConfig`` fields (sizes with no published key) by their own names.
    An ``ARCH_KEYS`` key the file leaves out keeps the registered value only
    where the file names it under ``"registered"``, so that every size run
    can be read in the file."""
    from repro.configs import get_config

    unnamed = [k for k in ARCH_KEYS
               if k not in conf and k not in conf.get("registered", [])]
    if unnamed:
        raise ValueError(f"{conf['name']}: {unnamed} neither stated nor "
                         "named under 'registered'")
    stated = {f: conf[k] for k, f in ARCH_KEYS.items() if k in conf}
    # a name that is no ArchConfig field, or a field stated twice, is refused
    # by ``replace`` (TypeError)
    return dataclasses.replace(get_config(conf["repo_config"]), **stated,
                               **conf.get("program", {}))


def model(conf: dict):
    """``(ArchConfig, model)``: the program's model as the file states it,
    its weights and its computation in the file's ``dtype``."""
    from repro.models import ModelOptions, build_model

    cfg = arch_config(conf)
    return cfg, build_model(cfg, ModelOptions(param_dtype=conf["dtype"],
                                              compute_dtype=conf["dtype"]))


def _module(path: pathlib.Path):
    """The module in ``path``, a file inside the checkout (by its file: a
    metric's name holds dots)."""
    name = ".".join(path.relative_to(ROOT).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: dict, quant=None):
    """The plain reference that the file names (``"reference"``, a path from
    the checkout's root): its module's ``make_logits_fn(conf, quant)``."""
    return _module(ROOT / conf["reference"]).make_logits_fn(conf, quant=quant)


def reader(metric: str):
    """``metrics/<metric>.py``'s ``read``."""
    return _module(HERE / "metrics" / f"{metric}.py").read


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
