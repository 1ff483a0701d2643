"""Helpers of the benchmark's CPU tests: the serve cells at ``reduced()``
sizes."""

import copy
import json

from chipbench import cells

SERVE_CELLS = [w["name"] for w in json.loads(
    (cells.ROOT / "BENCHMARK.json").read_text())["workloads"]]

#: a test-only cell, in a file shaped like ``BENCHMARK.json``: a model with
#: sparse experts, added by files alone
REHEARSAL = cells.HERE / "tests" / "data" / "benchmark.json"
MOE_CELL = "qwen3-moe-235b-a22b.decode-backlog"


def reduced_cell(name: str) -> cells.Cell:
    """The cell (of ``BENCHMARK.json``, else of ``REHEARSAL``) with its
    configuration cut to ``reduced()`` sizes in every field the file states
    (``ARCH_KEYS`` and ``"program"``), so that the file, the program and the
    reference agree, and its answers to 8-32 tokens, so that requests finish
    inside a short window on a busy CPU (prompts, the engine and the limits
    stay as committed)."""
    bench = cells.ROOT / "BENCHMARK.json" if name in SERVE_CELLS else REHEARSAL
    cell = copy.deepcopy(cells.load(name, bench))
    small = cells.arch_config(cell.conf).reduced()
    for key, field in cells.ARCH_KEYS.items():
        if key in cell.conf:
            cell.conf[key] = getattr(small, field)
    for field in cell.conf.get("program", {}):
        cell.conf["program"][field] = getattr(small, field)
    cell.traffic["output_tokens"] = {"median": 16, "sigma": 0.6, "min": 8, "max": 32}
    return cell
