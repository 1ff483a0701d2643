"""Helpers of the benchmark's CPU tests: the serve cells at ``reduced()``
sizes."""

import copy
import json

from chipbench import cells

SERVE_CELLS = [w["name"] for w in json.loads(
    (cells.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def reduced_cell(name: str) -> cells.Cell:
    """The cell with its configuration cut to ``reduced()`` sizes and its
    answers to 8-32 tokens, so that requests finish inside a short window
    on a busy CPU (prompts, the engine and the limits stay as committed)."""
    cell = copy.deepcopy(cells.load(name))
    small = cells.arch_config(cell.conf).reduced()
    for key, field in cells.ARCH_KEYS.items():
        cell.conf[key] = getattr(small, field)
    cell.traffic["output_tokens"] = {"median": 16, "sigma": 0.6, "min": 8, "max": 32}
    return cell
