#!/usr/bin/env python3
"""Readings that set a serve cell's limit: on each seed, the program's widest
logit gap against the reference (sound runs give the lower reading) and the
control's, the reference computed in fp8 in the program's place (it gives
the upper reading).  Not part of the benchmark's runs.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

Each seed serves the cell's traffic for a window at the cell's own load,
in one process for all seeds, and prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def serve_once(cell, model, params, seed: int, seconds: float):
    from repro.serve import EngineConfig

    from chipbench import serve, traffic as gen

    requests = gen.requests(cell.traffic, cell.conf["vocab_size"], seed, seconds)
    engine = serve.make_engine(model, params, EngineConfig(**cell.traffic["engine"]),
                               seconds, None)
    results, stats = engine.run(requests)
    return requests, results, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")
    import jax

    from chipbench import cells, serve
    from chipbench.weights import make_params
    from repro.models import ModelOptions, build_model

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    cell = cells.load(args.workload)
    cfg = cells.arch_config(cell.conf)
    model = build_model(cfg, ModelOptions(param_dtype="bfloat16",
                                          compute_dtype="bfloat16"))
    for seed in args.seeds:
        t0 = time.perf_counter()
        params = jax.block_until_ready(make_params(cfg, seed))
        requests, results, _ = serve_once(cell, model, params, seed, args.seconds)
        served_s = time.perf_counter() - t0
        checks = serve.verify(cell, params, requests, results, seed, control=True)
        print(json.dumps({"seed": seed, "served_s": served_s,
                          "check_s": time.perf_counter() - t0 - served_s,
                          **{k: v["value"] for k, v in checks.items()}}), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
