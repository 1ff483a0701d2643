#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names its configuration and traffic mix; the
weights and the inputs come from ``--seed``.  Set-up warms up every shape
the cell uses, then the window measures for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, then
``breakdown`` when traced, ``host``, where the window's host time went,
and last ``checks``: each number compared with its limit, also the last
lines of standard error).  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
JAX's compilation cache is kept in ``chipbench/.cache/jax``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache" / "jax"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import cells

    cell = cells.load(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2

    out = cells.kind_module(cell).run(cell, args.seed, args.seconds,
                                      bool(args.trace), T_START)
    print(f"host {json.dumps(out.get('host', {}))}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
