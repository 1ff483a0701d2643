"""Benchmark harness: one module per paper table/figure (DESIGN.md §5).

Prints ``name,us_per_call,derived`` CSV rows.  Run as:
    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run bench_e2e  # one
    PYTHONPATH=src python -m benchmarks.run latency serve --smoke  # CI
    PYTHONPATH=src python -m benchmarks.run --list    # areas + artifacts

Modules whose ``run`` accepts a ``smoke`` argument honor ``--smoke``
(shrunk workload, same code paths).  Modules with a ``BENCH_FILE``
attribute emit a cross-PR ``BENCH_<area>.json`` artifact (schema in
:mod:`benchmarks._artifact`).
"""

import inspect
import pathlib
import sys
import time
import traceback

if __package__ in (None, ""):  # `python benchmarks/run.py ...` (script mode)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmarks import (
    bench_breakdown,
    bench_comm,
    bench_e2e,
    bench_faults,
    bench_jct,
    bench_latency,
    bench_queue,
    bench_serve,
    bench_sim,
    bench_spread,
    bench_topology,
    bench_volume,
    roofline_report,
)

ALL = {
    "bench_volume": bench_volume,      # Appendix C (2 GB / 30 MB claim)
    "bench_comm": bench_comm,          # Figure 4 (BusBw model)
    "bench_spread": bench_spread,      # Figure 7 / Table 1
    "bench_latency": bench_latency,    # Figure 8 + scale tier -> BENCH_sched_latency.json
    "bench_e2e": bench_e2e,            # Figures 5 + 9 (simulated E2E)
    "bench_topology": bench_topology,  # DESIGN.md §9 cross-fabric -> BENCH_topology.json
    "bench_queue": bench_queue,        # Figure 14 / Appendix H -> BENCH_queue.json
    "bench_sim": bench_sim,            # DESIGN.md §10 replay perf -> BENCH_sim.json
    "bench_faults": bench_faults,      # DESIGN.md §11 fault injection -> BENCH_faults.json
    "bench_jct": bench_jct,            # Figure 13 / Appendix G
    "bench_breakdown": bench_breakdown,  # Figure 10 / Appendix I
    "bench_serve": bench_serve,        # DESIGN.md §7 -> BENCH_serve.json
    "roofline_report": roofline_report,  # §Roofline table from the dry-run
}

ALIASES = {"serve": "bench_serve", "latency": "bench_latency",
           "topology": "bench_topology", "sim": "bench_sim",
           "queue": "bench_queue", "faults": "bench_faults"}


def artifact_of(mod) -> "pathlib.Path | None":
    """The BENCH_*.json this module emits, if any."""
    return getattr(mod, "BENCH_FILE", None)


def list_areas() -> None:
    for name, mod in ALL.items():
        art = artifact_of(mod)
        smoke = "smoke" in inspect.signature(mod.run).parameters
        tags = [t for t, on in (("--smoke", smoke),) if on]
        if art is not None:
            tags.append(f"emits {art.name}")
        print(f"{name:<18} {' '.join(tags)}".rstrip())


def main() -> None:
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    argv = [a for a in argv if a != "--smoke"]
    if "--list" in argv:
        list_areas()
        return
    names = [ALIASES.get(n, n) for n in argv] or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        print(f"unknown benchmark(s) {unknown}; available: {list(ALL)}",
              file=sys.stderr)
        sys.exit(2)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = ALL[name]
        kwargs = {}
        if smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        t0 = time.perf_counter()
        try:
            rows = mod.run(**kwargs)
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{name}_FAILED,0,{type(e).__name__}")
            continue
        wall = (time.perf_counter() - t0) * 1e6
        for r in rows:
            print(",".join(str(x) for x in r))
        print(f"{name}_total,{wall:.0f},ok")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
