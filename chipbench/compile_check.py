#!/usr/bin/env python3
"""Compile each serve cell's programs for a described TPU v5e chip (nothing
runs, no chip needed) and print their memory, so that page pools and lane
counts are fixed before a chip run.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py [cell ...]

For every cell: the engine's decode program and its prefill program at the
largest bucket the traffic uses, and the reference's program; then the
page pool that fits beside the weights and the largest program's
temporaries, against ``USABLE_BYTES``.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

#: what XLA lets one v5e chip's programs use (16 GiB less its reservation)
USABLE_BYTES = int(15.75 * 2**30)
#: room for XLA's reservation (258 MiB), fragmentation and small buffers
MARGIN_BYTES = 2**30


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, f"{k}_size_in_bytes")
            for k in ("argument", "output", "alias", "temp")}


def check_cell(name: str, chip) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import cells
    from chipbench.reference.decoder import make_logits_fn
    from chipbench.weights import make_params
    from repro.models import ModelOptions, build_model
    from repro.serve import EngineConfig
    from repro.serve.engine import engine_steps

    cell = cells.load(name)
    cfg = cells.arch_config(cell.conf)
    model = build_model(cfg, ModelOptions(param_dtype="bfloat16",
                                          compute_dtype="bfloat16"))
    ecfg = EngineConfig(**cell.traffic["engine"])
    n_pages = ecfg.n_pages
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)  # noqa: E731
    params = jax.tree.map(on, jax.eval_shape(lambda: make_params(cfg, 0)))
    pages = jax.tree.map(on, jax.eval_shape(
        lambda: model.init_paged_cache(n_pages, ecfg.page_size)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    nb, mb = ecfg.max_batch, ecfg.max_blocks
    decode, prefill = engine_steps(model)
    out = {"decode": _mem(decode.lower(
        params, pages, i32(nb, mb), i32(nb), i32(nb, 1),
        jax.ShapeDtypeStruct((nb,), jnp.bool_, sharding=chip)).compile())}
    bucket = ecfg.prefill_bucket(cell.traffic["prompt_tokens"]["max"])
    out[f"prefill_{bucket}"] = _mem(prefill.lower(
        params, pages, i32(mb), i32(), i32(1, bucket)).compile())
    ref = make_logits_fn(cell.conf)
    out["reference"] = _mem(ref.lower(
        params, i32(mb * ecfg.page_size),
        i32(cell.traffic["output_tokens"]["max"])).compile())

    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    page_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages)) // n_pages
    temp = max(v["temp"] for k, v in out.items() if k != "reference")
    # the layer scan returns the pool as a new stacked output, so each
    # program's temporaries hold one copy of the whole pool
    fixed = temp - n_pages * page_bytes
    fit = (USABLE_BYTES - MARGIN_BYTES - weights - fixed) // (2 * page_bytes)
    worst = ecfg.max_batch * ecfg.max_blocks
    return {"cell": name, "programs": out, "weights_bytes": weights,
            "page_bytes": page_bytes, "pages_that_fit": int(fit),
            "pages_for_every_lanes_worst_case": worst,
            "n_pages": n_pages, "resident_at_n_pages": weights + n_pages * page_bytes,
            "peak_estimate": weights + fixed + 2 * n_pages * page_bytes}


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import cells

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in bench["workloads"] if w["chips"] == 1]
    for name in names:
        print(json.dumps(check_cell(name, chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
