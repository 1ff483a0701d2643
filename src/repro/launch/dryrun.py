import os
# 512 placeholder CPU devices: pinned to the CPU, with the device count
# appended to any XLA flags the caller set
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512"]))

# --- everything below may import jax -----------------------------------------
"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape x mesh) cell against the production meshes --
single-pod (16,16)=(data,model) and multi-pod (2,16,16)=(pod,data,model) --
on 512 placeholder host devices, recording memory_analysis / cost_analysis /
collective bytes for the roofline (deliverable g).

Usage:
    python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out reports/
"""

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    Roofline,
    analytic_hbm_bytes,
    inner_scan_flops,
    model_flops_for,
    parse_collective_bytes,
)
from repro.models import ModelOptions, build_model, input_specs
from repro.optim import AdamWConfig, init_opt_state
from repro.parallel import sharding as shd
from repro.train.train_step import cache_shardings
from repro.optim.adamw import adamw_update
from repro.train.train_step import loss_and_grads


#: per-shape implementation knobs (baseline configuration; §Perf iterates)
def options_for(arch: str, shape_name: str, overrides: dict | None = None) -> ModelOptions:
    kw = dict(param_dtype="bfloat16", compute_dtype="bfloat16", remat=True)
    if shape_name in ("prefill_32k",):
        kw.update(attn_impl="chunked", attn_chunk=1024)
    if overrides:
        kw.update(overrides)
    return ModelOptions(**kw)


def microbatches_for(arch: str, shape_name: str, mesh) -> int:
    if SHAPES[shape_name].kind != "train":
        return 1
    data = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            data *= mesh.shape[a]
    per_device = SHAPES[shape_name].global_batch // data
    cfg = get_config(arch)
    if cfg.is_moe:
        return max(1, per_device)    # MoE: 1 seq/device/microbatch (dispatch
                                     # + expert activations are the fat part)
    return max(1, per_device // 2)   # dense: 2 sequences per microbatch


def should_skip(arch: str, shape_name: str) -> str | None:
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return ("skipped: pure full-attention arch at 512k decode "
                "(KV cache exceeds HBM; see DESIGN.md §4)")
    return None


def _lower_cell(cfg, shape, mesh, opts, microbatches: int, rules=None,
                unroll_microbatches: bool = False):
    """Build the jitted step for one cell and lower it (no compile)."""
    model = build_model(cfg, opts)
    specs = input_specs(cfg, shape, opts)

    with shd.activate(mesh, rules=rules):
        params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        p_shard = shd.param_shardings(params_sds, mesh, rules=rules)
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if rules and rules.get("batch"):
            data_axes = tuple(rules["batch"])
        daxes = data_axes if len(data_axes) > 1 else data_axes[0]

        if shape.kind == "train":
            opt_sds = jax.eval_shape(init_opt_state, params_sds)
            o_shard = {
                "m": shd.opt_shardings(params_sds, mesh, rules=rules),
                "v": shd.opt_shardings(params_sds, mesh, rules=rules),
                "step": NamedSharding(mesh, P()),
            }
            b_shard = jax.tree.map(
                lambda leaf: NamedSharding(mesh, P(daxes)), specs
            )
            opt_cfg = AdamWConfig(lr=3e-4)

            def train_step(params, opt_state, batch):
                loss, metrics, grads = loss_and_grads(
                    model, params, batch, microbatches,
                    unroll=unroll_microbatches)
                params, opt_state, om = adamw_update(grads=grads, params=params,
                                                     state=opt_state, cfg=opt_cfg)
                return params, opt_state, {"loss": loss, **om}

            jitted = jax.jit(
                train_step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            )
            return jitted.lower(params_sds, opt_sds, specs)

        if shape.kind == "prefill":

            def prefill(params, batch):
                logits, _ = model.forward(params, batch)
                return logits

            b_shard = jax.tree.map(lambda leaf: NamedSharding(mesh, P(daxes)), specs)
            jitted = jax.jit(prefill, in_shardings=(p_shard, b_shard))
            return jitted.lower(params_sds, specs)

        # decode
        def serve_step(params, cache, tokens):
            return model.decode_step(params, cache, tokens)

        c_shard = cache_shardings(specs["cache"], mesh, rules=rules,
                                  model=model)
        t_shard = NamedSharding(mesh, P())
        jitted = jax.jit(
            serve_step,
            in_shardings=(p_shard, c_shard, t_shard),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        )
        return jitted.lower(params_sds, specs["cache"], specs["tokens"])


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_overrides: dict | None = None, verbose: bool = True,
             with_analysis: bool | None = None,
             rule_overrides: dict | None = None,
             microbatches: int | None = None,
             analysis_true_microbatches: bool = False) -> dict:
    """Lower + compile one cell.

    Two compiles per single-pod cell:
    * production -- scanned layers + microbatched grad accumulation; its
      ``memory_analysis`` is the fits-on-device proof.
    * analysis -- unrolled layers, microbatches=1; XLA cost analysis counts
      ``while`` bodies once, so only this variant yields trip-count-true
      FLOPs / bytes / collective totals for the roofline.  Recurrent inner
      scans (xLSTM/Mamba2) that cannot unroll get the closed-form
      ``inner_scan_flops`` correction.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    skip = should_skip(arch, shape_name)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": skip}
    if with_analysis is None:
        with_analysis = not multi_pod  # roofline table is single-pod only

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    opts = options_for(arch, shape_name, opt_overrides)
    mb = microbatches if microbatches is not None else microbatches_for(
        arch, shape_name, mesh)
    rules = None
    if rule_overrides:
        rules = shd.default_rules(mesh.axis_names)
        rules.update(rule_overrides)

    t0 = time.perf_counter()
    lowered = _lower_cell(cfg, shape, mesh, opts, mb, rules=rules)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    mem = compiled.memory_analysis()

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "status": "ok",
        "chips": chips, "microbatches": mb,
        "overrides": {"opts": opt_overrides or {}, "rules": 
                      {k: list(v) if isinstance(v, tuple) else v
                       for k, v in (rule_overrides or {}).items()}},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes_per_device": (
                (getattr(mem, "argument_size_in_bytes", 0) or 0)
                + (getattr(mem, "temp_size_in_bytes", 0) or 0)
            ),
        },
    }

    if with_analysis:
        a_over = dict(opt_overrides or {})
        a_over.update(scan_layers=False, attn_impl="xla")
        a_opts = options_for(arch, shape_name, a_over)
        # perf runs unroll the true microbatch count so grad-accumulation
        # effects (weight regathers per microbatch) appear in the totals
        a_mb = mb if analysis_true_microbatches else 1

        def analyse_at(n_layers: int, n_mb: int):
            acfg = cfg
            if n_layers != cfg.n_layers:
                acfg = dataclasses.replace(cfg, n_layers=n_layers)
            lowered_a = _lower_cell(acfg, shape, mesh, a_opts, microbatches=n_mb,
                                    rules=rules, unroll_microbatches=True)
            compiled_a = lowered_a.compile()
            c = compiled_a.cost_analysis()
            # Newer JAX returns a one-element list of per-program dicts.
            if isinstance(c, (list, tuple)):
                c = c[0] if c else {}
            coll = parse_collective_bytes(compiled_a.as_text())
            return (float(c.get("flops", 0.0)),
                    float(c.get("bytes accessed", 0.0)), coll)

        # Unrolled compile cost grows with n_layers x microbatches; both are
        # exactly linear per body (identical layers / identical microbatches),
        # so large cells are measured at small (L, M) grid points and fitted
        # bilinearly: cost = a + b*L + c*M + d*L*M.
        L_full, M_full = cfg.n_layers, a_mb
        layer_extrap = L_full > 48
        mb_extrap = M_full > 2
        if layer_extrap:
            step = max(cfg.attn_every or 1, cfg.slstm_every or 1, 1)
            l1 = max(step, (12 // step) * step or step)
            Ls = (l1, 2 * l1)
        else:
            Ls = (L_full,)
        Ms = (1, 2) if mb_extrap else (M_full,)
        grid = {(L, M): analyse_at(L, M) for L in Ls for M in Ms}
        extrapolated = layer_extrap or mb_extrap

        def fit(idx):
            def val(L, M):
                g = grid[(L, M)]
                return g[idx] if idx < 2 else g[2]

            def lin(p1, p2, x1, x2, x):
                return p1 + (p2 - p1) / (x2 - x1) * (x - x1) if x2 != x1 else p1

            if idx < 2:
                # numbers: fit M at each L, then L
                at_L = {
                    L: lin(val(L, Ms[0]), val(L, Ms[-1]), Ms[0], Ms[-1], M_full)
                    for L in Ls
                }
                return lin(at_L[Ls[0]], at_L[Ls[-1]], Ls[0], Ls[-1], L_full)
            # collectives: per-kind dict
            kinds = {k for g in grid.values() for k in g[2]}
            out = {}
            for k in kinds:
                at_L = {
                    L: lin(grid[(L, Ms[0])][2].get(k, 0),
                           grid[(L, Ms[-1])][2].get(k, 0), Ms[0], Ms[-1], M_full)
                    for L in Ls
                }
                out[k] = max(0.0, lin(at_L[Ls[0]], at_L[Ls[-1]], Ls[0], Ls[-1], L_full))
            return out

        a_flops, a_bytes, collectives = fit(0), fit(1), fit(2)
        cost = {"flops": a_flops, "bytes accessed": a_bytes}
        correction = inner_scan_flops(cfg, shape)
        if shape.kind == "train":
            correction *= 3.0  # fwd + bwd (~2x fwd)
        cache_bytes = 0.0
        if shape.kind == "decode":
            specs_d = input_specs(cfg, shape, opts)
            cache_bytes = float(sum(
                int(jnp.prod(jnp.array(l.shape))) * jnp.dtype(l.dtype).itemsize
                for l in jax.tree.leaves(specs_d["cache"])
            ))
        analytic = analytic_hbm_bytes(
            cfg, shape, microbatches=mb, attn_impl=opts.attn_impl,
            remat=opts.remat, kv_cache_bytes=cache_bytes,
        )
        rl = Roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            hlo_flops=float(cost.get("flops", 0.0)) * chips + correction,
            hlo_bytes=float(cost.get("bytes accessed", 0.0)) * chips,
            collective_bytes=float(sum(collectives.values())) * chips,
            collectives={k: v * chips for k, v in collectives.items()},
            model_flops=model_flops_for(cfg, shape),
            analytic_bytes=analytic,
        )
        record["roofline"] = rl.to_dict()
        record["scan_flop_correction"] = correction
        record["analysis_depth_extrapolated"] = extrapolated

    if verbose:
        peak = record["memory"]["peak_bytes_per_device"] or 0
        extra = ""
        if with_analysis:
            rd = record["roofline"]
            extra = (f"  flops={rd['hlo_flops']:.3e}  coll={rd['collective_bytes']:.3e}B"
                     f"  dominant={rd['dominant']}")
        print(
            f"[{arch} x {shape_name} x {mesh_name}] OK  "
            f"compile={t_compile:.0f}s  peak={peak/2**30:.2f} GiB/dev" + extra,
            flush=True,
        )
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--out", default="reports", help="output dir for JSONL")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in ["train_4k", "prefill_32k", "decode_32k", "long_500k"]:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_file = out_dir / "dryrun.jsonl"
    mode = "a" if args.append else "w"
    failures = 0
    with open(out_file, mode) as fh:
        for arch, shape in cells:
            for multi in meshes:
                try:
                    rec = run_cell(arch, shape, multi)
                except Exception as e:  # noqa: BLE001 - report and continue
                    failures += 1
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "multi" if multi else "single",
                        "status": f"FAILED: {type(e).__name__}: {e}",
                    }
                    print(f"[{arch} x {shape} x {rec['mesh']}] FAILED: {e}",
                          flush=True)
                    traceback.print_exc()
                fh.write(json.dumps(rec) + "\n")
                fh.flush()
    print(f"wrote {out_file}; failures={failures}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
