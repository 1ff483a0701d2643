"""End-to-end training driver: ``python -m repro.launch.train --arch <id>``.

Wires the whole stack together: config -> model -> synthetic data pipeline ->
AdamW (+schedule) -> fault-tolerant Trainer with checkpoint-restart, and
optionally a sharded run (``--devices N --mesh-shape dxm``: a mesh over the
chips, or over N virtual devices on the CPU; with ``--arnold`` the MILP
placement permutes the mesh), trained under pjit with state created and
restored already sharded.  ``--full`` (the published widths) computes in
bfloat16.
"""

import argparse
import os
import sys


def run(argv=None) -> list[float]:
    """Train as the command line ``argv`` says; return the logged losses."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (default: reduced)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--devices", type=int, default=0,
                    help="devices of a sharded run (0 = one device); on the "
                         "CPU, that many virtual host devices")
    ap.add_argument("--mesh-shape", default="2x4",
                    help="dataxmodel for the sharded run")
    ap.add_argument("--arnold", action="store_true",
                    help="order mesh devices by the Arnold MILP placement")
    ap.add_argument("--scheduler", default="mip",
                    help="placement policy for --arnold: a registry name "
                         "(see repro.core.list_schedulers()) or a comma-"
                         "separated fallback chain, e.g. 'mip,topo-aware'")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.devices:
        # virtual host devices for a sharded run on the CPU (no effect on a
        # chip, and none once JAX has started in this process)
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={args.devices}"]))

    from repro.configs import get_config
    from repro.data import SyntheticDataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import ModelOptions, build_model
    from repro.optim import AdamWConfig, get_schedule
    from repro.train import Trainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    opts = ModelOptions(
        compute_dtype="bfloat16" if args.full or args.devices else "float32",
        remat=bool(args.full),
    )
    model = build_model(cfg, opts)

    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = ((args.global_batch, cfg.n_patches, cfg.d_model), "float32")
    if cfg.family == "audio":
        extra["frames"] = ((args.global_batch, 24, cfg.d_model), "float32")
    ds = SyntheticDataset(cfg.vocab, args.seq_len, args.global_batch,
                          seed=args.seed, extra_specs=extra)
    schedule = get_schedule(cfg.lr_schedule, args.lr, warmup_steps=max(1, args.steps // 20),
                            total_steps=args.steps)
    opt = AdamWConfig(lr=schedule)

    trainer = Trainer(
        model, ds, opt, ckpt_dir=args.ckpt_dir,
        cfg=TrainerConfig(
            total_steps=args.steps, ckpt_every=args.ckpt_every,
            log_every=args.log_every, microbatches=args.microbatches,
            seed=args.seed,
        ),
        on_step=lambda h: print(
            f"step {h['step']:5d}  loss {h['loss']:.4f}  "
            f"gnorm {h['grad_norm']:.3f}  {h['step_time']*1e3:.0f} ms",
            flush=True,
        ),
        mesh=build_mesh(args, cfg) if args.devices else None,
    )
    trainer.run()
    losses = trainer.losses()
    print(f"done: first logged loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return losses


def build_mesh(args, cfg):
    """The (data, model) mesh of a sharded run: JAX's device order, or with
    ``--arnold`` the order of the scheduler's placement."""
    import jax
    from repro.launch.mesh import make_arnold_mesh, make_mesh, mesh_group_spread

    d, m = (int(x) for x in args.mesh_shape.split("x"))
    if d * m > min(args.devices, len(jax.devices())):
        raise SystemExit(f"mesh {d}x{m} needs {d * m} devices; "
                         f"--devices {args.devices}, {len(jax.devices())} present")
    if not args.arnold:
        return make_mesh((d, m), ("data", "model"))

    from repro.core import (
        CharacterizationDB, Cluster, JobSpec, ModelSpec, ScheduleRequest,
        build_comm_matrix, get_scheduler,
    )

    # A node is the chips of one host (4 on a TPU v5e host, every virtual
    # device on the CPU), or the whole job where that is smaller; the
    # cluster is the devices present, in pods of up to 4 nodes.
    per_node = min(jax.local_device_count(), d * m)
    n_nodes = len(jax.devices()) // per_node
    cluster = Cluster([min(4, n_nodes - i) for i in range(0, n_nodes, 4)])
    mspec = ModelSpec(name=cfg.name, hidden=cfg.d_model,
                      layers=cfg.n_layers, vocab=cfg.vocab,
                      seq_len=args.seq_len, global_batch=args.global_batch,
                      d_ff=cfg.d_ff or 4 * cfg.d_model)
    job = JobSpec(n_gpus=d * m, tp=min(m, per_node), pp=1, model=mspec,
                  gpus_per_node=per_node)
    comm = build_comm_matrix(job)
    alpha, beta, unit = CharacterizationDB().affinity_for(comm)
    res = get_scheduler(args.scheduler).schedule(ScheduleRequest(
        comm=comm, cluster=cluster, alpha=alpha, beta=beta, unit=unit,
    ))
    mesh = make_arnold_mesh(res.placement, tp=job.tp, shape=(d, m),
                            axes=("data", "model"), gpus_per_node=per_node)
    print(f"Arnold placement [{res.method}]: pods={res.n_pods_used()} "
          f"spread(data axis)={mesh_group_spread(mesh, 'data', 4 * per_node)}")
    return mesh


def main(argv=None) -> int:
    losses = run(argv)
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    sys.exit(main())
