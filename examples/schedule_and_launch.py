"""The paper's full flow, end to end on 64 fake devices:

  1. a CLOS cluster (8 minipods) + an LPJ spec (64 GPUs, TP=4, PP=2)
  2. communication matrix (Eq. 1) + affinity lookup (characterization DB)
  3. Arnold's MILP placement (Eq. 4-10) vs a naive packing baseline
  4. placement -> logical-rank device permutation -> JAX mesh
  5. verify the mesh's communication-group spread dropped (Eq. 3 on-mesh)
  6. run sharded pjit train steps on the Arnold mesh

Run:  PYTHONPATH=src python examples/schedule_and_launch.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"

import jax
import jax.numpy as jnp

from repro.core import (
    CharacterizationDB,
    Cluster,
    JobSpec,
    ModelSpec,
    ScheduleRequest,
    build_comm_matrix,
    get_scheduler,
    list_schedulers,
)
from repro.configs import get_config
from repro.data import SyntheticDataset
from repro.launch.mesh import make_arnold_mesh, make_mesh, mesh_group_spread
from repro.models import ModelOptions, build_model
from repro.optim import AdamWConfig, init_opt_state
from repro.parallel import sharding as shd
from repro.train import make_train_step

DEVICES_PER_POD = 16  # fake-device convention: contiguous id blocks = pods


def main():
    # -- 1. cluster + job ----------------------------------------------------
    cluster = Cluster.uniform(4, 2)        # 4 minipods x 2 nodes = 64 GPUs
    arch = get_config("minicpm-2b")
    mspec = ModelSpec(
        name=arch.name, hidden=arch.d_model, layers=arch.n_layers,
        vocab=arch.vocab, seq_len=64, global_batch=16, d_ff=arch.d_ff,
    )
    job = JobSpec(n_gpus=64, tp=4, pp=2, model=mspec)

    # -- 2. comm matrix + affinity -------------------------------------------
    comm = build_comm_matrix(job)
    alpha, beta, unit = CharacterizationDB().affinity_for(comm)
    print(f"comm matrix {comm.shape}; v_d={comm.v_d/2**20:.0f} MiB "
          f"v_p={comm.v_p/2**20:.1f} MiB; affinity alpha={alpha:.2f} unit={unit}")

    # -- 3. MILP placement vs baseline, via the unified scheduler API --------
    request = ScheduleRequest(comm=comm, cluster=cluster, alpha=alpha,
                              beta=beta, unit=unit)
    print(f"registered schedulers: {list_schedulers()}")
    res = get_scheduler("mip").schedule(request)
    base = get_scheduler("gpu-packing").schedule(request)
    print(f"Arnold spreads (dp, pp): ({res.dp_spread}, {res.pp_spread}) "
          f"[{res.method}, {res.solve_seconds*1e3:.1f} ms]")
    print(f"packing spreads (dp, pp): ({base.dp_spread}, {base.pp_spread})")

    # -- 4./5. mesh from the placement ---------------------------------------
    mesh = make_arnold_mesh(res.placement, tp=job.tp, shape=(8, 8),
                            axes=("data", "model"))
    naive = make_mesh((8, 8), ("data", "model"))
    for name, m in [("arnold", mesh), ("naive", naive)]:
        print(f"{name} mesh: model-axis spread="
              f"{mesh_group_spread(m, 'model', DEVICES_PER_POD)}, "
              f"data-axis spread="
              f"{mesh_group_spread(m, 'data', DEVICES_PER_POD)}")

    # -- 6. sharded training steps on the Arnold mesh ------------------------
    cfg = arch.reduced()
    model = build_model(cfg, ModelOptions(remat=False))
    ds = SyntheticDataset(cfg.vocab, seq_len=64, global_batch=16)
    opt = AdamWConfig(lr=1e-3)
    params = model.init(jax.random.PRNGKey(0))
    state = init_opt_state(params)
    with shd.activate(mesh):
        stepper = make_train_step(model, opt, mesh=mesh, donate=False)
        batch0 = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
        fn = stepper(jax.eval_shape(lambda: batch0))
        for i in range(3):
            batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
            params, state, metrics = fn(params, state, batch)
            print(f"sharded step {i}: loss={float(metrics['loss']):.4f}")
    print("OK: scheduled, placed, and trained on the Arnold-aligned mesh")


if __name__ == "__main__":
    main()
