"""Integration: Arnold placement -> device permutation -> JAX mesh, and the
on-mesh spread verification (the JAX-side analogue of Eq. 3)."""

import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import REPO_ROOT, cpu_child_env
from repro.core import (
    Cluster,
    JobSpec,
    ModelSpec,
    ScheduleRequest,
    build_comm_matrix,
    device_permutation,
    get_scheduler,
    logical_to_physical_gpus,
)

MODEL = ModelSpec(name="m", hidden=1024, layers=8, vocab=5000, seq_len=128,
                  global_batch=64, d_ff=4096)


class TestRankAssign:
    def test_permutation_is_bijection(self):
        cluster = Cluster.uniform(4, 4)
        comm = build_comm_matrix(JobSpec(n_gpus=64, tp=4, pp=2, model=MODEL))
        res = get_scheduler("mip").schedule(
            ScheduleRequest(comm=comm, cluster=cluster, alpha=0.3))
        perm = device_permutation(res.placement, tp=4)
        assert sorted(perm) == sorted(
            g for n in res.placement.node_ids() for g in range(n * 8, n * 8 + 8)
        )

    def test_tp_stays_intra_node(self):
        """TP ranks of any (pp, dp) pair must map to the same physical node
        (the paper's §2 invariant: TP on NVLink only)."""
        cluster = Cluster.uniform(4, 4)
        comm = build_comm_matrix(JobSpec(n_gpus=64, tp=4, pp=2, model=MODEL))
        res = get_scheduler("mip").schedule(
            ScheduleRequest(comm=comm, cluster=cluster, alpha=0.3))
        phys = logical_to_physical_gpus(res.placement, tp=4)
        nodes = phys // 8
        assert (nodes == nodes[..., :1]).all()

    def test_dp_groups_align_to_pods(self):
        """With alpha=1 (pure DP consolidation) on an ample cluster, every
        DP group should land inside one minipod."""
        cluster = Cluster.uniform(2, 12)
        comm = build_comm_matrix(JobSpec(n_gpus=96, tp=4, pp=2, model=MODEL))
        res = get_scheduler("mip").schedule(
            ScheduleRequest(comm=comm, cluster=cluster, alpha=1.0, unit="dp"))
        phys = logical_to_physical_gpus(res.placement, tp=4)  # (pp, dp, tp)
        pods = phys // (8 * 12)
        for c in range(phys.shape[0]):
            assert len(np.unique(pods[c])) == 1, f"DP group of stage {c} spans pods"


class TestArnoldMeshOnDevices:
    def test_arnold_mesh_reduces_spread(self):
        """On 64 fake devices (4 pods x 16), a fragmented cluster forces the
        naive id-order mesh to split communication groups across pods;
        the Arnold-ordered mesh must not be worse on the model axis."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
            import json
            import jax
            from repro.core import (Cluster, JobSpec, ModelSpec, ScheduleRequest,
                                    build_comm_matrix, get_scheduler)
            from repro.launch.mesh import (make_arnold_mesh, make_mesh,
                                           mesh_group_spread)

            cluster = Cluster.uniform(4, 2)  # 4 pods x 2 nodes (16 devs/pod)
            model = ModelSpec(name="m", hidden=1024, layers=8, vocab=5000,
                              seq_len=128, global_batch=64, d_ff=4096)
            comm = build_comm_matrix(JobSpec(n_gpus=64, tp=8, pp=2, model=model))
            res = get_scheduler("mip").schedule(
                ScheduleRequest(comm=comm, cluster=cluster, alpha=0.0))
            mesh = make_arnold_mesh(res.placement, tp=8, shape=(8, 8),
                                    axes=("data", "model"))
            naive = make_mesh((8, 8), ("data", "model"))
            out = {
                "arnold_model": mesh_group_spread(mesh, "model", 16),
                "naive_model": mesh_group_spread(naive, "model", 16),
                "arnold_data": mesh_group_spread(mesh, "data", 16),
                "naive_data": mesh_group_spread(naive, "data", 16),
            }
            print(json.dumps(out))
        """)
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600, env=cpu_child_env(), cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        # TP (model axis) groups always stay intra-node -> spread 1
        assert out["arnold_model"] == 1
        assert out["arnold_data"] <= out["naive_data"]
