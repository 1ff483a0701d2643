"""Tests for the workload representation (Eq. 1) and Appendix C volumes."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need it; skip, not error
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import (
    JobSpec,
    ModelSpec,
    build_comm_matrix,
    dp_volume_bytes,
    ep_volume_bytes,
    pp_volume_bytes,
)

GB = 1 << 30
MB = 1 << 20


class TestEq1:
    def test_paper_example(self, model7b):
        """Figure 12: 96 GPUs, DP=6, PP=2 -> 6x2 matrix of 12 nodes."""
        job = JobSpec(n_gpus=96, tp=4, pp=2, model=model7b)
        comm = build_comm_matrix(job)
        assert job.dp == 12  # 96/4/2
        assert comm.shape == (6, 2)  # DP/(8/TP) = 12/2 = 6 rows, PP=2 cols
        assert comm.n_cells == job.n_nodes == 12

    @given(
        tp=st.sampled_from([1, 2, 4, 8]),
        pp=st.sampled_from([1, 2, 4, 8]),
        rows=st.integers(1, 16),
    )
    @settings(max_examples=60, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    def test_matrix_accounts_every_node(self, model7b, tp, pp, rows):
        dp = rows * (8 // tp)
        job = JobSpec(n_gpus=dp * tp * pp, tp=tp, pp=pp, model=model7b)
        comm = build_comm_matrix(job)
        assert comm.n_rows * comm.n_cols == job.n_nodes
        assert comm.n_cols == pp

    def test_rejects_intra_node_tp_violation(self, model7b):
        with pytest.raises(ValueError):
            JobSpec(n_gpus=96, tp=16, pp=2, model=model7b)  # TP > node size

    def test_rejects_non_divisible(self, model7b):
        with pytest.raises(ValueError):
            JobSpec(n_gpus=100, tp=4, pp=2, model=model7b)

    @pytest.mark.parametrize("per_node,n_gpus,tp,shape", [
        (4, 4, 2, (1, 1)),     # one TPU v5e host: 4 chips, (data=2, model=2)
        (4, 16, 2, (4, 1)),    # four such hosts
        (8, 8, 4, (1, 1)),     # the paper's 8-GPU node
    ])
    def test_node_size_follows_the_host(self, model7b, per_node, n_gpus, tp, shape):
        job = JobSpec(n_gpus=n_gpus, tp=tp, pp=1, model=model7b,
                      gpus_per_node=per_node)
        comm = build_comm_matrix(job)
        assert comm.shape == shape
        assert comm.n_cells == job.n_nodes == n_gpus // per_node

    def test_default_node_rejects_a_four_chip_job(self, model7b):
        with pytest.raises(ValueError, match="8 GPUs per node"):
            JobSpec(n_gpus=4, tp=2, pp=1, model=model7b)


class TestAppendixC:
    def test_paper_sanity_numbers(self, model7b):
        """§4: 'substituting the parameters with a 7B GPT-based model ... the
        data volumes of the DP and PP groups are 2 GB and 30 MB'."""
        job = JobSpec(n_gpus=64, tp=4, pp=8, model=model7b)
        v_d = dp_volume_bytes(job)
        v_p = pp_volume_bytes(job)
        assert 1.5 * GB < v_d < 2.5 * GB, f"DP volume {v_d / GB:.2f} GB"
        assert 25 * MB < v_p < 40 * MB, f"PP volume {v_p / MB:.1f} MB"

    def test_dp_volume_scales_inverse_pp(self, model7b):
        j2 = JobSpec(n_gpus=64, tp=4, pp=2, model=model7b)
        j8 = JobSpec(n_gpus=256, tp=4, pp=8, model=model7b)
        # layer term dominates; embedding term is PP-independent
        assert dp_volume_bytes(j2) > 2.5 * dp_volume_bytes(j8)

    def test_pp_volume_independent_of_pp_degree(self, model7b):
        j2 = JobSpec(n_gpus=64, tp=4, pp=2, model=model7b)
        j8 = JobSpec(n_gpus=256, tp=4, pp=8, model=model7b)
        assert pp_volume_bytes(j2) == pp_volume_bytes(j8)

    def test_moe_ep_volume(self):
        moe = ModelSpec(
            name="moe", hidden=4096, layers=24, vocab=50304, seq_len=2048,
            global_batch=512, micro_batch=1, n_experts=16, top_k=4, d_expert=8192,
        )
        job = JobSpec(n_gpus=128, tp=4, pp=2, model=moe)
        v_e = ep_volume_bytes(job)
        # 2 * top_k * s * h * bytes = 2*4*2048*4096*2
        assert v_e == 2 * 4 * 2048 * 4096 * 2
        dense = ModelSpec(
            name="d", hidden=4096, layers=24, vocab=50304, seq_len=2048,
            global_batch=512, d_ff=16384,
        )
        assert ep_volume_bytes(JobSpec(n_gpus=128, tp=4, pp=2, model=dense)) == 0

    def test_ratios_positive(self, small_comm):
        r1, r2 = small_comm.ratios()
        assert r1 > 0 and r2 > 0
        # dense LPJ: DP volume >> PP volume per step
        assert r2 > 1
