"""Pipeline parallelism via shard_map + collective_permute (GPipe schedule).

This realizes the PP dimension of the comm matrix (columns) in JAX: stages
are sharded over a ``stage`` mesh axis, microbatches stream through a
``lax.scan`` of compute->``ppermute`` ticks, and reverse-mode AD through the
scan yields the backward pipeline automatically (ppermute's transpose is the
reverse ppermute), i.e. a fwd-all/bwd-all GPipe with bubble fraction
(S-1)/(m+S-1).

The boundary traffic per tick is exactly the paper's Eq. 13 PP volume
(2*mb*s*h bytes counting fwd+bwd), which is what the Arnold scheduler's
``v_p`` models -- see tests/test_pipeline.py for the volume assertion.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable,        # (stage_params, x) -> x  : one stage's compute
    n_stages: int,
    axis_name: str = "stage",
):
    """Build fn(stacked_stage_params, microbatched_x) -> y, to be called
    INSIDE shard_map where ``axis_name`` has size n_stages.

    x: (m, mb, ...) microbatches, identical on all stages (stage 0 consumes
    them); returns (m, mb, ...) outputs valid on the LAST stage.
    """

    def fn(stage_params, x_mb):
        stage = jax.lax.axis_index(axis_name)
        m = x_mb.shape[0]
        n_ticks = m + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        buf = jnp.zeros_like(x_mb[0])
        outs = jnp.zeros((m,) + x_mb.shape[1:], x_mb.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (while t < m); others use buf
            mb_in = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, m - 1), keepdims=False
            )
            x_in = jnp.where(stage == 0, mb_in, buf)
            y = stage_fn(stage_params, x_in)
            # last stage writes its result for microbatch (t - (S-1))
            out_idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, m - 1), 0
                ),
                lambda o: o,
                outs,
            )
            # boundary send-recv: stage i -> i+1 (Eq. 13 traffic)
            buf = jax.lax.ppermute(y, axis_name, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        # so out_specs=P() is well-defined on every stage
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis_name
        )
        return outs

    return fn


def make_pp_loss_fn(
    embed_fn: Callable,        # (params, batch) -> x0 (m, mb, s, d)
    stage_fn: Callable,        # (stage_params, x) -> x
    head_loss_fn: Callable,    # (params, x_out, batch) -> scalar loss
    mesh: Mesh,
    n_stages: int,
    axis_name: str = "stage",
):
    """End-to-end pipelined loss under shard_map: stage params sharded over
    the stage axis (leading dim), everything else replicated."""
    pipe = pipeline_forward(stage_fn, n_stages, axis_name)

    def loss(params, batch):
        def inner(stage_params, shared_params, batch):
            x0 = embed_fn(shared_params, batch)
            x_stage = jax.tree.map(lambda a: a[0], stage_params)  # local slice
            y = pipe(x_stage, x0)
            return head_loss_fn(shared_params, y, batch)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(axis_name), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(params["stages"], params["shared"], batch)

    return loss


def pp_boundary_bytes(mb: int, seq: int, d_model: int, n_microbatches: int,
                      bytes_per_el: int = 2) -> int:
    """Eq. 13 check: bytes crossing one PP boundary per step (fwd + bwd)."""
    return 2 * mb * seq * d_model * n_microbatches * bytes_per_el
