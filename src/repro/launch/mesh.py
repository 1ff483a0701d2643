"""Production mesh construction + Arnold-aligned device ordering.

``make_mesh`` is the one mesh constructor every caller uses: its axes are
``Auto`` (the sharding rules in ``parallel.sharding`` place arrays through
``with_sharding_constraint``, which only accepts Auto axes; ``jax.make_mesh``
defaults to Explicit ones).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod = (16, 16) over (data, model) = 256 chips;
multi-pod = (2, 16, 16) over (pod, data, model) = 512 chips.

``make_arnold_mesh`` is the paper's integration point: Arnold's MILP output
(a Placement) is converted to a logical->physical device permutation
(core/rank_assign.py) so mesh axes -- pjit's communication groups -- land on
the physical blocks the scheduler aligned.  On the fake-device dry-run the
"physical topology" is device-id order (contiguous id blocks = minipods),
mirroring how real TPU runtimes expose topology through device order.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

from repro.core.rank_assign import device_permutation
from repro.core.spread import Placement


def make_mesh(shape: tuple, axes: tuple, devices=None) -> Mesh:
    """Mesh of ``shape`` over ``axes`` with Auto axis types.  ``devices``
    (in mesh order) defaults to JAX's own device order for the shape."""
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    return Mesh(np.array(devices, dtype=object).reshape(shape), axes,
                axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_arnold_mesh(
    placement: Placement,
    tp: int,
    shape: tuple,
    axes: tuple,
    devices=None,
    gpus_per_node: int = 8,
) -> Mesh:
    """Mesh whose device order follows an Arnold placement.

    The permutation orders devices by logical rank (pp, dp, tp); reshaped
    into ``shape`` (which must multiply to the permutation length), mesh
    axes then map onto scheduler-aligned physical blocks.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    perm = device_permutation(placement, tp, gpus_per_node)
    if len(perm) > len(devices):
        raise ValueError(f"placement needs {len(perm)} devices, have {len(devices)}")
    return make_mesh(shape, axes, devices=[devices[i] for i in perm])


def mesh_device_minipods(mesh: Mesh, devices_per_pod: int) -> np.ndarray:
    """Minipod id of every device in the mesh (by id-block convention)."""
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    return ids // devices_per_pod


def mesh_group_spread(mesh: Mesh, axis: str, devices_per_pod: int) -> int:
    """Max spread (distinct minipods) over the communication groups of one
    mesh axis -- the JAX-side analogue of Eq. 3, used to verify that Arnold
    ordering actually reduces group spread on the fake-device cluster."""
    pods = mesh_device_minipods(mesh, devices_per_pod)
    axis_idx = mesh.axis_names.index(axis)
    moved = np.moveaxis(pods, axis_idx, 0)
    flat = moved.reshape(moved.shape[0], -1)
    # one group per column: devices varying along `axis` with others fixed
    spreads = [len(np.unique(flat[:, c])) for c in range(flat.shape[1])]
    return int(max(spreads))
