"""Production meshes (TPU v5e).  Single pod: 256 chips as (data=16,
model=16); two pods: (pod=2, data=16, model=16) with the pod axis as an
outer data-parallel dimension (cross-pod traffic = gradient all-reduce only).

Functions, not module constants — importing this module never touches jax
device state (smoke tests must keep seeing 1 CPU device; only
launch/dryrun.py forces 512 virtual devices, in its first two lines).

Every mesh declares Auto axis types: the engine's jits place arrays with
explicit ``NamedSharding`` in/out shardings and leave the rest to GSPMD
propagation, which ``jax.make_mesh``'s default Explicit axes reject.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for CI-scale pjit tests (8 virtual devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_serving_mesh(n_data: Optional[int] = None):
    """Data-only mesh for sharded continuous serving (replicated params,
    slot-pool capacity axis sharded over ``data``).  Defaults to every
    visible device.  On CPU, force multiple host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax is
    imported (see tests/test_sharded_serving.py)."""
    n = n_data if n_data is not None else len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes of a mesh ('pod' folds into data-parallel)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
