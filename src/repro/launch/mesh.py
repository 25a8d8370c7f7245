"""Production mesh construction.

FUNCTIONS (not module constants) so importing this module never touches
jax device state.  Single pod: 16x16 = 256 chips ("data","model").
Multi-pod: 2x16x16 = 512 chips ("pod","data","model") — "pod" extends the
data-parallel/FSDP group across the inter-pod (DCN/ICI) boundary.

Every mesh has ``Auto`` axes: the model code places values with
``with_sharding_constraint`` and shard_map, and leaves the rest to the
partitioner, which ``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(devices=None):
    """1x1 mesh with the production axis names — lets shard_map code paths
    run unmodified in single-device tests."""
    return make_mesh((1, 1), ("data", "model"),
                     devices=devices or jax.devices()[:1])
