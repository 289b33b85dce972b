"""Production mesh construction (a FUNCTION, so importing never touches jax
device state — required by the dry-run protocol)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor of the repo: every axis Auto.  jax's own
    ``make_mesh`` defaults to Explicit axes, under which the optimizer's
    block-domain reshapes raise ``ShardingTypeError``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e pod), or 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU example runs."""
    return make_mesh((1, 1), ("data", "model"))


def data_parallel_degree(mesh, axes=("pod", "data")) -> int:
    """Product of the data-parallel axis sizes present on ``mesh`` — the
    shard count the partitioned optimizer dispatch owns spans over
    (``OptimConfig.partition_shards``; DESIGN.md §12)."""
    deg = 1
    for a in axes:
        if a in mesh.axis_names:
            deg *= int(mesh.shape[a])
    return deg
