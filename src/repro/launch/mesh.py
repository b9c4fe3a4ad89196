"""Production mesh construction + the repo's one ``shard_map`` entry.

Mesh builders are FUNCTIONS (not module-level constants) so importing
this module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import, and tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by
    default.  Every shard_map in this repo goes through here so an API
    rename surfaces in exactly one place."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def _mesh(shape, axes):
    """``jax.make_mesh`` with auto (compiler-chosen) sharding on every
    axis: the engine and the models leave intermediate shardings to the
    compiler, which an explicit-axes mesh (the default) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_data_mesh(n_shards: int, axis: str = "data"):
    """1-D data mesh over the first ``n_shards`` devices — the MapReduce
    scale-out axis of the relational engine (DESIGN.md §11)."""
    n = len(jax.devices())
    assert n_shards <= n, (n_shards, n)
    return _mesh((n_shards,), (axis,))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (real or forced-host) devices exist —
    used by distributed tests and the CPU examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
