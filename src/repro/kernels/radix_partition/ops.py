"""Jit'd wrappers with impl dispatch.

Both entry points accept ANY row count: the kernels pad with invalid
rows to whole tiles and slice back, so callers never reason about tile
granularity (padded rows are invalid, which both kernels park/drop by
construction).  The ref tiles its histogram the same way the ops
wrapper always did, padding to a multiple of ``min(tile_n, N)``.
"""
import jax.numpy as jnp

from ... import kernels
from .radix_partition import partition_scatter, radix_partition
from .ref import partition_scatter_ref, radix_partition_ref


def _pad_invalid(hashes, valid, tile_n):
    n = hashes.shape[0]
    pad = (-n) % min(tile_n, n)
    if pad == 0:
        return hashes, valid, n
    return (jnp.concatenate([hashes, jnp.zeros((pad,), hashes.dtype)]),
            jnp.concatenate([valid, jnp.zeros((pad,), bool)]), n)


def partition(hashes, valid, *, n_parts: int, impl: str = "ref",
              tile_n: int = 256, interpret=None):
    h, v, n = _pad_invalid(hashes, valid, tile_n)
    if impl == "pallas":
        pid, hist = radix_partition(
            h, v, n_parts=n_parts, tile_n=tile_n,
            interpret=kernels.resolve_interpret(interpret))
    else:
        pid, hist = radix_partition_ref(h, v, n_parts=n_parts,
                                        tile_n=tile_n)
    return pid[:n], hist


def scatter_slots(hashes, valid, *, n_parts: int, bucket: int,
                  impl: str = "ref", tile_n: int = 1024, interpret=None):
    """Fused partition + bucket-scatter slots (DESIGN.md §14).  Returns
    (slot (N,) int32 — ``n_parts * bucket`` is the drop slot — and the
    scalar count of valid rows that overflowed their bucket)."""
    if impl == "pallas" and n_parts & (n_parts - 1) == 0:
        return partition_scatter(
            hashes, valid, n_parts=n_parts, bucket=bucket, tile_n=tile_n,
            interpret=kernels.resolve_interpret(interpret))
    return partition_scatter_ref(hashes, valid, n_parts=n_parts,
                                 bucket=bucket, tile_n=tile_n)
