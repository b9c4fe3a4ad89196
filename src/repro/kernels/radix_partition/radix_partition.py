"""Radix partitioning (hash binning) as Pallas TPU kernels.

The shuffle-preparation hot spot: every row gets a partition id
``h & (P-1)``, and the exchange needs either per-tile histograms
(``radix_partition``) or, fused, each row's slot in a bounded
per-destination bucket (``partition_scatter``).  GPU radix partitioning
uses shared-memory atomics; here rows are laid out lane-dense,
``(N/128, 128)``, and every block is a whole number of (8, 128) tiles:

* histograms are lane reductions of ``pid == p`` per lane row;
* arrival ranks are prefix counts in row-major order, taken on the MXU
  as ``onehot @ strict_upper(128)`` within a lane row plus
  ``strict_lower(R) @ onehot`` across the block's lane rows, with the
  per-destination totals of earlier blocks carried in VMEM scratch
  across the sequential grid.

Hashes enter as int32 bit patterns (only the low bits are read) and
validity as int32, the layouts Mosaic tiles natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANES, SUBLANES, block_rows, lane_dense


def _lanes(hashes, valid, n_pad):
    h = jax.lax.bitcast_convert_type(hashes.astype(jnp.uint32), jnp.int32)
    return (lane_dense(h, n_pad, 0),
            lane_dense(valid.astype(jnp.int32), n_pad, 0))


def _pid(hash_ref, valid_ref, n_parts):
    valid = valid_ref[...] != 0
    pid = jnp.where(valid, hash_ref[...] & (n_parts - 1), n_parts)
    return pid, valid


def _radix_kernel(hash_ref, valid_ref, pid_ref, hist_ref, *, n_parts):
    pid, _ = _pid(hash_ref, valid_ref, n_parts)      # invalid parked at P
    pid_ref[...] = pid
    for p in range(n_parts):
        hist_ref[:, p:p + 1] = jnp.sum(
            (pid == p).astype(jnp.float32), axis=1,
            keepdims=True).astype(jnp.int32)


def _tile_hist(hist_rows, n: int, tile_n: int):
    """Regroup per-lane-row histograms into per-``tile_n``-row ones."""
    t = min(tile_n, n)
    n_tiles = -(-n // t)
    if n_tiles == 1:
        return jnp.sum(hist_rows, axis=0, keepdims=True)
    if t % LANES:
        raise ValueError(f"tile_n={tile_n} must be a multiple of {LANES}")
    per = t // LANES
    return hist_rows[:n_tiles * per].reshape(n_tiles, per, -1).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("n_parts", "tile_n",
                                             "interpret"))
def radix_partition(hashes, valid, *, n_parts: int, tile_n: int = 256,
                    interpret: bool = False):
    """hashes: (N,) uint32; valid: (N,) bool; n_parts power of two.
    Returns (pid (N,) int32 with invalid rows = n_parts,
             hist (ceil(N / tile_n), n_parts) int32 — rows per tile).
    ``tile_n`` is a multiple of 128 unless one tile covers all N."""
    assert n_parts & (n_parts - 1) == 0
    n = hashes.shape[0]
    blk = min(block_rows(tile_n), block_rows(n))
    n_pad = -(-n // blk) * blk
    rows = blk // LANES
    h, v = _lanes(hashes, valid, n_pad)

    pid, hist = pl.pallas_call(
        functools.partial(_radix_kernel, n_parts=n_parts),
        grid=(n_pad // blk,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((rows, n_parts), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(h.shape, jnp.int32),
                   jax.ShapeDtypeStruct((h.shape[0], n_parts), jnp.int32)],
        name="radix_partition",
        interpret=interpret,
    )(h, v)
    return pid.reshape(n_pad)[:n], _tile_hist(hist, n, tile_n)


def _scatter_kernel(hash_ref, valid_ref, slot_ref, ovf_ref, count_ref, *,
                    n_parts, bucket):
    """Fused binning + bucket-slot assignment over one block.

    ``count_ref`` row p holds (broadcast over lanes) the valid rows of
    earlier blocks bound for destination p; it carries across the
    sequential grid, so each row's rank is its global arrival rank —
    identical to what a stable sort by destination would give."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    pid, valid = _pid(hash_ref, valid_ref, n_parts)
    rows = pid.shape[0]
    lane_before = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
                   < jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
                   ).astype(jnp.float32)
    row_before = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
                  < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
                  ).astype(jnp.float32)
    rank = jnp.zeros(pid.shape, jnp.int32)
    for p in range(n_parts):
        hit = (pid == p).astype(jnp.float32)        # invalid rows park at P
        in_row = jnp.dot(hit, lane_before,
                         preferred_element_type=jnp.float32)
        above = jnp.sum(jnp.dot(row_before, hit,
                                preferred_element_type=jnp.float32),
                        axis=1, keepdims=True)
        base = count_ref[p:p + 1, :]
        rank = jnp.where(pid == p, base + (in_row + above).astype(jnp.int32),
                         rank)
        total = jnp.sum(jnp.sum(hit, axis=1, keepdims=True), axis=0,
                        keepdims=True).astype(jnp.int32)
        count_ref[p:p + 1, :] = base + total
    keep = valid & (rank < bucket)
    slot_ref[...] = jnp.where(keep, pid * bucket + rank, n_parts * bucket)
    lost = (valid & ~keep).astype(jnp.int32)
    ovf_ref[...] = jnp.sum(lost.reshape(rows // SUBLANES, SUBLANES, LANES),
                           axis=0)


@functools.partial(jax.jit, static_argnames=("n_parts", "bucket", "tile_n",
                                             "interpret"))
def partition_scatter(hashes, valid, *, n_parts: int, bucket: int,
                      tile_n: int = 1024, interpret: bool = False):
    """hashes: (N,) uint32; valid: (N,) bool; n_parts power of two; any
    N, ``tile_n`` rows per grid step rounded up to whole (8, 128) tiles.
    Returns (slot (N,) int32 in [0, n_parts*bucket] with n_parts*bucket
    the drop slot, overflow () int32) — see ``partition_scatter_ref``."""
    assert n_parts & (n_parts - 1) == 0
    n = hashes.shape[0]
    blk = min(block_rows(tile_n), block_rows(n))
    n_pad = -(-n // blk) * blk
    rows = blk // LANES
    n_blocks = n_pad // blk
    h, v = _lanes(hashes, valid, n_pad)

    slot, ovf = pl.pallas_call(
        functools.partial(_scatter_kernel, n_parts=n_parts, bucket=bucket),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(h.shape, jnp.int32),
                   jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES),
                                        jnp.int32)],
        scratch_shapes=[pltpu.VMEM((max(SUBLANES, n_parts), LANES),
                                   jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="partition_scatter",
        interpret=interpret,
    )(h, v)
    return slot.reshape(n_pad)[:n], jnp.sum(ovf)
