"""Sorted segment reduction as a Pallas TPU kernel.

The relational GROUPBY hot spot.  GPU implementations use shared-memory
hash tables + atomics; the TPU-native design exploits that rows arrive
*sorted by segment* with dense ids: each run of 128 consecutive rows
(one lane row of the lane-dense ``(N/128, 128)`` layout) touches at most
128 consecutive segment ids, so it reduces to a one-hot matmul on the
MXU

    partial[row] = values[row] @ onehot(seg[row] - seg[row, 0])^T   (1 x 128)

with a cheap jnp scatter-add combine across lane rows in the wrapper
(the boundary segment of adjacent lane rows overlaps, which the combine
resolves — no atomics anywhere).  Blocks are whole (8, 128) tiles, so
Mosaic lowers them without relayouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import LANES, block_rows, lane_dense


def _seg_kernel(seg_ref, val_ref, part_ref, *, n_cols):
    seg = seg_ref[...]                            # (R, 128) int32, sorted
    off = seg - seg[:, 0:1]                       # 0 <= off < 128 if live
    sid = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    for j in range(seg.shape[0]):
        # (segment offset, row) one-hot of lane row j; rows whose offset
        # falls outside the lane row's 128 ids match no column
        onehot = (sid == off[j:j + 1, :]).astype(jnp.float32)
        for k in range(n_cols):
            part_ref[k, j:j + 1, :] = jax.lax.dot_general(
                val_ref[k, j:j + 1, :], onehot, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_segments", "tile_n",
                                             "interpret"))
def segment_sum_sorted(values, seg_ids, *, num_segments: int,
                       tile_n: int = 1024, interpret: bool = False):
    """values: (N, D) f32; seg_ids: (N,) int32 sorted ascending AND dense
    (consecutive ids, as produced by cumsum-over-boundaries — the engine's
    GROUPBY contract; 128 consecutive rows then span <= 128 ids).  Rows
    with out-of-range ids (e.g. a num_segments sentinel) are dropped.
    Any N; ``tile_n`` rows per grid step, rounded up to whole (8, 128)
    tiles.  Returns (S, D)."""
    n, d = values.shape
    tile = min(block_rows(tile_n), block_rows(n))
    n_pad = -(-n // tile) * tile
    rows = tile // LANES
    # padded rows carry value 0 and the out-of-range id num_segments,
    # which keeps the lane sorted and outside every real segment
    seg = lane_dense(seg_ids.astype(jnp.int32), n_pad, num_segments)
    vals = jnp.stack([lane_dense(values[:, k].astype(jnp.float32),
                                 n_pad, 0.0) for k in range(d)])

    parts = pl.pallas_call(
        functools.partial(_seg_kernel, n_cols=d),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((d, rows, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((d, rows, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(vals.shape, jnp.float32),
        name="segment_sum_sorted",
        interpret=interpret,
    )(seg, vals)

    # combine: scatter-add each lane row's partials at its base id
    idx = seg[:, 0:1] + jnp.arange(LANES, dtype=jnp.int32)[None, :]
    # negative ids are out-of-range like the >= num_segments sentinel,
    # but mode="drop" only drops high indices — it WRAPS negatives, so
    # push them past the end explicitly
    idx = jnp.where(idx < 0, num_segments, idx)
    out = jnp.zeros((num_segments, d), jnp.float32)
    return out.at[idx.reshape(-1)].add(
        parts.reshape(d, n_pad).T, mode="drop")
