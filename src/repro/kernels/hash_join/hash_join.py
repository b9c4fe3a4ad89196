"""Join probe as a Pallas TPU kernel: a branch-free rank count.

The join hot spot.  GPU hash joins build shared-memory hash tables with
atomics; a TPU kernel has no vector gather from VMEM, so a binary
search cannot lower.  The probe instead counts, for every probe key,
the build keys below it — which for a sorted build side IS the leftmost
insertion point (``searchsorted(side='left')``).  The build side sits
whole in SMEM and is streamed as scalars; each one is compared against a
lane-dense ``(8k, 128)`` block of probe keys, so the work is
O(N * R) vector compares with no gather at all.  That suits the small
dimension tables the engine joins against; the operator keeps larger
build sides on XLA's search (``dataflow/physical.py``).

uint32 keys are compared as int32 after flipping the sign bit, which
preserves their unsigned order.  Exact-key verification happens in the
operator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANES, block_rows, lane_dense

# the largest build side the kernel is compiled for: SMEM holds it whole
MAX_BUILD_ROWS = 8192
_SIGN = 0x80000000


def _as_ordered_i32(h):
    return jax.lax.bitcast_convert_type(h.astype(jnp.uint32)
                                        ^ jnp.uint32(_SIGN), jnp.int32)


def _probe_kernel(rhash_ref, lhash_ref, pos_ref, *, n_right):
    lh = lhash_ref[...]                   # (R, 128) int32 probe keys

    def body(r, cnt):
        return cnt + (rhash_ref[r] < lh).astype(jnp.int32)

    pos_ref[...] = jax.lax.fori_loop(0, n_right, body,
                                     jnp.zeros(lh.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def join_probe(left_hashes, right_hashes_sorted, *, tile_n: int = 1024,
               interpret: bool = False):
    """left_hashes: (N,) uint32, any N; right_hashes_sorted: (R,) uint32
    ascending, R <= MAX_BUILD_ROWS.  Returns pos (N,) int32 =
    searchsorted(right, left, side='left')."""
    n = left_hashes.shape[0]
    r = right_hashes_sorted.shape[0]
    assert r <= MAX_BUILD_ROWS, (r, MAX_BUILD_ROWS)
    tile = min(block_rows(tile_n), block_rows(n))
    n_pad = -(-n // tile) * tile
    rows = tile // LANES
    lh = lane_dense(_as_ordered_i32(left_hashes), n_pad, 0)

    pos = pl.pallas_call(
        functools.partial(_probe_kernel, n_right=r),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # build side resident
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(lh.shape, jnp.int32),
        name="join_probe",
        interpret=interpret,
    )(_as_ordered_i32(right_hashes_sorted), lh)
    return pos.reshape(n_pad)[:n]
