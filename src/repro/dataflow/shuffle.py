"""Distributed shuffle for the relational engine: the MapReduce
map->shuffle->reduce stage as a shard_map program (DESIGN.md §11).

Hadoop's sort-shuffle writes spill files; the TPU-native exchange is:

  map side   : ONE fused kernel (radix_partition.partition_scatter)
               assigns every row its destination shard AND its slot in
               a bounded per-destination bucket — binning + arrival
               rank, no sort; skew overflows are counted, as in the
               join's probe-window contract.  The reduce side's sort
               hashes are also computed here, over the small
               pre-exchange shard;
  shuffle    : all columns + validity + shipped hash lanes byte-packed
               into one buffer -> ONE jnp scatter -> ONE
               jax.lax.all_to_all along the "data" axis (the T_sort
               term of Eq. 2 becomes ICI traffic).  A join's two sides
               are independent dataflow, so XLA may overlap one side's
               collective with the other side's reduce prep;
  reduce side: rows for the same key are now co-located — the ordinary
               sort-based segment aggregation runs per shard, seeded
               with the shipped hash lanes instead of re-hashing.

Every blocking operator (GROUPBY / DISTINCT / JOIN / COGROUP) has a
distributed form here, and every one has a **shuffle-free** variant:
when the input is already hash-partitioned on compatible keys across
the same shard count (a co-partitioned repository artifact, or the
output of an upstream exchange — M3R's partition stability), the
map+all_to_all phases are skipped entirely and only the local reduce
runs.  That skip is what partition-aware reuse buys: a reused artifact
answers not just the compute but the exchange.

Losslessness: the per-destination bucket is ``min(cap_loc, max(8,
cap_loc * skew_factor / n_shards))`` rows, so ``skew_factor >=
n_shards`` makes the exchange lossless (every source shard can route
all of its rows to a single destination); smaller factors trade memory
for a counted overflow, exactly like the join probe window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.plan import _join_out_names
from ..kernels import autotune
from ..launch.mesh import shard_map
from .physical import (_cogroup_prepare, _cogroup_rename, op_distinct,
                       op_distinct_hashed, op_groupby, op_groupby_hashed,
                       op_join, use_pallas)
from .table import (Table, key_hash, pack_rows, partition_finalize,
                    unpack_rows)


def pad_to_multiple(table: Table, mult: int) -> Table:
    """Append invalid rows so ``capacity % mult == 0`` (shard_map needs
    the row dimension divisible by the mesh axis)."""
    pad = (-table.capacity) % mult
    if pad == 0:
        return table
    cols = {n: jnp.concatenate(
        [c, jnp.zeros((pad,) + c.shape[1:], c.dtype)])
        for n, c in table.columns.items()}
    valid = jnp.concatenate([table.valid, jnp.zeros((pad,), bool)])
    return Table(cols, valid)


def _bucket_size(cap_loc: int, n_shards: int, skew_factor: float) -> int:
    return min(cap_loc, max(8, int(cap_loc * skew_factor / n_shards)))


def _exchange(local: Table, keys, n_shards: int, bucket: int, axis: str):
    """Fused map-side exchange (DESIGN.md §14).  One kernel assigns
    every row its destination bucket slot (partition binning + arrival
    rank, no sort); all columns, the validity lane, and the shipped
    hash lane are byte-packed into a single buffer, so the whole
    exchange is ONE scatter and ONE all_to_all instead of one pair per
    column.  Runs inside a shard_map body.  Returns (received Table
    with capacity ``n_shards * bucket``, shipped hash lanes (a 1-tuple
    holding the seed-0 key hash), global overflow count).

    The key columns are string-folded ONCE: the routing bits are
    ``partition_finalize`` (a few integer ops) over the same seed-0
    ``key_hash`` lane that is shipped to the reduce side, where it
    seeds the segmenting / join probe instead of a re-hash over the
    inflated ``n_shards * bucket`` receive capacity — map-side prep
    the collective carries along instead of serializing the reduce
    behind it."""
    h1 = key_hash(local, keys, seed=0)
    tile = autotune.choose("partition_scatter", local.capacity, "uint32",
                           "tile_n", 1024)
    from ..kernels.radix_partition.ops import scatter_slots
    slot, overflow = scatter_slots(
        partition_finalize(h1), local.valid, n_parts=n_shards, bucket=bucket,
        impl="pallas" if use_pallas() else "ref", tile_n=tile)
    overflow = jax.lax.psum(overflow, axis)

    cols = dict(local.columns)
    cols["__h1__"] = h1
    packed, layout = pack_rows(cols, local.valid)
    row_bytes = packed.shape[1]
    n = packed.shape[0]
    # route the permutation through a 4-byte index scatter + row gather:
    # XLA CPU prices a scatter ~10x a gather of the same rows, so
    # inverting the slot map first and gathering the packed rows beats
    # scattering them directly; unhit slots gather the appended
    # zero row, which unpacks to valid=False
    inv = jnp.full((n_shards * bucket,), n, jnp.int32)
    inv = inv.at[slot].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    src = jnp.concatenate([packed, jnp.zeros((1, row_bytes), jnp.uint8)])
    buf = jnp.take(src, inv, axis=0)
    recv = jax.lax.all_to_all(
        buf.reshape(n_shards, bucket, row_bytes), axis,
        split_axis=0, concat_axis=0, tiled=False
    ).reshape(n_shards * bucket, row_bytes)
    rcols, rvalid = unpack_rows(recv, layout)
    pre = (rcols.pop("__h1__"),)
    return Table(rcols, rvalid), pre, overflow


def _table_specs(table: Table, axis: str):
    return tuple(P(axis) for _ in table.names) + (P(axis),)


def _table_args(table: Table):
    return tuple(table.col(n) for n in table.names) + (table.valid,)


def _as_local(names, flat):
    return Table(dict(zip(names, flat[:-1])), flat[-1])


def distributed_groupby(table: Table, keys, aggs, mesh,
                        axis: str = "data", skew_factor: float = 4.0,
                        co_partitioned: bool = False,
                        lossless: bool = False,
                        pre_lane=None):
    """GROUPBY over a row-sharded Table.  Returns (result table sharded
    over ``axis`` — each shard holds the groups of its hash range —
    and the global overflow count).  With ``co_partitioned`` the input
    is already hash-partitioned on (a subset of) ``keys`` across the
    shards and the exchange is skipped (DESIGN.md §11).

    The per-shard reduce is the sort-free hash-segmented groupby; its
    h1-collision count folds into the overflow so the engine's lossless
    retry covers both loss modes.  ``lossless`` selects the sort-based
    reduce (collision-proof) — the retry path.

    ``pre_lane`` optionally carries a row-aligned seed-0 ``key_hash``
    lane for ``keys`` (e.g. an upstream join's shipped hash, see
    ``distributed_join(return_pre=True)``); it seeds the reduce in the
    exchange-skipped path so co-partitioned inputs never re-hash their
    key columns.  Ignored unless ``co_partitioned``."""
    n_shards = mesh.shape[axis]
    if not co_partitioned:
        table = pad_to_multiple(table, n_shards)
        pre_lane = None   # lane rows would not survive the exchange
    names = table.names
    cap_loc = table.capacity // n_shards
    bucket = _bucket_size(cap_loc, n_shards, skew_factor)
    n_in = len(names) + 1

    def body(*flat):
        local = _as_local(names, flat[:n_in])
        if co_partitioned:
            pre = (flat[n_in],) if pre_lane is not None else None
            recv, overflow = local, jnp.zeros((), jnp.int32)
        else:
            recv, pre, overflow = _exchange(local, keys, n_shards,
                                            bucket, axis)
        if lossless:
            grouped = op_groupby(recv, keys, aggs, pre=pre)
        else:
            grouped, coll = op_groupby_hashed(recv, keys, aggs, pre=pre)
            overflow = overflow + jax.lax.psum(coll, axis)
        return _table_args(grouped) + (overflow,)

    out_names = sorted(set(list(keys) + list(aggs)))
    in_specs = _table_specs(table, axis)
    args = _table_args(table)
    if pre_lane is not None:
        in_specs = in_specs + (P(axis),)
        args = args + (pre_lane,)
    out_specs = tuple(P(axis) for _ in out_names) + (P(axis), P())
    flat = shard_map(body, mesh, in_specs, out_specs)(*args)
    return Table(dict(zip(out_names, flat[:-2])), flat[-2]), flat[-1]


def distributed_distinct(table: Table, mesh, axis: str = "data",
                         skew_factor: float = 4.0,
                         co_partitioned: bool = False,
                         lossless: bool = False):
    """DISTINCT over a row-sharded Table: exchange on all columns (equal
    rows co-locate), then the local hash-segmented (or, ``lossless``,
    sort-based) distinct per shard."""
    n_shards = mesh.shape[axis]
    if not co_partitioned:
        table = pad_to_multiple(table, n_shards)
    names = table.names
    cap_loc = table.capacity // n_shards
    bucket = _bucket_size(cap_loc, n_shards, skew_factor)

    def body(*flat):
        local = _as_local(names, flat)
        if co_partitioned:
            recv, pre, overflow = local, None, jnp.zeros((), jnp.int32)
        else:
            recv, pre, overflow = _exchange(local, names, n_shards,
                                            bucket, axis)
        if lossless:
            uniq = op_distinct(recv, pre=pre)
        else:
            uniq, coll = op_distinct_hashed(recv, pre=pre)
            overflow = overflow + jax.lax.psum(coll, axis)
        return _table_args(uniq) + (overflow,)

    out_specs = tuple(P(axis) for _ in names) + (P(axis), P())
    flat = shard_map(body, mesh, _table_specs(table, axis), out_specs)(
        *_table_args(table))
    return Table(dict(zip(names, flat[:-2])), flat[-2]), flat[-1]


def distributed_join(left: Table, right: Table, lkeys, rkeys, mesh,
                     axis: str = "data", expansion: int = 1,
                     skew_factor: float = 4.0,
                     co_left: bool = False, co_right: bool = False,
                     return_pre: bool = False):
    """Inner equi-join: both sides are hash-exchanged on their keys with
    POSITIONALLY aligned partition hashes (matching key values land on
    the same shard), then the local sort+probe join runs per shard.
    Either side skips its exchange when already aligned-partitioned.
    Returns (table, exchange overflow, probe-window overflow) — the two
    loss modes are audited separately (JobStats.shuffle_overflow vs
    join_overflow).

    With ``return_pre=True`` the result tuple gains a second element:
    the left exchange's shipped h1 lane repeated onto the join output's
    row layout (output row ``i*expansion+k`` is left row ``i``), or
    None when the left exchange was skipped.  A downstream
    co-partitioned GROUPBY on the same key columns can seed its
    hash-segmented reduce from that lane instead of re-hashing string
    keys over the inflated receive capacity (DESIGN.md §14)."""
    n_shards = mesh.shape[axis]
    if not co_left:
        left = pad_to_multiple(left, n_shards)
    if not co_right:
        right = pad_to_multiple(right, n_shards)
    lnames, rnames = left.names, right.names
    lbucket = _bucket_size(left.capacity // n_shards, n_shards, skew_factor)
    rbucket = _bucket_size(right.capacity // n_shards, n_shards, skew_factor)

    def body(*flat):
        nl = len(lnames) + 1
        llocal = _as_local(lnames, flat[:nl])
        rlocal = _as_local(rnames, flat[nl:])
        if co_left:
            lrecv, lpre, lovf = llocal, None, jnp.zeros((), jnp.int32)
        else:
            lrecv, lpre, lovf = _exchange(llocal, lkeys, n_shards,
                                          lbucket, axis)
        if co_right:
            rrecv, rpre, rovf = rlocal, None, jnp.zeros((), jnp.int32)
        else:
            rrecv, rpre, rovf = _exchange(rlocal, rkeys, n_shards,
                                          rbucket, axis)
        joined, jovf = op_join(lrecv, rrecv, lkeys, rkeys, expansion,
                               pre_left=lpre, pre_right=rpre)
        out = _table_args(joined)
        if return_pre and not co_left:
            out = out + (jnp.repeat(lpre[0], expansion),)
        return out + (lovf + rovf, jax.lax.psum(jovf, axis))

    # the SEQUENTIAL rename rule shared with op_join/plan props: a
    # right-side name colliding with an already-renamed "_r" column
    # chains to "_r_r" — a set comprehension would collapse it and
    # desynchronize out_specs from the body's returned columns
    out_names = list(_join_out_names(lnames, rnames))
    in_specs = _table_specs(left, axis) + _table_specs(right, axis)
    n_lane = 1 if return_pre and not co_left else 0
    out_specs = (tuple(P(axis) for _ in out_names)
                 + (P(axis),) * (1 + n_lane) + (P(), P()))
    flat = shard_map(body, mesh, in_specs, out_specs)(
        *(_table_args(left) + _table_args(right)))
    nc = len(out_names)
    table = Table(dict(zip(out_names, flat[:nc])), flat[nc])
    if not return_pre:
        return table, flat[-2], flat[-1]
    lane = flat[nc + 1] if n_lane else None
    return table, lane, flat[-2], flat[-1]


def distributed_cogroup(a: Table, b: Table, keys_l, keys_r,
                        aggs_l, aggs_r, mesh, axis: str = "data",
                        skew_factor: float = 4.0,
                        co_partitioned: bool = False,
                        lossless: bool = False):
    """COGROUP: both inputs are aligned onto the shared (k0..kn, va_*,
    vb_*) schema on the map side, exchanged on the unified keys, then
    unioned + grouped locally per shard.  The union happens INSIDE the
    shard body: concatenating the global tables first would interleave
    the two inputs' partition blocks and break co-location."""
    n_shards = mesh.shape[axis]
    ta, tb, keys, aggs = _cogroup_prepare(a, b, keys_l, keys_r,
                                          aggs_l, aggs_r)
    if not co_partitioned:
        ta = pad_to_multiple(ta, n_shards)
        tb = pad_to_multiple(tb, n_shards)
    anames, bnames = ta.names, tb.names
    abucket = _bucket_size(ta.capacity // n_shards, n_shards, skew_factor)
    bbucket = _bucket_size(tb.capacity // n_shards, n_shards, skew_factor)

    def body(*flat):
        na = len(anames) + 1
        aloc = _as_local(anames, flat[:na])
        bloc = _as_local(bnames, flat[na:])
        if co_partitioned:
            arecv, brecv, pre = aloc, bloc, None
            overflow = jnp.zeros((), jnp.int32)
        else:
            arecv, apre, aovf = _exchange(aloc, keys, n_shards,
                                          abucket, axis)
            brecv, bpre, bovf = _exchange(bloc, keys, n_shards,
                                          bbucket, axis)
            overflow = aovf + bovf
            pre = tuple(jnp.concatenate([x, y])
                        for x, y in zip(apre, bpre))
        cols = {n: jnp.concatenate([arecv.col(n), brecv.col(n)])
                for n in arecv.names}
        both = Table(cols, jnp.concatenate([arecv.valid, brecv.valid]))
        if lossless:
            grouped = op_groupby(both, keys, aggs, pre=pre)
        else:
            grouped, coll = op_groupby_hashed(both, keys, aggs, pre=pre)
            overflow = overflow + jax.lax.psum(coll, axis)
        return _table_args(grouped) + (overflow,)

    out_names = sorted(set(list(keys) + list(aggs)))
    in_specs = _table_specs(ta, axis) + _table_specs(tb, axis)
    out_specs = tuple(P(axis) for _ in out_names) + (P(axis), P())
    flat = shard_map(body, mesh, in_specs, out_specs)(
        *(_table_args(ta) + _table_args(tb)))
    grouped = Table(dict(zip(out_names, flat[:-2])), flat[-2])
    return _cogroup_rename(grouped, keys_l), flat[-1]
