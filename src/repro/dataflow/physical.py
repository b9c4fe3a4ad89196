"""Execution of physical plans over Tables, in pure JAX.

All operators are static-shape: capacities are compile-time, deletion is
masking.  The blocking operators (JOIN / GROUPBY / COGROUP / DISTINCT) are
implemented sort-based — the TPU-native replacement for Hadoop's
sort-shuffle and for GPU shared-memory hash tables (see DESIGN.md §7).

Hash-collision handling: rows are ordered by a (h1, h2) pair of
independent uint32 hashes, but *all* equality decisions (segment
boundaries, join-match verification) compare the actual key columns, so
grouping/distinct are exact and joins are exact up to a bounded probe
window whose overflows are counted in job stats.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..core.plan import Operator, PhysicalPlan
from .table import Table, cols_equal, hash_columns

_U32_MAX = jnp.uint32(0xFFFFFFFF)

# Pallas kernel integration for the relational hot spots (join probe,
# segment aggregation, the exchange's partition scatter).  On a TPU
# backend the kernels always run, compiled; elsewhere they are opt-in,
# because the Pallas interpreter is correct but slow
# (``repro.kernels.interpret`` decides compiled vs interpreted).
_USE_PALLAS = False


def set_use_pallas(v: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = v


def use_pallas() -> bool:
    return _USE_PALLAS or jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Sorting & segments shared by GROUPBY / DISTINCT / COGROUP


class HashCache:
    """Per-plan-execution memo of raw key-column hashes.

    GROUPBY / DISTINCT / COGROUP / JOIN all hash the same (table, keys)
    pairs — often the *same* columns, e.g. a SPLIT fan-out feeding a
    GROUPBY and a JOIN on one key.  Keyed by the identity of the column
    arrays (in sorted-name order, which is what ``hash_columns`` mixes
    over), so a FILTER that only rewrites ``valid`` still shares the
    hashes of its input.  Validity masking happens at the use site."""

    def __init__(self):
        # value holds the column objects alongside the hash: the memo
        # key uses id()s, which are only stable while the arrays stay
        # referenced (a GC'd temporary's recycled id must never hit)
        self._memo: Dict[Tuple, Tuple[Tuple, jnp.ndarray]] = {}

    def hashes(self, t: Table, keys, seed: int) -> jnp.ndarray:
        cols = tuple(t.col(n) for n in sorted(keys))
        key = (tuple(id(c) for c in cols), seed)
        ent = self._memo.get(key)
        if ent is None:
            ent = (cols, hash_columns(t, keys, seed=seed))
            self._memo[key] = ent
        return ent[1]


def _key_hashes(t: Table, keys, seed: int,
                hc: "HashCache | None") -> jnp.ndarray:
    if hc is None:
        return hash_columns(t, keys, seed=seed)
    return hc.hashes(t, keys, seed)


def _sort_by_keys(t: Table, keys,
                  hc: "HashCache | None" = None,
                  pre=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (order, new_seg): stable order by (h1, h2) with invalid rows
    last, and exact segment-start mask in sorted order.  ``pre`` is an
    optional (h1, h2) pair of UNMASKED key hashes computed upstream —
    the lanes a distributed exchange ships with each row (DESIGN.md
    §14) — substituting for re-hashing the key columns here.  Validity
    masking still happens at this use site, so zero-filled rows from
    unhit exchange slots are parked with the invalid rows either way."""
    if pre is not None:
        h1u = pre[0]
        h2u = pre[1] if len(pre) > 1 else _key_hashes(t, keys, 101, hc)
    else:
        h1u = _key_hashes(t, keys, 0, hc)
        h2u = _key_hashes(t, keys, 101, hc)
    h1 = jnp.where(t.valid, h1u, _U32_MAX)
    h2 = jnp.where(t.valid, h2u, _U32_MAX)
    order = jnp.lexsort((h2, h1))
    sv = jnp.take(t.valid, order)
    prev = jnp.roll(order, 1)
    same_as_prev = cols_equal(t, order, t, prev, keys)
    same_as_prev = same_as_prev & jnp.take(t.valid, prev)
    same_as_prev = same_as_prev.at[0].set(False)
    new_seg = sv & ~same_as_prev
    return order, new_seg


def _segment_aggregate(t: Table, keys, aggs, order, new_seg) -> Table:
    cap = t.capacity
    sv = jnp.take(t.valid, order)
    seg_id = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    seg_id = jnp.where(sv, seg_id, cap - 1)  # park invalid in last bucket
    n_seg = jnp.sum(new_seg.astype(jnp.int32))
    out_valid = jnp.arange(cap) < n_seg

    # representative row per segment (for key columns)
    rep = jnp.zeros(cap, dtype=jnp.int32)
    rep = rep.at[jnp.where(new_seg, seg_id, cap - 1)].set(
        order.astype(jnp.int32), mode="drop")

    cols: Dict[str, jnp.ndarray] = {}
    for k in keys:
        kc = jnp.take(t.col(k), rep, axis=0)
        cols[k] = jnp.where(
            out_valid.reshape((-1,) + (1,) * (kc.ndim - 1)), kc,
            jnp.zeros_like(kc))

    def _segsum(v):
        if use_pallas():
            from ..kernels.segment_reduce.ops import segment_sum
            return segment_sum(v[:, None], seg_id, num_segments=cap,
                               impl="pallas")[:, 0]
        return jax.ops.segment_sum(v, seg_id, num_segments=cap)

    ones = sv.astype(jnp.float32)
    counts = _segsum(ones)
    for out_name, (fn, cname) in aggs.items():
        if fn == "count":
            cols[out_name] = counts.astype(jnp.float32)
            continue
        v = jnp.take(t.col(cname), order, axis=0).astype(jnp.float32)
        v = jnp.where(sv, v, 0.0)
        if fn in ("sum", "mean"):
            s = _segsum(v)
            cols[out_name] = s if fn == "sum" else s / jnp.maximum(counts, 1.0)
        elif fn == "min":
            v = jnp.where(sv, v, jnp.inf)
            cols[out_name] = jax.ops.segment_min(v, seg_id, num_segments=cap)
        elif fn == "max":
            v = jnp.where(sv, v, -jnp.inf)
            cols[out_name] = jax.ops.segment_max(v, seg_id, num_segments=cap)
        else:
            raise ValueError(f"unknown aggregate {fn}")
        cols[out_name] = jnp.where(out_valid, cols[out_name], 0.0)
    return Table(cols, out_valid)


# ---------------------------------------------------------------------------
# Sort-free hash-segmented reduce (distributed path, DESIGN.md §14)
#
# XLA CPU argsort costs ~6x a plain value sort at 64k rows, and the
# lexsort in _sort_by_keys dominates every blocking operator.  The
# distributed reduce does not need a row ORDER, only segment ids: sort
# the h1 VALUES (cheap), then each row's segment is the first sorted
# position of its hash.  Exactness: every row's actual key columns are
# verified against its segment representative; any mismatch (two
# distinct keys sharing an h1) is COUNTED, and the engine reruns the
# job on the lossless sort-based path — the same contract as the
# exchange's bounded buckets and the join's probe window.
#
# Bit-identity with the single-device sort path: within a group all
# rows share (h1, h2), so the stable lexsort keeps them in row-index
# order — exactly the order segment_sum accumulates them here; group
# representatives are the minimum-index row on both paths.


def _hash_segments(t: Table, keys, h1u):
    """Return (pos, out_valid, rep, collisions): per-row segment id
    (the first sorted position of the row's masked h1, invalid rows
    parked at cap-1), validity of each output slot (first-occurrence
    positions among valid rows), the minimum-index representative row
    per segment, and the count of valid rows whose keys mismatch their
    representative (h1 collisions between distinct keys)."""
    cap = t.capacity
    h1m = jnp.where(t.valid, h1u, _U32_MAX)
    s = jnp.sort(h1m)
    pos = jnp.searchsorted(s, h1m, side="left").astype(jnp.int32)
    # invalid rows park at cap-1; a valid row's first-occurrence
    # position is always < n_valid <= cap-1 when any invalid row
    # exists, so parking never mixes with a real segment
    pos = jnp.where(t.valid, pos, cap - 1)
    iota = jnp.arange(cap, dtype=jnp.int32)
    n_valid = jnp.sum(t.valid.astype(jnp.int32))
    new = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    out_valid = new & (iota < n_valid)
    rep = jax.ops.segment_min(jnp.where(t.valid, iota, cap), pos,
                              num_segments=cap)
    rep = jnp.clip(rep, 0, cap - 1).astype(jnp.int32)
    eq = cols_equal(t, iota, t, jnp.take(rep, pos), keys)
    collisions = jnp.sum((t.valid & ~eq).astype(jnp.int32))
    return pos, out_valid, rep, collisions


def op_groupby_hashed(t: Table, keys, aggs, hc: "HashCache | None" = None,
                      pre=None) -> Tuple[Table, jnp.ndarray]:
    """Sort-free GROUPBY for the distributed reduce.  Returns (table,
    collision count); a nonzero count means the result dropped/merged
    groups and the caller must fall back to the sort-based path."""
    h1u = pre[0] if pre is not None else _key_hashes(t, keys, 0, hc)
    pos, out_valid, rep, collisions = _hash_segments(t, keys, h1u)
    cap = t.capacity
    sv = t.valid

    cols: Dict[str, jnp.ndarray] = {}
    for k in keys:
        kc = jnp.take(t.col(k), rep, axis=0)
        cols[k] = jnp.where(
            out_valid.reshape((-1,) + (1,) * (kc.ndim - 1)), kc,
            jnp.zeros_like(kc))

    # one batched (N, k) scatter-add covers the count column and every
    # sum/mean aggregate: segment reduction is row-bound scatter traffic
    # (~6 ms per pass at 128k rows on host XLA), so lanes ride together
    need_counts = any(fn in ("count", "mean") for fn, _ in aggs.values())
    lanes, lane_names = [], []
    if need_counts:
        lanes.append(sv.astype(jnp.float32))
        lane_names.append(None)
    for out_name, (fn, cname) in aggs.items():
        if fn in ("sum", "mean"):
            lanes.append(jnp.where(sv, t.col(cname).astype(jnp.float32),
                                   0.0))
            lane_names.append(out_name)
    if lanes:
        summed = jax.ops.segment_sum(jnp.stack(lanes, axis=1), pos,
                                     num_segments=cap)
        by_lane = {n: summed[:, i] for i, n in enumerate(lane_names)}
        counts = by_lane.get(None)

    for out_name, (fn, cname) in aggs.items():
        if fn == "count":
            cols[out_name] = counts.astype(jnp.float32)
            continue
        if fn in ("sum", "mean"):
            s = by_lane[out_name]
            cols[out_name] = s if fn == "sum" else s / jnp.maximum(counts,
                                                                   1.0)
        elif fn == "min":
            v = jnp.where(sv, t.col(cname).astype(jnp.float32), jnp.inf)
            cols[out_name] = jax.ops.segment_min(v, pos, num_segments=cap)
        elif fn == "max":
            v = jnp.where(sv, t.col(cname).astype(jnp.float32), -jnp.inf)
            cols[out_name] = jax.ops.segment_max(v, pos, num_segments=cap)
        else:
            raise ValueError(f"unknown aggregate {fn}")
        cols[out_name] = jnp.where(out_valid, cols[out_name], 0.0)
    return Table(cols, out_valid), collisions


def op_distinct_hashed(t: Table, hc: "HashCache | None" = None,
                       pre=None) -> Tuple[Table, jnp.ndarray]:
    """Sort-free DISTINCT: keep each segment's minimum-index row in
    place (no reorder).  Returns (table, collision count)."""
    keys = t.names
    h1u = pre[0] if pre is not None else _key_hashes(t, keys, 0, hc)
    pos, out_valid, rep, collisions = _hash_segments(t, keys, h1u)
    keep = t.valid & (jnp.take(rep, pos)
                      == jnp.arange(t.capacity, dtype=jnp.int32))
    return t.with_valid(keep), collisions


# ---------------------------------------------------------------------------
# Operator implementations


def op_filter(t: Table, pred) -> Table:
    p = pred.eval(t)
    return t.with_valid(t.valid & p.astype(bool))


def op_project(t: Table, cols) -> Table:
    return t.select(cols)


def op_foreach(t: Table, gens) -> Table:
    out = {}
    for name, e in gens.items():
        v = e.eval(t)
        if v.ndim == 0:
            v = jnp.broadcast_to(v, (t.capacity,))
        out[name] = v
    return Table(out, t.valid)


def op_groupby(t: Table, keys, aggs, hc: "HashCache | None" = None,
               pre=None) -> Table:
    order, new_seg = _sort_by_keys(t, keys, hc, pre=pre)
    return _segment_aggregate(t, keys, aggs, order, new_seg)


def op_distinct(t: Table, hc: "HashCache | None" = None,
                pre=None) -> Table:
    keys = t.names
    order, new_seg = _sort_by_keys(t, keys, hc, pre=pre)
    return t.gather(order, new_seg)


def op_union(a: Table, b: Table) -> Table:
    names = a.names
    assert set(names) == set(b.columns), "UNION schema mismatch"
    cols = {n: jnp.concatenate([a.col(n), b.col(n)], axis=0) for n in names}
    return Table(cols, jnp.concatenate([a.valid, b.valid]))


def op_join(left: Table, right: Table, lkeys, rkeys,
            expansion: int = 1,
            hc: "HashCache | None" = None,
            pre_left=None, pre_right=None) -> Tuple[Table, jnp.ndarray]:
    """Inner equi-join, sort+probe based.  Output capacity =
    left.capacity * expansion.  ``pre_left``/``pre_right`` optionally
    carry each side's exchange-shipped (h1,) probe-hash lane in place
    of re-hashing the key columns (DESIGN.md §14); every match is still
    verified against the actual key columns, and validity masks every
    decision, so shipped hashes change nothing observable.
    Returns (table, overflow_count)."""
    from ..kernels import autotune
    # window slack absorbs h1 ties among distinct right keys; every
    # exhausted window is counted in the returned overflow, so a tuned
    # narrower window stays auditable (the tuner rejects candidates
    # whose measurement reports overflow)
    probe_w = expansion + autotune.choose("join_probe", left.capacity,
                                          "uint32", "slack", 4)
    cap_r = right.capacity

    h_r_raw = (pre_right[0] if pre_right is not None
               else _key_hashes(right, rkeys, 0, hc))
    h_r = jnp.where(right.valid, h_r_raw, _U32_MAX)
    r_order = jnp.argsort(h_r, stable=True)
    h_r_sorted = jnp.take(h_r, r_order)

    h_l = (pre_left[0] if pre_left is not None
           else _key_hashes(left, lkeys, 0, hc))
    from ..kernels.hash_join import hash_join
    if use_pallas() and cap_r <= hash_join.MAX_BUILD_ROWS:
        # the kernel holds the build side whole in SMEM and counts ranks
        # in O(left x right) compares: dimension-table builds only
        from ..kernels.hash_join.ops import probe
        pos = probe(h_l, h_r_sorted, impl="pallas")
    else:
        pos = jnp.searchsorted(h_r_sorted, h_l, side="left")
    cand = jnp.clip(pos[:, None] + jnp.arange(probe_w)[None, :], 0, cap_r - 1)
    cand_rows = jnp.take(r_order, cand)  # (Cl, W) right row ids
    hash_ok = jnp.take(h_r_sorted, cand) == h_l[:, None]

    # exact key verification
    eq = jnp.ones(cand_rows.shape, dtype=bool)
    for lk, rk in zip(lkeys, rkeys):
        lc = left.col(lk)
        rc = jnp.take(right.col(rk), cand_rows, axis=0)
        e = lc[:, None] == rc if lc.ndim == 1 else \
            (lc[:, None, :] == rc).all(axis=-1)
        eq = eq & e
    ok = (hash_ok & eq & jnp.take(right.valid, cand_rows)
          & left.valid[:, None])

    rank = jnp.cumsum(ok.astype(jnp.int32), axis=1) - 1
    # overflow: window exhausted while hashes were still equal.  Only a
    # tail INSIDE the array can witness that — when pos + probe_w runs
    # past the end, the window already covers every remaining row, and
    # the old clip-to-last-row check false-flagged any left key whose
    # hash sorted within probe_w of the array end.
    in_range = pos + probe_w <= cap_r - 1
    tail = jnp.clip(pos + probe_w, 0, cap_r - 1)
    overflow = jnp.sum(((jnp.take(h_r_sorted, tail) == h_l)
                        & in_range & left.valid).astype(jnp.int32))

    out_cols: Dict[str, jnp.ndarray] = {}
    matched_list: List[jnp.ndarray] = []
    ridx_list: List[jnp.ndarray] = []
    for j in range(expansion):
        sel = ok & (rank == j)
        matched_list.append(sel.any(axis=1))
        # per-row gather of the selected window slot.  Must be
        # take_along_axis: jnp.take(..., axis=1) with a (Cl, 1) index
        # array both materializes a (Cl, Cl) gather (XLA CPU: ~800x
        # slower at 64k rows) and — worse — indexes every row by row
        # 0's argmax, silently joining the wrong right row whenever a
        # probe window's first match sits past slot 0 (h1 ties,
        # duplicate right keys under expansion > 1).
        ridx_list.append(jnp.take_along_axis(
            cand_rows, jnp.argmax(sel, axis=1)[:, None], axis=1)[:, 0])
    matched = jnp.stack(matched_list, 1).reshape(-1)      # (Cl*exp,)
    ridx = jnp.stack(ridx_list, 1).reshape(-1)

    for n in left.names:
        c = jnp.repeat(left.col(n), expansion, axis=0)
        out_cols[n] = c
    for n in right.names:
        name = n if n not in out_cols else n + "_r"
        out_cols[name] = jnp.take(right.col(n), ridx, axis=0)
    return Table(out_cols, matched), overflow


def _cogroup_prepare(a: Table, b: Table, keys_l, keys_r, aggs_l, aggs_r):
    """Map-side alignment of both COGROUP inputs onto one shared schema
    (``k0..kn`` unified keys, ``va_*``/``vb_*`` value carriers): after
    this, COGROUP is UNION + GROUPBY.  The other side's carrier rows are
    the aggregate's neutral element (0 for sums, NaN-masked otherwise).
    Shared with the distributed path, which exchanges the two prepared
    tables separately and unions them per shard (DESIGN.md §11)."""
    a_cols = {f"k{i}": a.col(k) for i, k in enumerate(keys_l)}
    b_cols = {f"k{i}": b.col(k) for i, k in enumerate(keys_r)}
    aggs = {}
    for out, (fn, c) in aggs_l.items():
        fn2 = "sum" if fn == "count" else fn
        a_cols[f"va_{out}"] = (a.col(c).astype(jnp.float32)
                               if fn != "count" else jnp.ones(a.capacity))
        b_cols[f"va_{out}"] = jnp.full(
            (b.capacity,), 0.0 if fn2 == "sum" else jnp.nan, jnp.float32)
        aggs[f"l_{out}"] = (fn2, f"va_{out}")
    for out, (fn, c) in aggs_r.items():
        fn2 = "sum" if fn == "count" else fn
        b_cols[f"vb_{out}"] = (b.col(c).astype(jnp.float32)
                               if fn != "count" else jnp.ones(b.capacity))
        a_cols[f"vb_{out}"] = jnp.full(
            (a.capacity,), 0.0 if fn2 == "sum" else jnp.nan, jnp.float32)
        aggs[f"r_{out}"] = (fn2, f"vb_{out}")
    keys = [f"k{i}" for i in range(len(keys_l))]
    return Table(a_cols, a.valid), Table(b_cols, b.valid), keys, aggs


def _cogroup_rename(grouped: Table, keys_l) -> Table:
    """Restore the left input's key names on the grouped result."""
    renamed = {}
    for i, k in enumerate(keys_l):
        renamed[k] = grouped.col(f"k{i}")
    for n in grouped.names:
        if not n.startswith("k"):
            renamed[n] = grouped.col(n)
    return Table(renamed, grouped.valid)


def op_cogroup(a: Table, b: Table, keys_l, keys_r, aggs_l, aggs_r,
               hc: "HashCache | None" = None) -> Table:
    """Group both inputs by key; per-key aggregates from each side."""
    ta, tb, keys, aggs = _cogroup_prepare(a, b, keys_l, keys_r,
                                          aggs_l, aggs_r)
    grouped = op_groupby(op_union(ta, tb), keys, aggs, hc)
    return _cogroup_rename(grouped, keys_l)


def op_store(t: Table) -> Table:
    # no in-graph work: compaction/truncation to the live row count
    # happens host-side on the store's write-behind path (DESIGN.md §3),
    # keeping sorts/gathers off the timed critical path of every job
    return t


# ---------------------------------------------------------------------------
# Plan evaluation


def execute_plan(plan: PhysicalPlan, datasets: Dict[str, Table],
                 mesh=None, shuffle_axis: str = "data",
                 skew_factor: float = 4.0, props=None,
                 lossless: bool = False):
    """Evaluate a physical plan.  Returns (outputs, stats):
    outputs: store-name -> output Table (uncompacted; the artifact
    store's flusher compacts it on the device before writing it);
    stats: op uid -> dict of traced scalars (rows_out, join_overflow,
    shuffle_overflow).

    With a ``mesh``, the blocking operators run through the shard_map
    map->shuffle->reduce path of ``dataflow/shuffle.py`` across the
    ``shuffle_axis`` devices; ``props`` (a ``core.plan.PlanProps``, same
    plan object) marks which exchanges are skipped because the input is
    already co-partitioned (DESIGN.md §11).  ``lossless=True`` is the
    engine's overflow-retry configuration: callers pair it with
    ``skew_factor >= n_shards`` (lossless buckets) and it selects the
    collision-proof sort-based reduce over the hash-segmented one."""
    values: Dict[int, Table] = {}
    outputs: Dict[str, Table] = {}
    stats: Dict[int, Dict[str, jnp.ndarray]] = {}
    # table id -> (key column names, row-aligned h1 lane): shipped hash
    # lanes that survive an op (a join's left exchange) and can seed a
    # downstream co-partitioned GROUPBY's reduce (DESIGN.md §14)
    pres: Dict[int, Tuple[Tuple[str, ...], jnp.ndarray]] = {}
    # (h1, h2) key hashes are computed once per (columns, seed) within
    # this plan execution and shared across GROUPBY/DISTINCT/COGROUP/JOIN
    hc = HashCache()
    if mesh is not None:
        from .shuffle import (distributed_cogroup, distributed_distinct,
                              distributed_groupby, distributed_join)
        n_shards = int(mesh.shape[shuffle_axis])
    skips = props.skip if props is not None else {}

    def _skip(op, i: int, table: Table) -> bool:
        flags = skips.get(id(op), ())
        if not (i < len(flags) and flags[i]):
            return False
        if table.capacity % n_shards != 0:
            # a partitioned value is always laid out in n_shards equal
            # blocks; silently falling back to an exchange here would
            # leave downstream partitioning claims wrong — fail loud
            raise ValueError(
                f"co-partitioned input of {op.kind}#{op.uid} has capacity "
                f"{table.capacity} not divisible by {n_shards} shards")
        return True

    for op in plan.topo():
        # the operator names the device ops it lowers to (their
        # metadata), so a device trace can be read per operator
        with jax.named_scope(op.kind.lower()):
            p = op.params
            ins = [values[id(i)] for i in op.inputs]
            extra: Dict[str, jnp.ndarray] = {}
            if op.kind == "LOAD":
                v = datasets[p["dataset"]]
            elif op.kind == "FILTER":
                v = op_filter(ins[0], p["pred"])
            elif op.kind == "PROJECT":
                v = op_project(ins[0], p["cols"])
            elif op.kind == "FOREACH":
                v = op_foreach(ins[0], p["gens"])
            elif op.kind == "JOIN":
                if mesh is not None:
                    v, jpre, sh_ovf, ovf = distributed_join(
                        ins[0], ins[1], p["left_keys"], p["right_keys"], mesh,
                        axis=shuffle_axis, expansion=p.get("expansion", 1),
                        skew_factor=skew_factor,
                        co_left=_skip(op, 0, ins[0]),
                        co_right=_skip(op, 1, ins[1]),
                        return_pre=True)
                    if jpre is not None:
                        # left-side names survive the join rename rule
                        # unchanged, so the lane keys are the left keys
                        pres[id(v)] = (tuple(p["left_keys"]), jpre)
                    extra["shuffle_overflow"] = sh_ovf
                else:
                    v, ovf = op_join(ins[0], ins[1], p["left_keys"],
                                     p["right_keys"], p.get("expansion", 1),
                                     hc)
                extra["join_overflow"] = ovf
            elif op.kind == "GROUPBY":
                if mesh is not None:
                    entry = pres.get(id(ins[0]))
                    lane = (entry[1] if entry is not None
                            and entry[0] == tuple(p["keys"]) else None)
                    v, ovf = distributed_groupby(
                        ins[0], p["keys"], p["aggs"], mesh, axis=shuffle_axis,
                        skew_factor=skew_factor,
                        co_partitioned=_skip(op, 0, ins[0]),
                        lossless=lossless, pre_lane=lane)
                    extra["shuffle_overflow"] = ovf
                else:
                    v = op_groupby(ins[0], p["keys"], p["aggs"], hc)
            elif op.kind == "COGROUP":
                if mesh is not None:
                    co = _skip(op, 0, ins[0]) and _skip(op, 1, ins[1])
                    v, ovf = distributed_cogroup(
                        ins[0], ins[1], p["keys_left"], p["keys_right"],
                        p["aggs_left"], p["aggs_right"], mesh,
                        axis=shuffle_axis, skew_factor=skew_factor,
                        co_partitioned=co, lossless=lossless)
                    extra["shuffle_overflow"] = ovf
                else:
                    v = op_cogroup(ins[0], ins[1], p["keys_left"],
                                   p["keys_right"], p["aggs_left"],
                                   p["aggs_right"], hc)
            elif op.kind == "DISTINCT":
                if mesh is not None:
                    v, ovf = distributed_distinct(
                        ins[0], mesh, axis=shuffle_axis,
                        skew_factor=skew_factor,
                        co_partitioned=_skip(op, 0, ins[0]),
                        lossless=lossless)
                    extra["shuffle_overflow"] = ovf
                else:
                    v = op_distinct(ins[0], hc)
            elif op.kind == "UNION":
                v = op_union(ins[0], ins[1])
            elif op.kind == "SPLIT":
                v = ins[0]
            elif op.kind == "STORE":
                v = op_store(ins[0])
                outputs[p["name"]] = v
            else:
                raise ValueError(op.kind)
            values[id(op)] = v
            extra["rows_out"] = v.num_valid()
            stats[op.uid] = extra
    return outputs, stats
