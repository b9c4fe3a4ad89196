"""Table: the tuple-stream representation of the dataflow engine.

Hadoop streams tuples between operators; XLA wants static shapes.  A Table
is a struct-of-arrays with a *compile-time capacity* and a validity mask:

  * every column is a jnp array of shape ``(capacity,)`` (numeric) or
    ``(capacity, width)`` (fixed-width byte strings, dtype uint8);
  * ``valid`` is a boolean ``(capacity,)`` mask — Filter marks rows
    invalid instead of compacting; the artifact store's flusher compacts
    an output on the device (``Table.compact``) before it copies the
    stored rows to the host.

Tables are pytrees so they flow through jit/shard_map unchanged.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Schema


@dataclasses.dataclass(frozen=True)
class ColumnType:
    """dtype + optional byte-width (width > 0 means fixed-width string)."""

    dtype: str  # numpy dtype name, e.g. "int32", "float32", "uint8"
    width: int = 0  # 0 => scalar column; >0 => (capacity, width) bytes

    @property
    def is_string(self) -> bool:
        return self.width > 0

    def key(self) -> Tuple:
        return ("col", self.dtype, self.width)


INT = ColumnType("int32")
FLOAT = ColumnType("float32")


def STR(width: int = 20) -> ColumnType:
    return ColumnType("uint8", width)


Schema = Dict[str, ColumnType]


def schema_key(schema: Schema) -> Tuple:
    return tuple(sorted((n, t.key()) for n, t in schema.items()))


# ---------------------------------------------------------------------------
# Table pytree


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Table:
    columns: Dict[str, jnp.ndarray]
    valid: jnp.ndarray  # bool (capacity,)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[n] for n in names) + (self.valid,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(columns=dict(zip(names, children[:-1])), valid=children[-1])

    # -- accessors ----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    @property
    def names(self):
        return sorted(self.columns)

    def col(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    def schema(self) -> Schema:
        out: Schema = {}
        for n, c in self.columns.items():
            if c.ndim == 2:
                out[n] = ColumnType("uint8", int(c.shape[1]))
            else:
                out[n] = ColumnType(str(c.dtype))
        return out

    def num_valid(self) -> jnp.ndarray:
        return jnp.sum(self.valid.astype(jnp.int32))

    def nbytes(self) -> int:
        """Logical bytes at full capacity (the T_load/T_store proxy)."""
        total = self.valid.size  # 1 byte/bool
        for c in self.columns.values():
            total += c.size * c.dtype.itemsize
        return int(total)

    # -- construction helpers ----------------------------------------------
    @staticmethod
    def from_numpy(cols: Dict[str, np.ndarray], nvalid: int | None = None,
                   capacity: int | None = None) -> "Table":
        n = len(next(iter(cols.values())))
        nvalid = n if nvalid is None else nvalid
        capacity = n if capacity is None else capacity
        out = {}
        for name, a in cols.items():
            a = np.asarray(a)
            if capacity != n:
                pad = [(0, capacity - n)] + [(0, 0)] * (a.ndim - 1)
                a = np.pad(a, pad)
            out[name] = jnp.asarray(a)
        valid = jnp.arange(capacity) < nvalid
        return Table(out, valid)

    def to_numpy(self, only_valid: bool = True) -> Dict[str, np.ndarray]:
        mask = np.asarray(self.valid)
        out = {}
        for n, c in self.columns.items():
            a = np.asarray(c)
            out[n] = a[mask] if only_valid else a
        return out

    # -- row ops used by physical operators ----------------------------------
    def gather(self, idx: jnp.ndarray, valid: jnp.ndarray) -> "Table":
        cols = {n: jnp.take(c, idx, axis=0) for n, c in self.columns.items()}
        return Table(cols, valid)

    def with_valid(self, valid: jnp.ndarray) -> "Table":
        return Table(dict(self.columns), valid)

    def select(self, names) -> "Table":
        return Table({n: self.columns[n] for n in names}, self.valid)

    def compact(self, capacity: int | None = None) -> "Table":
        """Valid rows first, in stable order, then zero-filled invalid
        rows, at the static ``capacity`` (this table's by default; valid
        rows past it are dropped).  The artifact store's flusher runs it
        jitted (``compact_to``) before the copy to the host.  The order
        is a stable argsort of the invalid flags, and the rows move in
        one gather of their byte-packed form (``pack_rows``): on a TPU
        v5e, at 2^22 rows, the gathers dominate, and one gather of packed
        rows took under half the time of a gather per column, while a
        binary search or a scatter over the running count of valid rows
        took longer than the sort."""
        cap = self.capacity if capacity is None else int(capacity)
        order = jnp.argsort(~self.valid, stable=True)[:cap]
        # past this table's capacity, rows gather row 0 and are zeroed
        order = jnp.pad(order, (0, cap - order.shape[0]))
        keep = jnp.arange(cap) < jnp.sum(self.valid.astype(jnp.int32))
        packed, layout = pack_rows(self.columns, self.valid)
        rows = jnp.where(keep[:, None], jnp.take(packed, order, axis=0), 0)
        return Table(unpack_rows(rows, layout)[0], keep)

    def to_host(self) -> "Tuple[Dict[str, np.ndarray], np.ndarray]":
        """Every column and the validity mask, copied to the host."""
        return ({n: np.asarray(c) for n, c in self.columns.items()},
                np.asarray(self.valid).astype(bool))


# one program per schema, input capacity and output capacity
compact_to = jax.jit(Table.compact, static_argnames=("capacity",))


def concat_tables(parts, capacity: int | None = None) -> Table:
    """Host-side concatenation of the *valid* rows of ``parts``, in
    order — the append primitive of incremental artifact maintenance
    (DESIGN.md §12): an append-refreshed dataset/artifact is exactly the
    old valid rows followed by the delta's valid rows (prefix-stable).
    Schemas must match exactly."""
    assert parts, "concat_tables: no inputs"
    names = parts[0].names
    for p in parts[1:]:
        assert p.names == names, "concat_tables: schema mismatch"
    cols: Dict[str, np.ndarray] = {}
    for n in names:
        cols[n] = np.concatenate(
            [np.asarray(p.col(n))[np.asarray(p.valid).astype(bool)]
             for p in parts])
    nvalid = len(cols[names[0]])
    cap = capacity if capacity is not None else max(nvalid, 8)
    return Table.from_numpy(cols, nvalid=nvalid, capacity=cap)


def slice_valid(table: Table, lo: int, hi: int | None = None,
                round_pow2: bool = False, cols=None) -> Table:
    """Table holding valid rows ``[lo:hi]`` of ``table`` (host-side).
    With an append-only lineage, ``slice_valid(cur, 0, n_old)`` is the
    pre-append snapshot and ``slice_valid(cur, n_old)`` the delta
    (DESIGN.md §12).  ``round_pow2`` pads the capacity to the next
    power of two — data-dependent row counts otherwise produce a fresh
    shape (and a fresh jit trace) per call on anything downstream.
    ``cols`` restricts the slice to a column subset (delta bindings only
    materialize the bytes their subplan consumes)."""
    # one flatnonzero over the mask, then a gather of just the selected
    # rows — not an O(n)-per-column copy of every valid row first
    rows = np.flatnonzero(np.asarray(table.valid))[lo:hi]
    names = table.names if cols is None else sorted(cols)
    out: Dict[str, np.ndarray] = {}
    for n in names:
        out[n] = np.asarray(table.col(n))[rows]
    nvalid = len(rows)
    cap = max(nvalid, 8)
    if round_pow2:
        cap = 1 << (cap - 1).bit_length()
    return Table.from_numpy(out, nvalid=nvalid, capacity=cap)


def pad_capacity(table: Table, multiple: int) -> Table:
    """Pad ``table`` with invalid rows so its capacity is a multiple of
    ``multiple`` (mesh engines shard inputs into equal blocks)."""
    cap = table.capacity
    if multiple <= 1 or cap % multiple == 0:
        return table
    new_cap = ((cap + multiple - 1) // multiple) * multiple
    cols = {}
    for n, c in table.columns.items():
        pad = [(0, new_cap - cap)] + [(0, 0)] * (c.ndim - 1)
        cols[n] = jnp.asarray(np.pad(np.asarray(c), pad))
    valid = jnp.asarray(np.pad(np.asarray(table.valid), (0, new_cap - cap)))
    return Table(cols, valid)


def encode_strings(values, width: int = 20) -> np.ndarray:
    """Python strings -> (n, width) uint8, truncated/zero-padded."""
    out = np.zeros((len(values), width), dtype=np.uint8)
    for i, s in enumerate(values):
        b = s.encode("utf-8")[:width]
        out[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    return out


def decode_strings(arr: np.ndarray):
    return ["".join(chr(c) for c in row if c) for row in np.asarray(arr)]


# ---------------------------------------------------------------------------
# Row packing (DESIGN.md §14): the fused exchange moves every column of
# a table through ONE collective by byte-packing rows into a single
# (capacity, row_bytes) uint8 buffer.  bitcast keeps the packing exact
# (float32 round-trips bit-identically) and free of format work.


def _col_bytes(c: jnp.ndarray) -> jnp.ndarray:
    """A column's rows as bytes: ``(N, ...)`` of any dtype -> ``(N, B)``."""
    if c.dtype == jnp.bool_:
        c = c.astype(jnp.uint8)
    if c.dtype != jnp.uint8:
        c = jax.lax.bitcast_convert_type(c, jnp.uint8)  # adds an itemsize axis
    return c.reshape(c.shape[0], -1)


def pack_rows(cols: Dict[str, jnp.ndarray], valid: jnp.ndarray
              ) -> Tuple[jnp.ndarray, Tuple]:
    """Pack columns + the validity lane into one (N, B) uint8 buffer.
    Returns (packed, layout); the layout is static (hashable) and drives
    ``unpack_rows``.  Column order is sorted-name for determinism."""
    parts, layout = [], []
    for n in sorted(cols):
        c = cols[n]
        b = _col_bytes(c)
        parts.append(b)
        layout.append((n, str(c.dtype), int(b.shape[1]), tuple(c.shape[1:])))
    parts.append(valid.astype(jnp.uint8)[:, None])
    return jnp.concatenate(parts, axis=1), tuple(layout)


def unpack_rows(packed: jnp.ndarray, layout: Tuple
                ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Inverse of ``pack_rows``.  Zero-filled rows (unhit scatter slots)
    unpack to zero values with valid=False."""
    cols: Dict[str, jnp.ndarray] = {}
    off = 0
    for name, dtype, width, tail in layout:
        b = packed[:, off:off + width]
        off += width
        dt = jnp.dtype(dtype)
        if dt == jnp.bool_:
            b = b.astype(jnp.bool_)
        elif dt != jnp.uint8:
            b = jax.lax.bitcast_convert_type(
                b.reshape(b.shape[0], -1, dt.itemsize), dt)
        cols[name] = b.reshape(b.shape[:1] + tail)
    valid = packed[:, off].astype(jnp.bool_)
    return cols, valid


# ---------------------------------------------------------------------------
# Hashing (uint32; two independent lanes available for sort tie-breaking)

_FNV_OFFSET = np.uint32(2166136261)
_FNV_PRIME = np.uint32(16777619)


def _mix32(x: jnp.ndarray, seed: int) -> jnp.ndarray:
    """splitmix-style avalanche on uint32."""
    x = x.astype(jnp.uint32) ^ jnp.uint32(seed)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash_column(col: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """uint32 hash of one column (any dtype, 1-D or 2-D bytes)."""
    if col.ndim == 2:  # fixed-width string: FNV-1a fold, then mix
        h = jnp.full(col.shape[:1], _FNV_OFFSET, dtype=jnp.uint32)
        for j in range(col.shape[1]):
            h = (h ^ col[:, j].astype(jnp.uint32)) * _FNV_PRIME
        return _mix32(h, seed)
    if jnp.issubdtype(col.dtype, jnp.floating):
        col = jax.lax.bitcast_convert_type(col.astype(jnp.float32), jnp.uint32)
    return _mix32(col.astype(jnp.uint32), seed)


def hash_columns(table: Table, names, seed: int = 0) -> jnp.ndarray:
    """Combined uint32 hash over several key columns."""
    h = jnp.zeros(table.capacity, dtype=jnp.uint32)
    for i, n in enumerate(sorted(names)):
        h = _mix32(h * jnp.uint32(31) + hash_column(table.col(n), seed + i), seed)
    return h


def key_hash(table: Table, keys, seed: int = 0) -> jnp.ndarray:
    """uint32 key hash mixing the key columns in the GIVEN order.

    Unlike ``hash_columns`` (which sorts names so GROUPBY fingerprints
    are order-insensitive), this hash is positional: the two sides of a
    JOIN carry differently-named key columns, and their hashes only
    agree if column i on the left is hashed exactly like column i on
    the right."""
    h = jnp.zeros(table.capacity, dtype=jnp.uint32)
    for i, n in enumerate(keys):
        h = _mix32(h * jnp.uint32(31) + hash_column(table.col(n), seed + i),
                   seed)
    return h


def partition_finalize(h: jnp.ndarray) -> jnp.ndarray:
    """murmur3 fmix32 over an already-computed ``key_hash`` lane.

    The partition hash is *derived* from the seed-0 key hash with a
    handful of integer ops so the exchange pays ONE string-fold pass
    for both its routing bits and the ``__h0__`` lane it ships; the
    finalizer decorrelates the low routing bits from the lane the
    reducers sort/segment by.  Every component that assigns rows to
    shards — the shard_map exchange, the artifact store's sharded
    writer, and re-partition-on-read — must agree bit-for-bit on
    hash(keys) % P, or "co-partitioned" artifacts would silently hold
    rows on the wrong shard (DESIGN.md §11)."""
    h = h.astype(jnp.uint32)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def partition_hash(table: Table, keys) -> jnp.ndarray:
    """Canonical uint32 partition hash: ``partition_finalize`` of the
    positional seed-0 ``key_hash`` (see ``partition_finalize`` for why
    the derivation matters)."""
    return partition_finalize(key_hash(table, keys, seed=0))


@partial(jax.jit, static_argnames=("keys", "n_parts"))
def partition_ids_device(table: Table, keys: Tuple[str, ...],
                         n_parts: int) -> jnp.ndarray:
    """Jitted ``partition_hash(keys) % n_parts`` — the artifact store
    computes this on every partitioned put (the one on-clock device pass
    of a sharded store), so the ~dozen hash-mix ops must launch as one
    fused computation, not eager per-op dispatches."""
    return partition_hash(table, keys) % jnp.uint32(n_parts)


def cols_equal(table_a: Table, idx_a, table_b: Table, idx_b, names) -> jnp.ndarray:
    """Exact row equality on key columns between gathered row indices."""
    eq = jnp.ones(jnp.shape(idx_a), dtype=bool)
    for n in names:
        ca = jnp.take(table_a.col(n), idx_a, axis=0)
        cb = jnp.take(table_b.col(n), idx_b, axis=0)
        e = ca == cb
        if e.ndim == 2:
            e = e.all(axis=-1)
        eq = eq & e
    return eq
