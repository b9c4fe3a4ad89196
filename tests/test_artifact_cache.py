"""Device-resident artifact cache + write-behind persistence.

Covers the storage-hierarchy contracts from DESIGN.md §3: LRU eviction at
the byte bound, ``flush()`` as the durability barrier, crash safety (an
artifact is fully published or absent, never torn), alias resolution
through the cache, the injective name encoding, and manifest/data
capacity agreement.  Plus the ISSUE 8 accounting sweep: byte-exact
ledger under append/merge mutation storms, atomic read-merge-write
under concurrent appends, and swap_if never resurrecting an evicted
entry.
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.dataflow.table import Table
from repro.store.artifacts import (ArtifactStore, DeviceCache, _decode_name,
                                   _encode_name)


def _table(n=64, nvalid=None, seed=0):
    rng = np.random.default_rng(seed)
    return Table.from_numpy(
        {"a": rng.integers(0, 100, n).astype(np.int32),
         "b": rng.random(n).astype(np.float32)},
        nvalid=n if nvalid is None else nvalid)


def _tbytes(t):
    return t.nbytes()


# --------------------------------------------------------------- device LRU


def test_lru_evicts_at_byte_bound():
    t = _table(64)
    nb = _tbytes(t)
    cache = DeviceCache(max_bytes=3 * nb)
    for i in range(3):
        cache.put(f"t{i}", t, nb)
    assert len(cache) == 3 and cache.total_bytes == 3 * nb
    cache.get("t0")                      # refresh t0: t1 is now LRU
    cache.put("t3", t, nb)
    assert "t1" not in cache, "LRU entry must be evicted at the bound"
    assert "t0" in cache and "t2" in cache and "t3" in cache
    assert cache.total_bytes <= cache.max_bytes


def test_oversized_artifact_bypasses_cache():
    t = _table(64)
    cache = DeviceCache(max_bytes=10)
    cache.put("big", t, _tbytes(t))
    assert "big" not in cache and cache.total_bytes == 0


def test_get_of_just_produced_artifact_hits_device_cache(tmp_path):
    store = ArtifactStore(root=str(tmp_path / "a"))
    t = _table(64)
    store.put("x", t)
    h0 = store.cache.hits
    got = store.get("x")                 # no flush yet: must not need disk
    assert store.cache.hits == h0 + 1
    assert got.capacity == 64
    np.testing.assert_array_equal(np.asarray(got.col("a")),
                                  np.asarray(t.col("a")))
    store.close()


def test_eviction_falls_back_to_pending_then_disk(tmp_path):
    # cache far too small for even one artifact: every get must be served
    # by the pending write queue or by disk — never KeyError
    store = ArtifactStore(root=str(tmp_path / "a"), cache_bytes=1)
    t = _table(64)
    store.put("x", t)
    got = store.get("x")
    np.testing.assert_array_equal(np.asarray(got.col("a")),
                                  np.asarray(t.col("a")))
    store.flush()
    got2 = store.get("x")
    np.testing.assert_array_equal(np.asarray(got2.col("a")),
                                  np.asarray(t.col("a")))
    store.close()


def test_alias_resolves_through_cache(tmp_path):
    store = ArtifactStore(root=str(tmp_path / "a"))
    t = _table(32)
    store.put("target", t)
    store.alias("other", "target")
    assert store.exists("other")
    assert store.get("other") is store.get("target"), \
        "alias must hit the same cached device table"
    store.close()


# ------------------------------------------------------------ write-behind


def test_flush_is_a_durability_barrier(tmp_path):
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)
    t = _table(64, nvalid=20)
    store.put("x", t)
    store.flush()
    # fresh store object == simulated restart: only disk state survives
    store2 = ArtifactStore(root=root)
    assert store2.exists("x")
    got = store2.get("x")
    assert int(np.asarray(got.num_valid())) == 20
    store.close()
    store2.close()


def test_kill_before_flush_leaves_no_torn_artifact(tmp_path, monkeypatch):
    # simulated kill: the flusher thread never runs, pending writes die
    # with the process
    monkeypatch.setattr(
        "repro.store.artifacts._WriteBehind._ensure_thread",
        lambda self: None)
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)
    store.put("x", _table(64))
    assert store.exists("x")             # visible pre-crash via the cache
    # "restart": a new store sees either a complete artifact or nothing
    store2 = ArtifactStore(root=root)
    assert not store2.exists("x")
    assert store2.names() == []
    # no half-published directories: anything on disk is either a
    # complete artifact (manifest + data) or an unpublished .tmp- dir
    for d in os.listdir(root):
        full = os.path.join(root, d)
        if not d.startswith(".tmp-"):
            assert os.path.exists(os.path.join(full, "manifest.json"))
            assert os.path.exists(os.path.join(full, "data.npz"))
    store2.close()


def test_failed_write_publishes_nothing_and_raises_on_flush(
        tmp_path, monkeypatch):
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr("repro.store.artifacts.np.savez", boom)
    store.put("x", _table(64))
    with pytest.raises(OSError):
        store.flush()
    monkeypatch.undo()
    assert not os.path.exists(os.path.join(store._path("x"),
                                           "manifest.json"))
    assert [d for d in os.listdir(root) if not d.startswith(".tmp-")] == []
    # a lost write must stop advertising the artifact: otherwise later
    # runs would "reuse" data that will never be on disk
    assert not store.exists("x")
    with pytest.raises(KeyError):
        store.get("x")
    store.close()


def test_repeated_puts_coalesce_to_latest(tmp_path):
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)
    for seed in range(6):
        store.put("x", _table(64, seed=seed))
    store.flush()
    store2 = ArtifactStore(root=root)
    np.testing.assert_array_equal(
        np.asarray(store2.get("x").col("a")),
        np.asarray(_table(64, seed=5).col("a")))
    store.close()
    store2.close()


def test_delete_cancels_pending_write(tmp_path):
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)
    store.put("x", _table(64))
    store.delete("x")
    store.flush()
    assert not store.exists("x")
    store2 = ArtifactStore(root=root)
    assert not store2.exists("x")
    store.close()
    store2.close()


def test_synchronous_mode_still_supported(tmp_path):
    store = ArtifactStore(root=str(tmp_path / "a"), write_behind=False)
    store.put("x", _table(64))
    assert os.path.exists(os.path.join(store._path("x"), "data.npz"))
    store.flush()                        # no-op, must not hang
    store.close()


# ------------------------------------- accounting under mutation (ISSUE 8)


def test_append_byte_accounting_stays_exact_under_pressure(tmp_path):
    """Repeated in-place appends against a tight budget: the ledger
    must equal an independent recount after every merge, every recorded
    entry size must equal its live table's bytes (eviction ordering is
    priced on post-merge sizes), and the bound must hold."""
    t0 = _table(64)
    store = ArtifactStore(root=str(tmp_path / "a"),
                          cache_bytes=6 * t0.nbytes())
    store.put("x", t0)
    for i in range(6):
        store.append("x", _table(64, seed=i + 1))
        store.put(f"filler{i}", _table(64, seed=100 + i))  # pressure
        c = store.cache
        assert c.total_bytes == c.recount(), \
            f"ledger drifted after append {i}"
        with c._lock:
            entries = list(c._entries.items())
        for k, (tab, nb) in entries:
            assert nb == tab.nbytes(), \
                f"{k}: recorded {nb} != live table {tab.nbytes()}"
        assert c.total_bytes <= c.max_bytes
    # the appended artifact's cached copy is the merged value
    got = store.get("x")
    assert int(np.asarray(got.num_valid())) == 7 * 64
    store.close()


def test_concurrent_appends_merge_both_deltas(tmp_path):
    """Two racing appends of the same artifact: the read-merge-write
    must be atomic.  Pre-fix, thread A read the pre-B value, merged its
    own delta and put — silently erasing B's committed delta."""
    store = ArtifactStore(root=str(tmp_path / "a"))
    store.put("x", Table.from_numpy({"a": np.array([0], np.int64)}))
    a_entered = threading.Event()
    b_done = threading.Event()
    real_get = store.get

    def slow_get(name, *args, **kw):
        t = real_get(name, *args, **kw)
        if (threading.current_thread().name == "appender-a"
                and not a_entered.is_set()):
            a_entered.set()
            b_done.wait(timeout=0.5)   # pre-fix: B commits in this gap
        return t

    store.get = slow_get

    def run_a():
        store.append("x", Table.from_numpy({"a": np.array([1], np.int64)}))

    def run_b():
        a_entered.wait(timeout=2.0)
        store.append("x", Table.from_numpy({"a": np.array([2], np.int64)}))
        b_done.set()

    ta = threading.Thread(target=run_a, name="appender-a")
    tb = threading.Thread(target=run_b, name="appender-b")
    ta.start()
    tb.start()
    ta.join(timeout=10)
    tb.join(timeout=10)
    assert not ta.is_alive() and not tb.is_alive()
    store.get = real_get
    rows = sorted(store.get("x").to_numpy()["a"].tolist())
    assert rows == [0, 1, 2], f"a concurrent append was lost: {rows}"
    store.close()


def test_swap_if_does_not_resurrect_evicted_entry():
    """The flusher publishes a compacted table via swap_if after the
    LRU already evicted the entry: re-inserting would evict
    recently-used entries for one nobody asked for, and double-count
    its bytes against the budget."""
    t = _table(64)
    nb = t.nbytes()
    cache = DeviceCache(max_bytes=2 * nb)
    cache.put("a", t, nb)
    cache.put("b", _table(64, seed=1), nb)
    cache.put("c", _table(64, seed=2), nb)     # evicts "a"
    assert "a" not in cache
    cache.swap_if("a", t, _table(64, seed=3), nb)
    assert "a" not in cache, "evicted entry must not be resurrected"
    assert "b" in cache and "c" in cache
    assert cache.total_bytes == cache.recount() == 2 * nb


def test_oversized_put_reports_eviction_and_keeps_ledger_clean():
    t = _table(64)
    cache = DeviceCache(max_bytes=10)
    seen = []
    cache.on_evict = lambda name, tab, nb: seen.append((name, nb))
    cache.put("big", t, t.nbytes())
    assert "big" not in cache and cache.total_bytes == 0
    assert seen == [("big", t.nbytes())], \
        "oversized artifacts must still offer themselves for demotion"
    assert cache.recount() == 0


# ------------------------------------------------- naming & manifest fixes


def test_name_encoding_is_injective():
    names = ["art/q__v2", "a__b", "a/b", "a_u/b", "plain", "_u", "__",
             "art/x_y/z__w"]
    encoded = [_encode_name(n) for n in names]
    assert len(set(encoded)) == len(names)
    for n, e in zip(names, encoded):
        assert _decode_name(e) == n
        assert "/" not in e


def test_double_underscore_name_survives_reopen(tmp_path):
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)
    t = _table(32)
    store.put("art/q__v2", t)
    store.flush()
    store2 = ArtifactStore(root=root)
    assert store2.names() == ["art/q__v2"]
    got = store2.get("art/q__v2")
    np.testing.assert_array_equal(np.asarray(got.col("a")),
                                  np.asarray(t.col("a")))
    store.close()
    store2.close()


def test_manifest_capacity_matches_stored_data(tmp_path):
    root = str(tmp_path / "a")
    store = ArtifactStore(root=root)
    # 256-capacity table with 10 valid rows in a compacted prefix: stored
    # capacity shrinks to 16, and the manifest must say so
    store.put("x", _table(256, nvalid=10))
    store.flush()
    with open(os.path.join(store._path("x"), "manifest.json")) as f:
        manifest = json.load(f)
    z = np.load(os.path.join(store._path("x"), "data.npz"))
    assert manifest["capacity"] == len(z["__valid__"]) == 16
    assert manifest["rows"] == 10
    store2 = ArtifactStore(root=root)
    assert store2.get("x").capacity == manifest["capacity"]
    store.close()
    store2.close()


def _npz_of_numpy_path(t: Table, capacity: int, nvalid: int) -> dict:
    """The arrays the host-side write path stored: every column fetched
    whole, its valid rows kept in order, zero-padded to ``capacity``."""
    mask = np.asarray(t.valid).astype(bool)
    out = {"__valid__": np.arange(capacity) < nvalid}
    for n, c in t.columns.items():
        a = np.asarray(c)[mask][:capacity]
        out[n] = np.pad(a, [(0, capacity - len(a))] + [(0, 0)] * (a.ndim - 1))
    return out


@pytest.mark.parametrize("write_behind", [True, False])
def test_device_compaction_writes_the_numpy_paths_files(
        tmp_path, monkeypatch, write_behind):
    """The flusher compacts on the device and fetches only the stored
    rows; the files, the manifest's crc32, the swapped cache entry and
    the byte counters are what the host-side compaction gave."""
    import time
    import zlib

    import jax.numpy as jnp

    from repro.store.artifacts import _npz_bytes
    # npz members carry the time they were written: hold it still
    fixed = time.localtime(1_600_000_000)
    monkeypatch.setattr(time, "localtime", lambda *a: fixed)
    rng = np.random.default_rng(7)
    n = 100
    mask = np.zeros(n, bool)
    mask[rng.choice(n, 37, replace=False)] = True
    cols = {"v": rng.standard_normal(n).astype(np.float32),   # not sorted
            "s": rng.integers(0, 256, (n, 20), dtype=np.uint8),
            "k": rng.integers(-1000, 1000, n).astype(np.int32)}
    part = Table(Table.from_numpy(cols).columns, jnp.asarray(mask))
    full = Table.from_numpy(cols)
    store = ArtifactStore(root=str(tmp_path / "a"), write_behind=write_behind)
    for name, t, cap in (("part", part, 64), ("full", full, n)):
        io0 = store.io_stats()
        meta = store.put(name, t)
        store.flush()
        assert meta["capacity"] == cap
        want = _npz_of_numpy_path(t, cap, meta["rows"])
        with open(os.path.join(store._path(name), "data.npz"), "rb") as f:
            data = f.read()
        assert data == _npz_bytes(want)
        with open(os.path.join(store._path(name), "manifest.json")) as f:
            crc = json.load(f)["checksums"]["data.npz"]
        assert crc == zlib.crc32(data)
        z = np.load(os.path.join(store._path(name), "data.npz"))
        assert list(z.files) == list(want)
        for k in want:
            assert z[k].dtype == want[k].dtype
            np.testing.assert_array_equal(z[k], want[k])
        cached = store.cache.get(name)
        assert cached.capacity == cap
        if name == "full":        # handed on, not copied
            assert cached is t
        io = store.io_stats()
        assert io["flush_live_bytes"] - io0["flush_live_bytes"] == t.nbytes()
        assert (io["flush_fetch_bytes"] - io0["flush_fetch_bytes"]
                == meta["nbytes"] == cached.nbytes())
        store.drop_caches()
        got = store.get(name).to_numpy()
        for k, c in t.to_numpy().items():
            np.testing.assert_array_equal(got[k], c)
    store.close()
