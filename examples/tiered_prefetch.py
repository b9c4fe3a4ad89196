"""Tiered artifact store walkthrough (DESIGN.md §15).

The device → host → disk → remote story, with every claim asserted:

  1. artifacts are stored on disk and demoted to an S3-style remote
     tier (column-compressed blob, atomic publish) — after which
     exactly ONE durable tier owns each artifact;
  2. a fresh store over the same remote cold-starts from remote-only
     state via one batched header fetch, and a cold `get` serves the
     exact bytes back through the latency-injected remote;
  3. a speculative prefetcher mines the store's read log, predicts the
     hot artifact, and warms it with a batched background fetch — so
     the next probe is a device hit instead of a remote round-trip;
  4. promotion rehydrates the artifact to disk bit-identically and
     retires the remote copy (still exactly one owner).

Run: PYTHONPATH=src python examples/tiered_prefetch.py
"""
import tempfile
import time
import zlib

import numpy as np

from repro.dataflow.table import Table
from repro.store.artifacts import ArtifactStore
from repro.store.prefetch import SpeculativePrefetcher
from repro.store.tiers import RemoteObjectStore


def make_table(i: int, n: int = 4096) -> Table:
    rng = np.random.default_rng(i)
    return Table.from_numpy({
        "k": rng.integers(0, 997, n).astype(np.int64),
        "v": rng.random(n).astype(np.float32),
    })


def crc(t: Table) -> int:
    d = t.to_numpy()
    acc = 0
    for c in sorted(d):
        acc = zlib.crc32(np.ascontiguousarray(d[c]).tobytes(),
                         zlib.crc32(c.encode(), acc))
    return acc


def main():
    disk = tempfile.mkdtemp(prefix="tier_disk_")
    remote_root = tempfile.mkdtemp(prefix="tier_remote_")
    names = [f"agg_{i}" for i in range(6)]

    # 1. populate disk, then demote everything to the remote tier
    store = ArtifactStore(root=disk,
                          remote=RemoteObjectStore(remote_root),
                          write_behind=False)
    refs = {}
    for i, name in enumerate(names):
        t = make_table(i)
        refs[name] = crc(t)
        store.put(name, t)
        assert store.authoritative_tier(name) == "disk"
        store.demote_to_remote(name)
        assert store.authoritative_tier(name) == "remote"
    store.close()
    print(f"demoted {len(names)} artifacts to the remote tier")

    # 2. cold start: a FRESH disk root over the same remote.  Reopen
    # indexes the population with one batched header fetch; a cold get
    # pays the injected latency but serves the exact bytes.
    remote = RemoteObjectStore(remote_root, latency_s=0.01)
    store = ArtifactStore(root=tempfile.mkdtemp(prefix="tier_disk2_"),
                          remote=remote, write_behind=False)
    assert all(store.exists(n) for n in names), "cold open must index"
    t0 = time.perf_counter()
    assert crc(store.get("agg_0")) == refs["agg_0"]
    cold_s = time.perf_counter() - t0
    assert cold_s >= 0.01, "cold read must pay the remote latency"
    print(f"cold remote read: {cold_s * 1e3:.1f} ms (bit-identical)")

    # 3. speculative prefetch: replay a skewed probe pattern, let the
    # prefetcher mine the read log, then warm its prediction.
    store.drop_caches()
    pf = SpeculativePrefetcher(store, k=1)
    for name in ["agg_3", "agg_3", "agg_1", "agg_3"]:
        store.get(name)
    pf.poll()
    assert pf.predict() == ["agg_3"], "zipfian skew must rank agg_3 first"
    store.drop_caches()                       # tenant pressure evicts all
    warmed = pf.prefetch()                    # background, untimed re-warm
    assert warmed == ["agg_3"]
    assert store.residency("agg_3") == "device"
    t0 = time.perf_counter()
    assert crc(store.get("agg_3")) == refs["agg_3"]
    warm_s = time.perf_counter() - t0
    assert warm_s < 0.01, "a warmed probe must not pay remote latency"
    pf.poll()                                 # settle accounting
    assert pf.hits >= 1 and pf.hit_rate > 0.0
    print(f"prefetched {warmed} -> warm probe {warm_s * 1e3:.2f} ms, "
          f"hit rate {pf.hit_rate:.2f}")

    # 4. promote back to disk: bit-identical, remote copy retired
    store.promote_from_remote("agg_3")
    assert store.authoritative_tier("agg_3") == "disk"
    assert not remote.exists(store._remote_key("agg_3"))
    store.cache.drop("agg_3")
    assert crc(store.get("agg_3")) == refs["agg_3"]
    print("promotion round-trip bit-identical; exactly one durable owner")
    store.close()
    print("OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
