"""Tiered-store benchmark: speculative prefetch vs demand paging over a
device → host → disk → remote hierarchy, appended to ``BENCH_core.json``
(DESIGN.md §15).

Setup: N artifacts live remote-authoritative behind an emulated object
store with per-request latency and bandwidth injection.  A zipfian
probe stream reads them through a tiered ArtifactStore whose device and
host budgets each hold only a few artifacts, and every ``flush_every``
probes the working set is dropped (``drop_caches`` — other tenants
claiming the accelerator between this stream's bursts).  Both arms see
the IDENTICAL probe sequence, budgets, and pressure:

  off — demand paging: every cold probe pays the remote round-trip
        inside its own timed window;
  on  — a ``SpeculativePrefetcher`` mines the store's read log and,
        between probes (the background cadence a service runs it on,
        off the clock), re-warms the predicted top-k with ONE batched
        fetch.

The timed quantity is the sum of probe ``get()`` walls — the store-level
analogue of the stream drivers' timed windows (the engine warms loads
off the clock, so prefetch benefit is only observable here).  Gates
(tools/check_bench.py): prefetch speedup ≥ 1.3x at full size,
bit-identical probe results between arms at any size, and a cold start
from the remote tier alone (fresh disk root, batched rehydrate) must
complete.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
import zlib

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import numpy as np                                        # noqa: E402

from benchmarks.common import emit                        # noqa: E402
from repro.core.cost_model import CostModel               # noqa: E402
from repro.dataflow.table import Table                    # noqa: E402
from repro.store.artifacts import ArtifactStore           # noqa: E402
from repro.store.prefetch import SpeculativePrefetcher    # noqa: E402
from repro.store.tiers import RemoteObjectStore           # noqa: E402

OUT = os.path.join(_ROOT, "BENCH_core.json")

REMOTE_LATENCY_S = 0.015
REMOTE_BW = 2e8


def _art(i: int) -> str:
    return f"tier_art_{i:03d}"


def _mk_table(i: int, n_rows: int) -> Table:
    rng = np.random.default_rng(1000 + i)
    return Table.from_numpy({
        "k": rng.integers(0, 1 << 40, n_rows).astype(np.int64),
        "v": rng.standard_normal(n_rows).astype(np.float32)})


def _crc(table: Table) -> int:
    d = table.to_numpy()
    h = 0
    for c in sorted(d):
        h = zlib.crc32(np.ascontiguousarray(d[c]).tobytes(), h)
    return h


def _probe_seq(n_arts: int, probes: int, zipf_s: float = 1.1,
               seed: int = 7):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_arts + 1) ** zipf_s
    p /= p.sum()
    perm = np.random.default_rng(seed + 1).permutation(n_arts)
    return [int(perm[rng.choice(n_arts, p=p)]) for _ in range(probes)]


def _populate(disk_root: str, remote_root: str, n_arts: int, n_rows: int,
              art_bytes: int):
    """Fresh tiered store with every artifact remote-authoritative."""
    remote = RemoteObjectStore(remote_root, latency_s=0.0)  # free setup
    store = ArtifactStore(root=disk_root, cache_bytes=4 * art_bytes,
                          host_bytes=4 * art_bytes, remote=remote)
    for i in range(n_arts):
        store.put(_art(i), _mk_table(i, n_rows))
    store.flush()
    for i in range(n_arts):
        store.demote_to_remote(_art(i))
    store.drop_caches()
    store.close()
    return remote


def _run_arm(prefetch: bool, n_arts: int, n_rows: int, art_bytes: int,
             seq, flush_every: int, k: int):
    disk_root = tempfile.mkdtemp(prefix="tier_bench_")
    remote_root = tempfile.mkdtemp(prefix="tier_remote_")
    _populate(disk_root, remote_root, n_arts, n_rows, art_bytes)
    remote = RemoteObjectStore(remote_root, latency_s=REMOTE_LATENCY_S,
                               bandwidth_bytes_s=REMOTE_BW)
    store = ArtifactStore(root=disk_root, cache_bytes=4 * art_bytes,
                          host_bytes=4 * art_bytes, remote=remote)
    pf = SpeculativePrefetcher(store, k=k) if prefetch else None
    total = 0.0
    crcs = []
    for i, a in enumerate(seq):
        if i and i % flush_every == 0:
            store.drop_caches()         # tenant pressure: both arms
            if pf is not None:
                pf.prefetch()           # background re-warm, off clock
        t0 = time.perf_counter()
        t = store.get(_art(a))
        total += time.perf_counter() - t0
        crcs.append(_crc(t))
        if pf is not None:
            pf.prefetch()               # between-probe cadence, off clock
    stats = pf.stats() if pf is not None else {}
    cm = CostModel()
    cm.calibrate_io(store)
    bw = {"disk": cm.load_bw, **cm.tier_bw}
    store.close()
    return {"wall_s": total, "crcs": crcs, "prefetch": stats, "bw": bw,
            "disk_root": disk_root, "remote_root": remote_root}


def _cold_start(disk_root: str, remote_root: str, n_arts: int) -> float:
    """Fresh machine, remote tier only: reopen over an EMPTY disk root
    and rehydrate every artifact (batched head index + batched fetch)."""
    fresh = tempfile.mkdtemp(prefix="tier_cold_")
    remote = RemoteObjectStore(remote_root, latency_s=REMOTE_LATENCY_S,
                               bandwidth_bytes_s=REMOTE_BW)
    t0 = time.perf_counter()
    store = ArtifactStore(root=fresh, cache_bytes=1 << 30,
                          host_bytes=1 << 30, remote=remote)
    names = [_art(i) for i in range(n_arts)]
    assert all(store.exists(n) for n in names), \
        "cold start: remote index incomplete"
    warmed = store.prewarm(names)
    assert len(warmed) == n_arts, \
        f"cold start rehydrated {len(warmed)}/{n_arts}"
    cold_s = time.perf_counter() - t0
    store.close()
    shutil.rmtree(fresh, ignore_errors=True)
    return cold_s


def run(label: str | None = None, n_rows: int = 1 << 16,
        out_path: str = OUT):
    n_rows = int(os.environ.get("TIER_BENCH_NROWS", n_rows))
    n_arts = int(os.environ.get("TIER_BENCH_ARTS", 24))
    probes = int(os.environ.get("TIER_BENCH_PROBES", 120))
    flush_every = int(os.environ.get("TIER_BENCH_FLUSH_EVERY", 12))
    k = int(os.environ.get("TIER_BENCH_K", 6))
    art_bytes = _mk_table(0, n_rows).nbytes()
    seq = _probe_seq(n_arts, probes)

    off = _run_arm(False, n_arts, n_rows, art_bytes, seq, flush_every, k)
    on = _run_arm(True, n_arts, n_rows, art_bytes, seq, flush_every, k)
    identical = off["crcs"] == on["crcs"]
    speedup = off["wall_s"] / max(on["wall_s"], 1e-9)
    cold_s = _cold_start(on["disk_root"], on["remote_root"], n_arts)
    for r in (off, on):
        shutil.rmtree(r["disk_root"], ignore_errors=True)
        shutil.rmtree(r["remote_root"], ignore_errors=True)

    rec = {"label": label or "run", "n_rows": n_rows,
           "n_artifacts": n_arts, "probes": probes,
           "flush_every": flush_every, "prefetch_k": k,
           "remote_latency_s": REMOTE_LATENCY_S,
           "t_off_s": round(off["wall_s"], 6),
           "t_on_s": round(on["wall_s"], 6),
           "speedup_prefetch": round(speedup, 4),
           "prefetch_hit_rate": round(
               on["prefetch"].get("hit_rate", 0.0), 4),
           "prefetched": on["prefetch"].get("prefetched", 0),
           "cold_start_s": round(cold_s, 6),
           "identical": identical,
           "bw": {t: round(v, 1) for t, v in on["bw"].items()}}
    emit("tier/prefetch", on["wall_s"],
         f"off={off['wall_s']:.4f}s;speedup={speedup:.2f};"
         f"hit_rate={rec['prefetch_hit_rate']:.2f};"
         f"identical={identical}")
    emit("tier/cold_start", cold_s, f"n_artifacts={n_arts}")

    doc = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("tier_runs", [])
    # keep the last 2 prior same-label entries (the nightly regression
    # gate compares consecutive same-label entries)
    same = [r for r in runs if r["label"] == rec["label"]][-2:]
    doc["tier_runs"] = [r for r in runs
                        if r["label"] != rec["label"]] + same + [rec]
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    emit("tier/done", 0.0, f"out={out_path}")
    return rec


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(label=sys.argv[1] if len(sys.argv) > 1 else None)
