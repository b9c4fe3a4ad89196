"""Concurrent fault-tolerant ReStore service walkthrough (DESIGN.md
§13), with every claim asserted:

  1. two tenants submit workflows to a 4-worker service sharing ONE
     repository — bob's variant reuses the join sub-job alice's query
     materialized moments earlier;
  2. a stampede of identical submissions collapses via singleflight:
     one execution, every ticket gets the (identical) results, and the
     duplicate-execution counter stays 0;
  3. an artifact is corrupted on disk (one flipped byte); the checksum
     catches it on load, the artifact is quarantined, and the query
     transparently falls back to a cold recompute — same answer;
  4. the repository journal survives a "restart": a fresh store +
     recovered repository still answer alice's query with zero
     executed jobs.

Run: PYTHONPATH=src python examples/service_concurrent.py
"""
import os
import tempfile

import numpy as np

from repro.core.repository import Repository
from repro.service.journal import RepositoryJournal
from repro.service.service import ReStoreService
from repro.store.artifacts import ArtifactStore, Catalog, _encode_name
from repro.workloads import pigmix

N_ROWS = 2048


def canon(table):
    d = table.to_numpy()

    def key(a):
        return (np.ascontiguousarray(a).view(f"S{a.shape[1]}").ravel()
                if a.ndim == 2 else a)

    order = np.lexsort(tuple(key(d[c]) for c in sorted(d, reverse=True)))
    return {c: d[c][order] for c in sorted(d)}


def main():
    root = tempfile.mkdtemp(prefix="restore_service_")
    store = ArtifactStore(root=root)
    cat = Catalog(store)
    pigmix.register_all(cat, n_rows=N_ROWS)
    svc = ReStoreService(cat, store, Repository(), n_workers=4,
                         journal=RepositoryJournal(root))

    # -- 1. cross-tenant sub-job reuse through the shared repository
    results_a, rep_a = svc.run(pigmix.L3("sum"), tenant="alice")
    assert rep_a.n_executed == 2, "alice runs cold: join + groupby"
    _, rep_b = svc.run(pigmix.L3("mean"), tenant="bob")
    assert not rep_b.jobs[0].executed, \
        "bob's variant reuses alice's join sub-job"
    print(f"[1] alice executed {rep_a.n_executed} jobs cold; "
          f"bob reused her join and executed "
          f"{sum(1 for j in rep_b.jobs if j.executed)}")

    # -- 2. stampede control: 6 identical submissions, one execution
    tickets = [svc.submit(pigmix.L6(), tenant=t)
               for t in ("alice", "bob", "alice", "bob", "carol", "dan")]
    outs = [t.result(timeout=300) for t in tickets]
    st = svc.stats()
    assert st["singleflight_hits"] == 5, "five tickets drafted behind one"
    assert st["dup_executions"] == 0, "the key never executed twice"
    ref = canon(outs[0][0]["L6_out"])
    for results, _ in outs[1:]:
        got = canon(results["L6_out"])
        assert all(np.array_equal(ref[c], got[c]) for c in ref)
    print(f"[2] 6 identical submissions -> "
          f"{st['singleflight_hits']} singleflight hits, "
          f"{st['dup_executions']} duplicate executions")

    # -- 3. corruption -> quarantine -> transparent cold fallback
    store.flush()
    victim = svc.repo.entries[0].artifact
    d = os.path.join(root, _encode_name(victim))
    npz = [f for f in os.listdir(d) if f.endswith(".npz")][0]
    with open(os.path.join(d, npz), "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))        # one flipped bit(ish)
    baseline = canon(results_a["L3_sum_out"])
    svc.stop()

    store2 = ArtifactStore(root=root)        # cold caches: disk is read
    cat2 = Catalog(store2)
    pigmix.register_all(cat2, n_rows=N_ROWS)
    repo2, journal2 = RepositoryJournal.recover(store2)
    assert journal2.reconciled_drops >= 1, \
        "recovery reconciles the corrupt artifact away"
    assert all(store2.exists(e.artifact) and store2.verify(e.artifact)
               for e in repo2.entries)
    svc2 = ReStoreService(cat2, store2, repo2, n_workers=2,
                          journal=journal2)
    results_c, rep_c = svc2.run(pigmix.L3("sum"), tenant="alice")
    got = canon(results_c["L3_sum_out"])
    assert all(np.array_equal(baseline[c], got[c]) for c in baseline), \
        "cold fallback reproduces the original answer exactly"
    print(f"[3] corrupted {victim!r} was quarantined "
          f"(reconciled_drops={journal2.reconciled_drops}); "
          f"recompute matches the original bit-for-bit")

    # -- 4. journal recovery keeps reuse working across the "restart"
    assert rep_c.degraded == 0, "recovery already dropped the bad entry"
    _, rep_d = svc2.run(pigmix.L3("mean"), tenant="bob")
    assert not rep_d.jobs[0].executed, \
        "journal-recovered repository still serves the join sub-job"
    svc2.stop()
    print(f"[4] after restart + recovery: bob's query reused the join "
          f"again ({len(repo2)} entries survived)")
    print("service walkthrough OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
