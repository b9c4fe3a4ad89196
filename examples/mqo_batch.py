"""Multi-query batch optimization walkthrough (DESIGN.md §16), with
every claim asserted:

  1. three tenants write their queries in the Pig-style dataflow DSL —
     the front-end is pure notation: a DSL plan is fingerprint-identical
     to hand-built ``core.plan`` wiring, so it shares everything the
     hand-built plan would;
  2. ``optimize_batch`` finds the overlap: the scan+project two of the
     tenants start from is shared exactly, and their filter variants of
     different strength share the weaker (covering) chain by
     subsumption — the third tenant overlaps with nobody and is simply
     passed through;
  3. ``submit_batch`` executes the shared prefix ONCE, fans out one
     ticket per query, and every shared sub-plan is admitted to the
     repository with *known* (not estimated) consumer counts — the
     duplicate-execution audit stays 0;
  4. the batched answers are bit-identical to running each query alone
     on a cold driver.

Run: PYTHONPATH=src python examples/mqo_batch.py
"""
import numpy as np

from repro.core import plan as P
from repro.core.mqo import optimize_batch
from repro.core.restore import ReStore
from repro.dataflow.builder import Dataflow, col
from repro.dataflow.expr import Col
from repro.service.service import ReStoreService
from repro.store.artifacts import ArtifactStore, Catalog
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
    # ---- 1. three tenants' queries, written in the DSL ----------------
    scan = Dataflow.load("page_views").project("user", "timespent")
    alice = (Dataflow.load("page_views")
             .project("user", "estimated_revenue")
             .group_by("user", rev=("sum", "estimated_revenue"))
             .store("alice_revenue"))
    bob = (scan.filter(col("timespent") > 20)
           .group_by("user", n=("count", "timespent")).store("bob_hot"))
    carol = (scan.filter(col("timespent") > 60)
             .group_by("user", n=("count", "timespent"))
             .store("carol_hotter"))
    queries = [alice, bob, carol]

    # the DSL is pure notation: fingerprints match hand-built wiring
    hand = P.PhysicalPlan([P.store(
        P.groupby(P.project(P.load("page_views"),
                            ["user", "estimated_revenue"]),
                  ["user"], {"rev": ("sum", "estimated_revenue")}),
        "alice_revenue")])
    assert (set(alice.build().fingerprints().values())
            == set(hand.fingerprints().values()))
    print("1. DSL plan is fingerprint-identical to hand-built wiring")

    # ---- 2. the optimizer sees the overlap ---------------------------
    bp = optimize_batch(queries)
    kinds = sorted((s.kind, s.n_consumers, s.semantic) for s in bp.shared)
    # bob and carol share the scan+project exactly; carol's stricter
    # filter is answered from bob's covering chain by subsumption;
    # alice overlaps with nobody — and still gets the right answer
    assert ("PROJECT", 2, False) in kinds
    assert any(k == "FILTER" and sem for k, _, sem in kinds)
    print(f"2. shared sub-plans: {kinds}")
    assert bp.known_uses, "shared artifacts carry known-consumer hints"

    # ---- 3. one shared execution, N tickets --------------------------
    store = ArtifactStore()
    cat = Catalog(store)
    pigmix.register_all(cat, n_rows=N_ROWS)
    svc = ReStoreService(cat, store, n_workers=2, heuristic="cost")
    try:
        tickets = svc.submit_batch(queries, tenants=["alice", "bob",
                                                     "carol"])
        batched = [t.result(timeout=120)[0] for t in tickets]
        st = svc.stats()
    finally:
        svc.stop()
    assert st["batches"] == 1
    assert st["batch_shared_subplans"] == len(bp.shared)
    assert st["dup_executions"] == 0
    print(f"3. batch of {len(queries)} ran with "
          f"{st['batch_shared_subplans']} shared sub-plans and "
          f"0 duplicate executions")

    # ---- 4. bit-identical to cold solo runs --------------------------
    for q, got in zip(queries, batched):
        cold_store = ArtifactStore()
        cold_cat = Catalog(cold_store)
        pigmix.register_all(cold_cat, n_rows=N_ROWS)
        want, _ = ReStore(cold_cat, cold_store, heuristic="off").run(q)
        assert set(got) == set(want)
        for k in got:
            a, b = canon(got[k]), canon(want[k])
            assert all(np.array_equal(a[c], b[c]) for c in a)
    print("4. batched answers bit-identical to cold solo runs")

    # Col is re-exported for hand-built plans; the DSL's `col` is the
    # same Expr type, so predicates compare equal across front-ends
    assert (col("timespent") > 20).key() == (Col("timespent") > 20).key()
    print("ok: multi-query batch optimization walkthrough passed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
