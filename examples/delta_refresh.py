"""Incremental artifact maintenance walkthrough (DESIGN.md §12).

The append → refresh → exact-hit story, with every claim asserted:

  1. a per-user revenue aggregate runs cold and its artifact is stored;
  2. the page_views dataset GROWS by append (`Catalog.append`) — under
     rule R4 alone this would delete the artifact and recompute from
     zero;
  3. `ReStore.maintain()` derives a delta plan (aggregate the appended
     rows only), merges the partial into the stored artifact, and
     rebinds the entry to the new dataset version;
  4. the new-version query is answered WITHOUT executing anything, and
     the answer is bit-identical to a cold recompute over the appended
     data.

Run: PYTHONPATH=src python examples/delta_refresh.py
"""
import numpy as np

from repro.core import plan as P
from repro.core.plan import rebind_load_versions
from repro.core.restore import ReStore
from repro.dataflow.table import Table
from repro.store.artifacts import ArtifactStore, Catalog

N_USERS = 50


def page_views(seed: int, n: int) -> Table:
    rng = np.random.default_rng(seed)
    return Table.from_numpy({
        "user": rng.integers(0, N_USERS, n).astype(np.int32),
        # integer-valued revenue keeps float32 sums exact, so
        # bit-identity below is checkable
        "revenue": rng.integers(0, 100, n).astype(np.float32),
    })


def revenue_by_user() -> P.PhysicalPlan:
    g = P.groupby(P.load("page_views"), ["user"],
                  {"total": ("sum", "revenue"), "n": ("count", "revenue")})
    return P.PhysicalPlan([P.store(g, "rev_out")])


def canon(t: Table):
    d = t.to_numpy()
    order = np.lexsort(tuple(d[c] for c in sorted(d, reverse=True)))
    return {c: d[c][order] for c in sorted(d)}


def main():
    store = ArtifactStore()
    catalog = Catalog(store)
    catalog.register("page_views", page_views(0, 4096))
    rs = ReStore(catalog, store, heuristic="off")

    # 1. cold run: the aggregate is computed and registered
    _, cold = rs.run_plan(revenue_by_user())
    assert cold.n_executed == 1 and len(rs.repo) == 1
    (entry,) = rs.repo.entries
    print(f"cold run executed; artifact {entry.artifact} stored "
          f"(source version {entry.source_versions['page_views']})")

    # 2. the dataset grows by 10% — version bumps, entry goes stale
    catalog.append("page_views", page_views(7, 410))
    assert catalog.version("page_views") == 1
    assert catalog.is_append_since("page_views", 0)
    print(f"appended 410 rows (delta fraction "
          f"{catalog.delta_fraction('page_views', 0):.1%}); "
          f"entry is stale")

    # 3. refresh from the delta instead of R4 delete-and-recompute
    report = rs.maintain(mode="refresh")
    assert report == {"refreshed": 1, "lazy": 0, "deleted": 0}, report
    assert entry.source_versions["page_views"] == 1, \
        "refresh must rebind the entry to the new version"
    print("maintain(): delta aggregated + merged, entry rebound")

    # 4. the new-version query is an exact hit, bit-identical to cold
    plan_v1 = rebind_load_versions(revenue_by_user(), {"page_views": 1})
    got, warm = rs.run_plan(plan_v1)
    assert warm.n_executed == 0 and warm.n_reused == 1, \
        "refreshed entry must answer the new-version query exactly"

    ref_store = ArtifactStore()
    ref_cat = Catalog(ref_store)
    ref_cat.register("page_views", page_views(0, 4096))
    ref_cat.append("page_views", page_views(7, 410))
    ref_rs = ReStore(ref_cat, ref_store, heuristic="off",
                     rewrite_enabled=False, semantic=False)
    ref, _ = ref_rs.run_plan(plan_v1)
    a, b = canon(ref["rev_out"]), canon(got["rev_out"])
    for c in a:
        assert np.array_equal(a[c], b[c]), c
    print("new-version query: 0 jobs executed, result bit-identical "
          "to cold recompute — OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
