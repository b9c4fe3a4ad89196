"""Repository economics walkthrough: two workflows sharing one
byte-budgeted repository (store -> evict -> re-derive), DESIGN.md §9.

  1. Workflow A (L3 sum) populates the shared repository; its join
     sub-job becomes a stored artifact.
  2. Workflow B (L3 mean) — a different tenant's variant — reuses A's
     join job straight from the repository.
  3. Eviction: rule R3 (time-window) wipes the unused entries AND
     deletes their artifacts from the store through the bound store.
  4. Re-derivation: workflow B runs again, recomputes from the sources,
     and repopulates the repository — same results as step 2.
  5. Byte-budget admission: a tiny repository keeps the artifact with
     the highest predicted benefit per byte and rejects/evicts the rest.

Every printed claim is asserted, so this file doubles as a smoke test
(CI runs it in the docs job).

Usage: PYTHONPATH=src python examples/policy_walkthrough.py
"""
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import plan as P
from repro.core.cost_model import CostModel
from repro.core.repository import Repository, make_entry
from repro.core.restore import ReStore
from repro.store.artifacts import ArtifactStore, Catalog
from repro.workloads import pigmix


def sorted_rows(table):
    return {k: np.sort(v.astype(np.float64), axis=0)
            for k, v in table.to_numpy().items()
            if v.dtype.kind in "if"}


def main():
    store = ArtifactStore()
    catalog = Catalog(store)
    pigmix.register_all(catalog, n_rows=1 << 12)
    repo = Repository(budget_bytes=64 * 1024 * 1024, policy="cost")

    print("=== 1. Workflow A (tenant 1): L3 sum populates the repository ===")
    rs_a = ReStore(catalog, store, repo, heuristic="aggressive")
    _, rep_a = rs_a.run_plan(pigmix.L3("sum"))
    assert rep_a.n_executed == 2, "cold run must execute both jobs"
    assert len(repo) > 0, "repository must hold entries after workflow A"
    print(f"  executed {rep_a.n_executed} jobs, repository holds "
          f"{len(repo)} entries / {repo.total_stored_bytes()} bytes")

    print("=== 2. Workflow B (tenant 2): L3 mean reuses A's join job ===")
    rs_b = ReStore(catalog, store, repo, heuristic="aggressive")
    res_b, rep_b = rs_b.run_plan(pigmix.L3("mean"))
    assert not rep_b.jobs[0].executed, "join job must come from the repo"
    assert rep_b.jobs[1].executed, "only the mean aggregate recomputes"
    print(f"  join job reused ({rep_b.jobs[0].reused_artifacts}); "
          f"only the aggregate executed")

    print("=== 3. Eviction: rule R3 wipes the repo AND the store ===")
    artifacts = [e.artifact for e in repo.entries]
    time.sleep(0.02)
    dropped = repo.evict_unused(window_s=0.0)   # bound store deletes too
    assert dropped == len(artifacts) and len(repo) == 0
    for a in artifacts:
        assert not store.exists(a), f"{a} must be deleted from the store"
    print(f"  evicted {dropped} entries; artifacts deleted from the store")

    print("=== 4. Re-derivation: B recomputes from sources, same answer ===")
    res_b2, rep_b2 = rs_b.run_plan(pigmix.L3("mean"))
    assert rep_b2.n_executed == 2, "after eviction everything re-executes"
    a, b = sorted_rows(res_b["L3_mean_out"]), sorted_rows(res_b2["L3_mean_out"])
    for c in a:
        assert np.allclose(a[c], b[c], atol=1e-3), f"column {c} differs"
    assert len(repo) > 0, "re-derivation repopulates the repository"
    print(f"  re-executed {rep_b2.n_executed} jobs; results identical; "
          f"repository repopulated ({len(repo)} entries)")

    print("=== 5. Byte budget: benefit-per-byte admission ===")
    cm = CostModel(fixed_io_s=0.0, reuse_halflife_s=1e9)
    tiny = Repository(budget_bytes=2000, policy="cost", cost_model=cm)
    tiny.bind_store(store)

    def synthetic(name, producer_cost_s):
        pl = P.PhysicalPlan([P.store(P.project(P.load("d"), [name]), name)])
        store.put(name, pigmix.gen_users())
        return make_entry(pl, name, bytes_in=10_000, bytes_out=1000,
                          producer_cost_s=producer_cost_s)

    assert tiny.add(synthetic("art/cheap-to-recompute", 1e-4))
    assert tiny.add(synthetic("art/expensive-join", 5.0))
    # budget full (2 x 1000 bytes); a mid-value entry evicts the cheap one
    assert tiny.add(synthetic("art/mid-value", 1.0))
    kept = {e.artifact for e in tiny.entries}
    assert kept == {"art/expensive-join", "art/mid-value"}, kept
    assert not store.exists("art/cheap-to-recompute")
    # ... and a low-value newcomer is rejected outright
    assert not tiny.add(synthetic("art/near-worthless", 1e-5))
    assert tiny.rejections == 1
    print(f"  kept {sorted(kept)} under a 2000-byte budget; "
          f"evicted the cheap-to-recompute artifact, rejected the "
          f"worthless one")

    print("policy walkthrough OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
