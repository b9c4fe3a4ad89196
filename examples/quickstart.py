"""Quickstart: the paper's Q1/Q2 story end to end.

  1. Run Q1 (PigMix L2-style join).  ReStore stores the join output AND
     the sub-job outputs picked by the Aggressive Heuristic.
  2. Run Q2 (L3-style join+group).  Its first job is answered entirely
     from the repository (whole-job reuse, paper Fig 4); only the group
     job executes.
  3. Run Q3 (same Load+Project prefix, different filter).  The prefix is
     answered from a stored sub-job (paper Fig 6).

Usage: PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.core import plan as P
from repro.core.restore import ReStore
from repro.dataflow.expr import Col
from repro.store.artifacts import ArtifactStore, Catalog
from repro.workloads import pigmix


def main():
    store = ArtifactStore()
    catalog = Catalog(store)
    pigmix.register_all(catalog, n_rows=1 << 14)
    # min_splice_benefit_s=0: this walkthrough demonstrates the paper's
    # splice MECHANICS at toy scale, where the production default would
    # (correctly) decline the Q3 streaming splice as not worth its IO
    # (DESIGN.md §14)
    restore = ReStore(catalog, store, heuristic="aggressive",
                      min_splice_benefit_s=0.0)

    print("=== Q1: join page_views x users (paper Fig 2) ===")
    # exactly the paper's Q1: project both sources, join on user==name
    pv1 = P.project(P.load("page_views"), ["user", "estimated_revenue"])
    u1 = P.project(P.load("users"), ["name"])
    q1 = P.PhysicalPlan([P.store(P.join(pv1, u1, ["user"], ["name"]),
                                 "q1_out")])
    _, rep1 = restore.run_plan(q1)
    for j in rep1.jobs:
        print(f"  job {j.job_id}: executed={j.executed} "
              f"stored={len(j.stored_candidates)} sub-job artifacts")
    print(f"  repository now holds {len(restore.repo)} plans")

    print("=== Q2: join + group (paper Fig 3) ===")
    q2 = pigmix.L3("sum")
    res2, rep2 = restore.run_plan(q2)
    for j in rep2.jobs:
        print(f"  job {j.job_id}: executed={j.executed} "
              f"reused={j.reused_artifacts}")
    assert not rep2.jobs[0].executed, "join job must be reused from Q1"
    print(f"  -> job 1 answered from the repository (whole-job reuse); "
          f"result rows: {int(res2[list(res2)[0]].num_valid())}")

    print("=== Q3: same Load+Project prefix, new filter (paper Fig 6) ===")
    pv = P.project(P.load("page_views"), ["user", "estimated_revenue"])
    f = P.filter_(pv, Col("estimated_revenue") > 50.0)
    q3 = P.PhysicalPlan([P.store(f, "q3_out")])
    _, rep3 = restore.run_plan(q3)
    j3 = rep3.jobs[0]
    print(f"  job 0: reused sub-job artifacts {j3.reused_artifacts}")
    print(f"  plan shrank {j3.n_ops_before} -> {j3.n_ops_after} operators")
    assert j3.reused_artifacts, "sub-job reuse must fire"
    print("quickstart OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
