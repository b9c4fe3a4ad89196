"""LM data pipeline with ReStore reuse across training runs.

Two pipeline configurations share their tokenize+filter prefix; the
second run reuses the first run's intermediate artifacts — exactly the
paper's sub-job reuse, applied to the framework's own data preparation.

Usage: PYTHONPATH=src python examples/lm_data_pipeline.py
"""
import sys

sys.path.insert(0, "src")

from repro.core.restore import ReStore
from repro.store.artifacts import ArtifactStore, Catalog
from repro.train.data import pipeline_plan, synthetic_corpus


def main():
    store = ArtifactStore()
    catalog = Catalog(store)
    catalog.register("corpus", synthetic_corpus(512, 128, 8192))
    # min_splice_benefit_s=0: the walkthrough pins prefix-reuse
    # MECHANICS at toy scale, where the production default would
    # (correctly) decline the streaming tokenize+filter splice as not
    # worth its IO (DESIGN.md §14)
    restore = ReStore(catalog, store, heuristic="aggressive",
                      min_splice_benefit_s=0.0)

    print("=== run A: quality > 0.3 ===")
    _, repA = restore.run_plan(pipeline_plan(0.3, out_name="corpusA"))
    for j in repA.jobs:
        print(f"  job {j.job_id}: executed={j.executed} "
              f"stored={len(j.stored_candidates)}")

    print("=== run A again (identical pipeline) ===")
    _, repA2 = restore.run_plan(pipeline_plan(0.3, out_name="corpusA"))
    print(f"  jobs executed: {repA2.n_executed} (expect 0 — full reuse)")
    assert repA2.n_executed == 0

    print("=== run B: same filter, extra length cut ===")
    _, repB = restore.run_plan(pipeline_plan(0.3, min_length=64,
                                             out_name="corpusB"))
    reused = sum(len(j.reused_artifacts) for j in repB.jobs)
    print(f"  artifacts reused from run A: {reused}")
    assert reused > 0, "shared tokenize+filter prefix must be reused"
    print("lm_data_pipeline OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
