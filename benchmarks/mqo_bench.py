"""Multi-query batch-optimizer benchmark, appended to ``BENCH_core.json``
as ``mqo_runs`` (DESIGN.md §16).

Protocol — one batch of 7 queries from 3 tenants, all built through the
Pig-style DSL, engineered so the overlap sits *mid-job* (an expensive
shared FOREACH + selective FILTERs under divergent GROUPBYs, plus a
filter-variant family that only subsumption can share).  Blocking-op
boundaries are content-addressed and reused by plain sequential ReStore
after one sighting, so this workload isolates what batching adds:

  * no-reuse    — heuristic off, rewriting off: every query pays the
                  full pipeline (the paper's baseline);
  * sequential  — one cost-mode driver, queries run in arrival order:
                  the seen-once admission gate means shared chains
                  execute twice before the repository steps in, and the
                  filter variants never cross-share (each is seen once);
  * batched     — same cost-mode configuration, but the batch goes
                  through ``run_batch``: common sub-plans execute once
                  in a deduplicated shared prefix, known-uses hints
                  admit them with certain (not estimated) consumer
                  counts, and the subsumed variants compensate from the
                  covering chain.

All arms use measure_exec=True (jobs warmed off the clock — compile time
is excluded, as everywhere in this harness) on a disk-backed store, and
run MQO_BENCH_TRIALS times taking the median; batched time counts
planning + shared prefix + every per-query run.  The record also audits
``identical`` (batched results bit-identical to sequential) and
``dup_executions`` (a shared sub-plan executing twice anywhere is a
correctness bug in the optimizer, not a perf detail).

Env knobs: MQO_BENCH_NROWS (default 1<<15), MQO_BENCH_TRIALS (default 3).
"""
from __future__ import annotations

import json
import os
import shutil
import statistics
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import numpy as np                                        # noqa: E402

from benchmarks.common import emit, fresh_restore         # noqa: E402
from repro.core.mqo import run_batch                      # noqa: E402
from repro.dataflow.builder import Dataflow, col          # noqa: E402

OUT = os.path.join(_ROOT, "BENCH_core.json")

N_ROWS = int(os.environ.get("MQO_BENCH_NROWS", 1 << 15))
TRIALS = int(os.environ.get("MQO_BENCH_TRIALS", 3))
N_TENANTS = 3


def _heavy() -> Dataflow:
    """The expensive shared map phase: one wide FOREACH every tenant's
    query starts from (score in [0, 553))."""
    return Dataflow.load("page_views").foreach(
        user=col("user"),
        ts=col("timespent"),
        score=col("timespent") * 3 + col("timestamp") * 11,
        rev=col("estimated_revenue") * 2 + col("timespent"),
        load=col("timespent") * col("timespent") + col("action") * 13,
        wt=col("timestamp") * 7 + col("action") % 5,
    )


def make_batch():
    """7 queries, 3 tenants: an exact-shared selective chain under three
    divergent group-bys, a subsumption family of score thresholds, and
    one more exact pair on a different column."""
    hot = _heavy().filter(col("score") > 500)
    cool = _heavy().filter(col("load") > 9000)

    def var(t):
        return _heavy().filter(col("score") > t)

    queries = [
        hot.group_by("user", s=("sum", "score"),
                     n=("count", "ts")).store("mqo_q1").build(),
        hot.group_by("ts", r=("sum", "rev")).store("mqo_q2").build(),
        hot.group_by("wt", v=("mean", "load")).store("mqo_q3").build(),
        var(400).group_by("user", a=("mean", "rev")).store("mqo_q4")
           .build(),
        var(460).group_by("ts", b=("sum", "load")).store("mqo_q5")
           .build(),
        var(500).group_by("wt", c=("count", "ts")).store("mqo_q6")
           .build(),
        cool.group_by("user", w=("sum", "wt")).store("mqo_q7").build(),
    ]
    tenants = ["a", "b", "c", "a", "b", "c", "a"]
    return queries, tenants


def _canon(table):
    d = table.to_numpy()

    def key(a):
        return (np.ascontiguousarray(a).view(f"S{a.shape[1]}").ravel()
                if a.ndim == 2 else a)

    order = np.lexsort(tuple(key(d[c]) for c in sorted(d, reverse=True)))
    return {c: d[c][order] for c in sorted(d)}


def _identical(a, b) -> bool:
    if set(a) != set(b):
        return False
    for k in a:
        ca, cb = _canon(a[k]), _canon(b[k])
        if set(ca) != set(cb) or any(not np.array_equal(ca[c], cb[c])
                                     for c in ca):
            return False
    return True


def _teardown(rs) -> None:
    rs.store.close()
    shutil.rmtree(rs.store.root, ignore_errors=True)


def _trial(queries):
    """One cold trial of all three arms; returns
    (t_noreuse, t_sequential, t_batched, seq_results, batch_result)."""
    rs = fresh_restore(N_ROWS, "off", rewrite=False)
    t_noreuse = sum(rs.run(q)[1].total_wall_s for q in queries)
    _teardown(rs)

    rs = fresh_restore(N_ROWS, "cost", rewrite=True)
    seq = [rs.run(q) for q in queries]
    t_sequential = sum(rep.total_wall_s for _, rep in seq)
    _teardown(rs)

    rs = fresh_restore(N_ROWS, "cost", rewrite=True)
    br = run_batch(rs, queries)
    t_batched = (br.batch.planning_s + br.shared_wall_s
                 + sum(rep.total_wall_s for rep in br.reports))
    _teardown(rs)
    return (t_noreuse, t_sequential, t_batched,
            [out for out, _ in seq], br)


def run(label: str | None = None, out_path: str = OUT):
    queries, tenants = make_batch()
    rows = []
    for _ in range(TRIALS):
        rows.append(_trial(queries))
    t_noreuse = statistics.median(r[0] for r in rows)
    t_sequential = statistics.median(r[1] for r in rows)
    t_batched = statistics.median(r[2] for r in rows)
    seq_results, br = rows[-1][3], rows[-1][4]
    identical = all(_identical(b, s)
                    for b, s in zip(br.results, seq_results))
    dups = max(r[4].dup_executions for r in rows)

    sp_seq = t_sequential / max(t_batched, 1e-9)
    sp_plain = t_noreuse / max(t_batched, 1e-9)
    emit("mqo/no_reuse", t_noreuse, f"n_rows={N_ROWS}")
    emit("mqo/sequential", t_sequential,
         f"speedup_vs_plain={t_noreuse / max(t_sequential, 1e-9):.2f}x")
    emit("mqo/batched", t_batched,
         f"speedup_vs_sequential={sp_seq:.2f}x;"
         f"shared={len(br.batch.shared)};dups={dups};"
         f"identical={identical}")

    rec = {
        "label": label or "run",
        "n_rows": N_ROWS,
        "n_queries": len(queries),
        "n_tenants": len(set(tenants)),
        "trials": TRIALS,
        "t_noreuse_s": round(t_noreuse, 4),
        "t_sequential_s": round(t_sequential, 4),
        "t_batched_s": round(t_batched, 4),
        "speedup_batched_vs_sequential": round(sp_seq, 4),
        "speedup_batched_vs_noreuse": round(sp_plain, 4),
        "shared_subplans": len(br.batch.shared),
        "semantic_subplans": sum(1 for s in br.batch.shared if s.semantic),
        "dup_executions": dups,
        "identical": identical,
    }

    doc = {"runs": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("mqo_runs", [])
    doc["mqo_runs"] = [r for r in runs if r["label"] != rec["label"]]
    doc["mqo_runs"].append(rec)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    emit("mqo/done", 0.0, f"out={out_path}")
    return rec


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(label=sys.argv[1] if len(sys.argv) > 1 else None)
