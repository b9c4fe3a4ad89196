"""Semantic-reuse benchmark: speedup from subsumption matching with
compensation rewrites (DESIGN.md §10), appended to ``BENCH_core.json``.

The producer query is join + group-by + FILTER(total > θ_base) over
PigMix data; only *whole-job* outputs are stored (heuristic "off" — the
paper's free materialization).  The probe query re-runs with a strictly
STRONGER threshold θ(r), chosen so that a fraction ``r`` of the stored
rows survive (the predicate-overlap ratio).  Three arms per ratio:

  t_plain     fresh driver, no stores, no rewriting        (no-reuse)
  t_exact     warm driver, exact matching only — the FILTER fingerprint
              differs, so only the shared join job is answered
  t_semantic  warm driver with the subsumption fallback — the final job
              is answered from the covering artifact plus a residual
              FILTER, skipping the group-by entirely

The tracked claim (ISSUE 3 acceptance): t_plain / t_semantic ≥ 2 at
overlap ≥ 0.5.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks.common import emit, run_time              # noqa: E402
from repro.core import plan as P                          # noqa: E402
from repro.core.restore import ReStore                    # noqa: E402
from repro.dataflow.expr import Col                       # noqa: E402
from repro.store.artifacts import ArtifactStore, Catalog  # noqa: E402
from repro.workloads import pigmix                        # noqa: E402

OUT = os.path.join(_ROOT, "BENCH_core.json")

# every probe is strictly stronger than the stored predicate (overlap
# 1.0 would be the identical query — the whole-job fast path's business)
OVERLAPS = (0.90, 0.75, 0.50, 0.25)
BASE_KEEP = 0.8        # the stored artifact keeps 80% of the groups


def _query(theta: float) -> P.PhysicalPlan:
    pv = P.project(P.load("page_views"), ["user", "estimated_revenue"])
    u = P.project(P.load("users"), ["name"])
    j = P.join(pv, u, ["user"], ["name"])
    g = P.groupby(j, ["user"], {"total": ("sum", "estimated_revenue")})
    f = P.filter_(g, Col("total") > theta)
    return P.PhysicalPlan([P.store(f, "sem_out")])


def _totals(n_rows: int) -> "list[float]":
    """Per-user revenue totals, host-side (for threshold quantiles)."""
    import numpy as np
    d = pigmix.gen_page_views(n_rows).to_numpy()
    users = d["user"]
    flat = users.reshape(users.shape[0], -1)
    _, inv = np.unique(flat, axis=0, return_inverse=True)
    sums = np.zeros(inv.max() + 1, dtype=np.float64)
    np.add.at(sums, inv, d["estimated_revenue"].astype(np.float64))
    return sorted(sums)


def _theta_for_keep(totals, keep_frac: float) -> float:
    """Threshold keeping ~``keep_frac`` of the groups under total > θ."""
    idx = int(round((1.0 - keep_frac) * (len(totals) - 1)))
    return float(totals[max(0, min(idx, len(totals) - 1))])


def _fresh(n_rows: int, **kw) -> ReStore:
    store = ArtifactStore(root=tempfile.mkdtemp(prefix="restore_sem_"))
    cat = Catalog(store)
    store.put("page_views", pigmix.gen_page_views(n_rows))
    store.put("users", pigmix.gen_users())
    store.put("power_users", pigmix.gen_power_users())
    return ReStore(cat, store, measure_exec=True, **kw)


def _close(rs: ReStore) -> None:
    rs.store.close()
    shutil.rmtree(rs.store.root, ignore_errors=True)


def run(label: str | None = None, n_rows: int = 1 << 15,
        out_path: str = OUT, trials: int = 3):
    # CI sizes the sweep down via env (the docs job exercises the bench
    # on every push; the committed BENCH_core.json entry uses defaults)
    n_rows = int(os.environ.get("SEMANTIC_BENCH_NROWS", n_rows))
    trials = int(os.environ.get("SEMANTIC_BENCH_TRIALS", trials))
    totals = _totals(n_rows)
    theta_base = _theta_for_keep(totals, BASE_KEEP)

    rec = {"label": label or "run", "n_rows": n_rows, "trials": trials,
           "sweep": []}
    for overlap in OVERLAPS:
        theta_q = _theta_for_keep(totals, BASE_KEEP * overlap)
        t_plain, t_exact, t_semantic, hits = [], [], [], 0
        for _ in range(trials):
            rs0 = _fresh(n_rows, heuristic="off", rewrite_enabled=False,
                         semantic=False)
            t_plain.append(run_time(rs0, _query(theta_q)))
            _close(rs0)

            for use_sem, bucket in ((False, t_exact), (True, t_semantic)):
                rs = _fresh(n_rows, heuristic="off", semantic=use_sem)
                rs.run_plan(_query(theta_base))       # seed: whole-job only
                _, rep = rs.run_plan(_query(theta_q))
                bucket.append(rep.total_wall_s)
                if use_sem:
                    hits += rep.n_semantic
                _close(rs)

        med = lambda xs: sorted(xs)[len(xs) // 2]     # noqa: E731
        row = {"overlap": overlap,
               "theta": round(theta_q, 2),
               "t_plain_s": round(med(t_plain), 6),
               "t_exact_s": round(med(t_exact), 6),
               "t_semantic_s": round(med(t_semantic), 6),
               "semantic_hits": hits,
               "speedup_vs_plain": round(
                   med(t_plain) / max(med(t_semantic), 1e-9), 4),
               "speedup_vs_exact": round(
                   med(t_exact) / max(med(t_semantic), 1e-9), 4)}
        rec["sweep"].append(row)
        emit(f"semantic/overlap_{int(overlap * 100)}", row["t_semantic_s"],
             f"speedup={row['speedup_vs_plain']:.2f};"
             f"vs_exact={row['speedup_vs_exact']:.2f};hits={hits}")
        assert hits > 0, f"semantic path did not fire at overlap={overlap}"

    doc = {"runs": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("semantic_runs", [])
    # keep the last 2 prior same-label entries (real predecessors for
    # the nightly consecutive same-label regression gate)
    same = [r for r in runs if r["label"] == rec["label"]][-2:]
    doc["semantic_runs"] = [r for r in runs
                            if r["label"] != rec["label"]] + same + [rec]
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    at50 = next(r for r in rec["sweep"] if r["overlap"] == 0.50)
    emit("semantic/summary", 0.0,
         f"speedup_at_50={at50['speedup_vs_plain']:.2f};out={out_path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(label=sys.argv[1] if len(sys.argv) > 1 else None)
