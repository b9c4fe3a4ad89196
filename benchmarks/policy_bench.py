"""Policy benchmark: cumulative stream runtime vs repository byte budget,
appended to ``BENCH_core.json`` (DESIGN.md §9).

One multi-tenant zipfian stream (identical event schedule for every arm,
dataset churn included) is replayed under three policies:

  off   — recompute everything (no reuse)                [budget-free]
  lru   — store everything, LRU eviction at the budget
  cost  — cost-model materialization + benefit-per-byte eviction

for a sweep of budgets expressed as fractions of the total candidate
byte volume (measured once with an unbudgeted store-everything run).
The paper's economics predict — and this snapshot tracks PR over PR —
that at tight budgets (~25%) the cost policy beats both baselines:
unlike LRU it keeps the artifacts whose recompute-savings per byte are
highest, and unlike `off` it reuses at all.
"""
from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks.common import emit                        # noqa: E402
from repro.workloads.stream import StreamConfig, run_stream  # noqa: E402

OUT = os.path.join(_ROOT, "BENCH_core.json")

BUDGET_FRACTIONS = (0.10, 0.25, 0.50, 1.00)


def run(label: str | None = None, out_path: str = OUT,
        cfg: StreamConfig | None = None):
    cfg = cfg or StreamConfig(n_events=48, n_tenants=3, n_rows=1 << 12,
                              zipf_s=1.1, churn_every=20, seed=0)

    # size the candidate volume with an unbudgeted store-everything run
    keep = run_stream("keep", cfg)
    total_bytes = keep.peak_store_bytes
    emit("policy/keep", keep.total_wall_s,
         f"candidate_bytes={total_bytes}")

    off = run_stream("off", cfg)
    emit("policy/off", off.total_wall_s, "no-reuse baseline")

    budgets = []
    for frac in BUDGET_FRACTIONS:
        budget = int(total_bytes * frac)
        lru = run_stream("lru", cfg, budget_bytes=budget)
        cost = run_stream("cost", cfg, budget_bytes=budget)
        budgets.append({
            "frac": frac,
            "budget_bytes": budget,
            "lru_s": round(lru.total_wall_s, 6),
            "cost_s": round(cost.total_wall_s, 6),
            "lru_reuses": lru.n_reused_total,
            "cost_reuses": cost.n_reused_total,
            "lru_evictions": lru.evictions,
            "cost_evictions": cost.evictions,
            "cost_rejections": cost.rejections,
            "lru_cum_s": [round(x, 6) for x in lru.cum_wall_s],
            "cost_cum_s": [round(x, 6) for x in cost.cum_wall_s],
        })
        emit(f"policy/budget_{int(frac * 100)}pct", cost.total_wall_s,
             f"cost={cost.total_wall_s:.3f}s;lru={lru.total_wall_s:.3f}s;"
             f"off={off.total_wall_s:.3f}s")

    rec = {
        "label": label or "run",
        "n_events": cfg.n_events,
        "n_tenants": cfg.n_tenants,
        "n_rows": cfg.n_rows,
        "churn_every": cfg.churn_every,
        "total_candidate_bytes": total_bytes,
        "off_s": round(off.total_wall_s, 6),
        "off_cum_s": [round(x, 6) for x in off.cum_wall_s],
        "keep_s": round(keep.total_wall_s, 6),
        "budgets": budgets,
    }

    doc = {"runs": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("policy_runs", [])
    doc["policy_runs"] = [r for r in runs if r["label"] != rec["label"]]
    doc["policy_runs"].append(rec)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    emit("policy/done", 0.0, f"out={out_path}")
    return rec


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(label=sys.argv[1] if len(sys.argv) > 1 else None)
