"""Delta-refresh benchmark: refresh vs delete-and-recompute across
append fractions, appended to ``BENCH_core.json`` (DESIGN.md §12).

Two templates over PigMix-shaped data (integer-valued aggregation
columns, so float32 merges are exact and bit-identity is checkable):

  groupby — per-user sum+count of timespent (decomposable aggregates,
            merged by shard-/key-local re-aggregation);
  join    — page_views projection ⋈ power_users (delta join, merged by
            append).

Protocol per (template, append fraction):

  1. cold run through ReStore (whole-job output stored + registered);
  2. ``Catalog.append`` of fraction × n_rows fresh page_views rows;
  3. refresh arm — ``ReStore.maintain(mode="refresh")``: the delta job
     plus the merge, timed;
  4. recompute arm — identical setup, stale entries R4-deleted
     (``evict_stale``), the query re-run cold at the new size, timed;
  5. bit-identity — both arms' final outputs must be identical
     (canonically sorted rows), and the refreshed repository must
     answer the new-version query with zero executed jobs.

Each protocol runs ``trials`` times (fresh stores; the process-wide jit
cache is warm after the first trial, so medians compare execution, not
tracing) and the per-arm median is recorded.  The committed full-size
entry is gated by ``tools/check_bench.py``: at ≤10% append fraction,
refresh must beat recompute by ≥3x for both templates.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import numpy as np                                        # noqa: E402

from benchmarks.common import emit                        # noqa: E402
from repro.core import plan as P                          # noqa: E402
from repro.core.plan import rebind_load_versions          # noqa: E402
from repro.core.repository import Repository              # noqa: E402
from repro.core.restore import ReStore                    # noqa: E402
from repro.store.artifacts import ArtifactStore, Catalog  # noqa: E402
from repro.workloads import pigmix                        # noqa: E402

OUT = os.path.join(_ROOT, "BENCH_core.json")

FRACTIONS = (0.01, 0.05, 0.10, 0.25, 0.50)


def t_groupby() -> P.PhysicalPlan:
    # L6-shaped: wide (string) key pair, all four decomposable
    # aggregates — the expensive recurring aggregate the paper reuses
    pv = P.project(P.load("page_views"),
                   ["user", "query_term", "timespent"])
    g = P.groupby(pv, ["user", "query_term"],
                  {"total": ("sum", "timespent"),
                   "n": ("count", "timespent"),
                   "mn": ("min", "timespent"),
                   "mx": ("max", "timespent")})
    return P.PhysicalPlan([P.store(g, "delta_groupby_out")])


def t_join() -> P.PhysicalPlan:
    pv = P.project(P.load("page_views"), ["user", "timespent"])
    pu = P.project(P.load("power_users"), ["name"])
    j = P.join(pv, pu, ["user"], ["name"])
    return P.PhysicalPlan([P.store(j, "delta_join_out")])


TEMPLATES = {"groupby": t_groupby, "join": t_join}


def _sortable(a: np.ndarray) -> np.ndarray:
    """1-D lexsort key: fixed-width byte-string columns collapse to
    bytes scalars."""
    if a.ndim == 2:
        return np.ascontiguousarray(a).view(f"S{a.shape[1]}").ravel()
    return a


def _canon(table):
    d = table.to_numpy()
    order = np.lexsort(tuple(_sortable(d[c])
                             for c in sorted(d, reverse=True)))
    return {c: d[c][order] for c in sorted(d)}


def _identical(a, b) -> bool:
    ca, cb = _canon(a), _canon(b)
    if sorted(ca) != sorted(cb):
        return False
    return all(np.array_equal(ca[c], cb[c]) for c in ca)


def _setup(build, n_rows: int, seed: int) -> ReStore:
    store = ArtifactStore(cache_bytes=256 * 1024 * 1024)
    cat = Catalog(store)
    pigmix.register_all(cat, n_rows=n_rows, seed=seed)
    rs = ReStore(cat, store, Repository(), heuristic="off")
    rs.run_plan(build())                       # cold run: artifact stored
    return rs


def _one_point(build, n_rows: int, frac: int, seed: int):
    """(t_refresh, t_recompute, identical, refreshed_count) for one
    template at one append fraction."""
    n_delta = max(int(n_rows * frac), 1)
    delta = pigmix.gen_page_views(n_delta, seed=seed + 9000)

    # refresh arm
    rs = _setup(build, n_rows, seed)
    rs.catalog.append("page_views", delta)
    t0 = time.perf_counter()
    rep = rs.maintain(mode="refresh")
    t_refresh = time.perf_counter() - t0
    plan2 = rebind_load_versions(
        build(), {ds: rs.catalog.version(ds) for ds in
                  ("page_views", "users", "power_users")})
    out_r, run_rep = rs.run_plan(plan2)
    assert run_rep.n_executed == 0, \
        "refreshed repository must answer the new-version query exactly"

    # recompute arm (the pre-§12 behavior: R4 delete, run cold)
    rs2 = _setup(build, n_rows, seed)
    rs2.catalog.append("page_views", delta)
    rs2.repo.evict_stale(rs2.catalog)
    t0 = time.perf_counter()
    out_c, _ = rs2.run_plan(plan2)
    t_recompute = time.perf_counter() - t0

    key = list(out_r)[0]
    ident = _identical(out_r[key], out_c[key])
    n_ref = rep["refreshed"]
    # free both arms' stores (hundreds of MB of device tables) NOW:
    # deferred GC of prior points otherwise stalls later timed windows
    rs.store.close()
    rs2.store.close()
    del rs, rs2, out_r, out_c
    gc.collect()
    return t_refresh, t_recompute, ident, n_ref


def run(label: str | None = None, n_rows: int = 1 << 19,
        out_path: str = OUT, trials: int = 3):
    n_rows = int(os.environ.get("DELTA_BENCH_NROWS", n_rows))
    trials = int(os.environ.get("DELTA_BENCH_TRIALS", trials))
    sweep = []
    for tname, build in TEMPLATES.items():
        for frac in FRACTIONS:
            # warmup pass (discarded): every plain/delta/merge shape of
            # this point compiles here, so the timed trials below
            # compare execution, not tracing — the same convention as
            # every other benchmark in this repo
            _one_point(build, n_rows, frac, seed=0)
            rs_t, rc_t, idents, refreshed = [], [], [], 0
            for trial in range(trials):
                tr, tc, ident, n_ref = _one_point(build, n_rows, frac,
                                                  seed=trial)
                rs_t.append(tr)
                rc_t.append(tc)
                idents.append(ident)
                refreshed += n_ref
            t_refresh = sorted(rs_t)[len(rs_t) // 2]
            t_recompute = sorted(rc_t)[len(rc_t) // 2]
            assert refreshed >= trials, \
                f"{tname}@{frac}: refresh path not exercised"
            pt = {"template": tname, "frac": frac,
                  "t_refresh_s": round(t_refresh, 6),
                  "t_recompute_s": round(t_recompute, 6),
                  "speedup": round(t_recompute / max(t_refresh, 1e-9), 4),
                  "identical": all(idents)}
            sweep.append(pt)
            emit(f"delta/{tname}_{int(frac * 100)}pct", t_refresh,
                 f"recompute={t_recompute:.4f}s;"
                 f"speedup={pt['speedup']:.2f};identical={pt['identical']}")

    rec = {"label": label or "run", "n_rows": n_rows, "trials": trials,
           "sweep": sweep}
    doc = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("delta_runs", [])
    # keep the last 2 prior same-label entries: check_bench's
    # regression gate compares CONSECUTIVE same-label entries, so the
    # nightly workflow (which restores the previous snapshot from the
    # actions cache) gets a real predecessor to gate against
    same = [r for r in runs if r["label"] == rec["label"]][-2:]
    doc["delta_runs"] = [r for r in runs
                         if r["label"] != rec["label"]] + same + [rec]
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    emit("delta/done", 0.0, f"out={out_path}")
    return rec


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(label=sys.argv[1] if len(sys.argv) > 1 else None)
