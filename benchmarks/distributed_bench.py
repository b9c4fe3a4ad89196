"""Distributed execution benchmark (DESIGN.md §11), appended to
``BENCH_core.json`` under ``dist_runs``.

Workload: the shuffle-heavy PigMix shape — join(page_views, users) then
group-by user — on a data mesh over every device of a TPU host, or, as a CPU
rehearsal, an 8-way forced-host device mesh.  The users table
scales with the data (n_rows / 8 distinct users) the way PigMix's does.
Inputs live in the store's distributed layout: the partition-aware
engine loads them co-partitioned on the demanded keys (one cached host
pass — M3R-style partition stability), so steady-state mesh runs spend
their time on sharded compute, not on re-exchanging static datasets.
Arms:

  t_single        single device, no reuse (plain)
  t_mesh_plain    8-way mesh, no result reuse: cold sharded execution
                  over co-partitioned input loads
  t_reuse_blind   8-way mesh, WARM, partition-blind: the join artifact
                  is reused but stored monolithic, so the group-by must
                  still exchange every row (and the input loads are
                  exchanged too — the blind engine ignores layout)
  t_reuse_copart  8-way mesh, WARM, partition-aware: the reused join
                  artifact is co-partitioned on the grouping key — the
                  group-by runs shuffle-free per shard

Tracked claims: t_reuse_blind / t_reuse_copart >= 2 at the default
(committed) size (ISSUE 4 — partition-aware reuse skips the exchange,
not just the compute), and t_single / t_mesh_plain >= 1 (ISSUE 7 — the
sharded path must not lose to recompute-on-one-device).

With ``RESTORE_AUTOTUNE=1`` the child runs a tuning pass first
(kernels/autotune.py): exchange skew measured on an exchange-running
configuration, join probe slack on the co-partitioned one, and the
Pallas scatter tile priced through roofline/analysis.py; the persisted
table then feeds every arm via ``autotune.choose``.

On a TPU host the sweep runs in this process, on a mesh over
``jax.devices()``: the chips belong to the process that first touched
JAX.  On a CPU-only host it runs in a SUBPROCESS that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before importing
jax, exactly like tests/test_distributed.py; the parent process (and
anything else in the same interpreter) keeps its 1-device view.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

OUT = os.path.join(_ROOT, "BENCH_core.json")
N_SHARDS = 8
HISTORY_PER_LABEL = 5        # the check_bench regression gate needs
                             # same-label history, so entries append


# ---------------------------------------------------------------------------
# Sweep: in-process on a TPU host, in the 8-device subprocess on CPU


def _sweep(n_rows: int, trials: int, n_shards: int) -> dict:
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    import jax

    from repro.core import plan as P
    from repro.core.restore import ReStore
    from repro.launch.mesh import make_data_mesh
    from repro.store.artifacts import ArtifactStore, Catalog
    from repro.workloads import pigmix

    assert len(jax.devices()) >= n_shards, jax.devices()
    mesh = make_data_mesh(n_shards)

    def probe(aggs):
        pv = P.project(P.load("page_views"), ["user", "estimated_revenue"])
        u = P.project(P.load("users"), ["name"])
        j = P.join(pv, u, ["user"], ["name"])
        g = P.groupby(j, ["user"], aggs)
        return P.PhysicalPlan([P.store(g, "dist_out")])

    A_SEED = {"total": ("sum", "estimated_revenue")}
    A_PROBE = {"total": ("sum", "estimated_revenue"),
               "n": ("count", "estimated_revenue"),
               "mx": ("max", "estimated_revenue")}

    n_users = max(200, n_rows // 8)

    def fresh(**kw):
        store = ArtifactStore(root=tempfile.mkdtemp(prefix="dist_bench_"))
        store.put("page_views",
                  pigmix.gen_page_views(n_rows, n_users=n_users))
        store.put("users", pigmix.gen_users(n_users=n_users))
        return ReStore(Catalog(store), store, measure_exec=True,
                       repeats=3, **kw)

    def close(rs):
        import shutil
        rs.store.close()
        shutil.rmtree(rs.store.root, ignore_errors=True)

    def timed(rs, plan):
        _, rep = rs.run_plan(plan)
        return rep.total_wall_s, rep

    med = lambda xs: sorted(xs)[len(xs) // 2]     # noqa: E731

    def _tune():
        """Tuning pass (only under RESTORE_AUTOTUNE=1): measure the
        probe workload per candidate, reject any candidate that
        overflowed a bucket or probe window (a dropped-row retry is
        never worth a faster wall), persist the winners."""
        from repro.kernels import autotune
        if not autotune.enabled():
            return
        table = autotune.get_table(refresh=True)

        def run_arm(**kw):
            rs = fresh(heuristic="off", rewrite_enabled=False,
                       semantic=False, mesh=mesh, **kw)
            t, rep = timed(rs, probe(A_PROBE))
            bad = any(j.stats.shuffle_overflow or j.stats.join_overflow
                      or j.stats.shuffle_retries
                      for j in rep.jobs if j.stats)
            close(rs)
            return 1e9 if bad else t

        # skew: the per-destination bucket headroom of the exchange —
        # tuned with partition-blind loads so the exchange actually
        # runs.  The candidate is pinned into the live table first:
        # the engine reads the knob through choose(), which shadows
        # any constructor argument once an entry exists.
        def skew_measure(s):
            table.put("exchange", 0, "row", "skew", float(s))
            table.save(autotune.table_path())
            autotune.get_table(refresh=True)
            return run_arm(partition_aware=False)

        best_skew = autotune.tune("exchange", 0, "row", "skew",
                                  [1.25, 2.0, 4.0], skew_measure,
                                  table=table, reps=1)
        skew_measure(best_skew)      # leave the winner in the table
        # probe slack: extra hash-tie window width of the join probe —
        # tuned on the co-partitioned path the arms below run
        def slack_measure(s):
            table.put("join_probe", 0, "uint32", "slack", int(s))
            for b in range(8, 21):
                table.put("join_probe", 1 << b, "uint32", "slack", int(s))
            table.save(autotune.table_path())
            autotune.get_table(refresh=True)
            return run_arm()

        best = autotune.tune("join_probe", 0, "uint32", "slack",
                             [1, 2, 4], slack_measure, table=table, reps=1)
        slack_measure(best)          # leave the winner in the table
        # Pallas scatter tile: priced analytically (roofline) on a chip
        # with published peaks; a CPU host keeps the built-in tile, as
        # it can neither price nor time the real kernel
        from repro.roofline.analysis import PEAKS
        if autotune.device_kind() in PEAKS:
            price = autotune.scatter_tile_price(n_rows, n_shards)
            autotune.tune("partition_scatter", n_rows, "uint32", "tile_n",
                          [1024, 2048, 4096, 8192], price,
                          table=table, price=price, top_k=4, reps=1)
        table.save(autotune.table_path())
        autotune.get_table(refresh=True)

    _tune()
    t_single, t_mesh, t_blind, t_copart = [], [], [], []
    skipped = 0
    for _ in range(trials):
        rs = fresh(heuristic="off", rewrite_enabled=False, semantic=False)
        t_single.append(timed(rs, probe(A_PROBE))[0])
        close(rs)

        rs = fresh(heuristic="off", rewrite_enabled=False, semantic=False,
                   mesh=mesh)
        t_mesh.append(timed(rs, probe(A_PROBE))[0])
        close(rs)

        for aware, bucket in ((False, t_blind), (True, t_copart)):
            rs = fresh(heuristic="aggressive", mesh=mesh,
                       partition_aware=aware)
            rs.run_plan(probe(A_SEED))            # warm: join artifact
            t, rep = timed(rs, probe(A_PROBE))
            bucket.append(t)
            if aware:
                skipped += sum(j.stats.shuffles_skipped
                               for j in rep.jobs if j.stats)
            close(rs)

    rec = {
        "n_rows": n_rows, "n_shards": n_shards, "trials": trials,
        "arms": {"t_single_s": round(med(t_single), 6),
                 "t_mesh_plain_s": round(med(t_mesh), 6),
                 "t_reuse_blind_s": round(med(t_blind), 6),
                 "t_reuse_copart_s": round(med(t_copart), 6)},
        "shuffles_skipped": skipped,
        "speedup_copart_vs_blind": round(
            med(t_blind) / max(med(t_copart), 1e-9), 4),
        "speedup_copart_vs_plain": round(
            med(t_mesh) / max(med(t_copart), 1e-9), 4),
        "mesh_vs_single": round(
            med(t_single) / max(med(t_mesh), 1e-9), 4),
    }
    assert skipped > 0, "partition-aware arm never skipped an exchange"
    return rec


def _forced_host_sweep(n_rows: int, trials: int) -> dict:
    """CPU rehearsal: the sweep in a child with N_SHARDS forced host
    devices (the flag must be set before the child's JAX starts)."""
    child_out = tempfile.mktemp(suffix=".json")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_SHARDS}"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", child_out,
         "--n-rows", str(n_rows), "--trials", str(trials)],
        env=env, capture_output=True, text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(f"distributed bench child failed:\n"
                           f"{proc.stderr[-3000:]}")
    with open(child_out) as f:
        rec = json.load(f)
    os.unlink(child_out)
    return rec


# ---------------------------------------------------------------------------
# Parent


def run(label: str | None = None, n_rows: int = 1 << 16,
        out_path: str = OUT, trials: int = 3):
    from benchmarks.common import emit

    # CI sizes the sweep down via env (the docs job exercises the bench
    # on every push; the committed BENCH_core.json entry uses defaults)
    n_rows = int(os.environ.get("DIST_BENCH_NROWS", n_rows))
    trials = int(os.environ.get("DIST_BENCH_TRIALS", trials))

    import jax
    if jax.default_backend() == "cpu":
        rec = _forced_host_sweep(n_rows, trials)
    else:
        rec = _sweep(n_rows, trials, len(jax.devices()))
    rec["label"] = label or "run"

    a = rec["arms"]
    mesh = f"dist/mesh{rec['n_shards']}"
    emit("dist/single_device", a["t_single_s"], "plain")
    emit(f"{mesh}_plain", a["t_mesh_plain_s"],
         f"vs_single={rec['mesh_vs_single']:.2f}")
    emit(f"{mesh}_reuse_blind", a["t_reuse_blind_s"],
         "warm;monolithic artifact")
    emit(f"{mesh}_reuse_copart", a["t_reuse_copart_s"],
         f"warm;speedup_vs_blind={rec['speedup_copart_vs_blind']:.2f};"
         f"skipped={rec['shuffles_skipped']}")

    doc = {"runs": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("dist_runs", [])
    runs.append(rec)
    # keep bounded same-label history (newest last) for the regression gate
    kept, per_label = [], {}
    for r in reversed(runs):
        per_label[r["label"]] = per_label.get(r["label"], 0) + 1
        if per_label[r["label"]] <= HISTORY_PER_LABEL:
            kept.append(r)
    doc["dist_runs"] = list(reversed(kept))
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    emit("dist/summary", 0.0,
         f"copart_vs_blind={rec['speedup_copart_vs_blind']:.2f};"
         f"out={out_path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None)
    ap.add_argument("--n-rows", type=int, default=1 << 16)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--label", default=None)
    args = ap.parse_args()
    if args.child:
        rec = _sweep(args.n_rows, args.trials, N_SHARDS)
        with open(args.child, "w") as f:
            json.dump(rec, f)
    else:
        run(label=args.label, n_rows=args.n_rows, trials=args.trials)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
