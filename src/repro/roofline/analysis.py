"""Three-term roofline analysis from the dry-run's compiled artifacts.

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = collective_bytes_per_chip / link_bw

HLO numbers come from ``compiled.cost_analysis()`` with the loop-aware
depth extrapolation (launch/dryrun.py); collective bytes are parsed from
the post-SPMD HLO text (shapes there are already per-shard, so dividing
by the chip count again would double-count).

Hardware constants come from :data:`PEAKS`, one table keyed by
``jax.Device.device_kind``; a kind that is not in it is an error, never
a default.  The dry-run models v5e pods (:data:`DRYRUN_KIND`).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

# Published per-chip peaks by device kind.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
# HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}
DRYRUN_KIND = "TPU v5 lite"

N_CHIPS = {"16x16": 256, "2x16x16": 512}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; KeyError if unknown."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def predict_tile_time_s(kind: str, bytes_accessed: float,
                        flops: float = 0.0,
                        collective_bytes: float = 0.0,
                        dispatch_overhead_s: float = 0.0) -> float:
    """Price one candidate kernel/exchange configuration on a chip of
    ``kind`` by the same three-term roofline that scores whole dry-run
    cells: the dominant of compute, HBM, and ICI time, plus a
    caller-modeled fixed dispatch cost (per-tile grid overhead,
    collective launch).  Consumed by ``kernels/autotune.py`` to prune a
    candidate grid down to the few configurations worth measuring."""
    p = peaks(kind)
    return max(flops / p["flops"], bytes_accessed / p["hbm_bw"],
               collective_bytes / p["ici_link_bw"]) + dispatch_overhead_s


def model_flops(report: dict) -> float:
    """6*N*D (train) / 2*N*D (fwd-only), N = active params, D = tokens."""
    n = report["active_params"]
    kind = report["kind"]
    if kind == "train":
        tokens = report["seq"] * report["global_batch"]
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = report["seq"] * report["global_batch"]
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * report["global_batch"]


def analyze_cell(report: dict) -> Optional[dict]:
    if report.get("status") != "ok":
        return None
    chips = N_CHIPS[report["mesh"]]
    ce = report.get("cost_extrapolated")
    if not ce:
        return None
    flops_dev = max(ce["flops"], 0.0)
    bytes_dev = max(ce["bytes"], 0.0)
    # depth-extrapolation noise can drive tiny cells negative — clamp
    coll_dev = max(sum(ce["collective_bytes"].values()), 0.0)

    p = peaks(DRYRUN_KIND)
    t_compute = flops_dev / p["flops"]
    t_memory = bytes_dev / p["hbm_bw"]
    t_coll = coll_dev / p["ici_link_bw"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)

    mf = model_flops(report)
    hlo_global = flops_dev * chips
    useful = mf / hlo_global if hlo_global else float("nan")
    # roofline fraction: useful work vs what the dominant term costs
    t_ideal = (mf / chips) / p["flops"]
    frac = t_ideal / max(terms[dominant], 1e-30)

    return {
        "arch": report["arch"], "shape": report["shape"],
        "mesh": report["mesh"], "kind": report["kind"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_ratio": useful, "roofline_fraction": frac,
        "collective_breakdown": ce["collective_bytes"],
        "memory_per_device": report.get("memory", {}),
    }


_SUGGESTIONS = {
    "compute": ("compute-bound: raise MXU utilization — fuse the "
                "attention softmax (Pallas flash kernel), drop remat "
                "recompute on cheap ops, verify no replicated einsum."),
    "memory": ("memory-bound: cut HBM traffic — fuse elementwise chains "
               "into the matmuls, keep activations bf16, shard the "
               "largest resident tensor further."),
    "collective": ("collective-bound: overlap or shrink comms — "
                   "reduce-scatter instead of all-reduce+slice, "
                   "sequence-shard the KV cache, async collectives "
                   "overlapped with compute."),
}


def suggestion(row: dict) -> str:
    base = _SUGGESTIONS[row["dominant"]]
    if row["useful_ratio"] < 0.4 and row["dominant"] == "compute":
        base += (" useful/HLO flops is low (remat or redundant "
                 "recompute dominates) — revisit checkpoint policy.")
    return base


def load_reports(dryrun_dir: str) -> List[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rep = json.load(f)
        rep["_optimized"] = path.endswith("_opt.json")
        out.append(rep)
    return out


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.2f}ms"
    return f"{x * 1e6:.1f}us"


def to_markdown(rows: List[dict], skipped: List[dict]) -> str:
    lines = [
        "| arch | shape | compute | memory | collective | dominant | "
        "MODEL_FLOPS | useful/HLO | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(r['t_compute_s'])} | "
            f"{fmt_s(r['t_memory_s'])} | {fmt_s(r['t_collective_s'])} | "
            f"**{r['dominant']}** | {r['model_flops']:.3g} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} |")
    lines.append("")
    lines.append("Per-cell bottleneck notes:")
    for r in rows:
        lines.append(f"* `{r['arch']} x {r['shape']}`: {suggestion(r)}")
    if skipped:
        lines.append("")
        lines.append("Skipped cells (assignment rules):")
        for s in skipped:
            lines.append(f"* `{s['arch']} x {s['shape']}` ({s['mesh']}): "
                         f"{s.get('reason', '')}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline.md")
    ap.add_argument("--json-out", default="experiments/roofline.json")
    args = ap.parse_args()

    reports = load_reports(args.dryrun_dir)
    rows, rows_opt, skipped = [], [], []
    for rep in reports:
        if rep.get("mesh") != "16x16":   # roofline table: single-pod only
            continue
        if rep.get("status") == "skipped":
            if not rep["_optimized"]:
                skipped.append(rep)
            continue
        row = analyze_cell(rep)
        if row:
            (rows_opt if rep["_optimized"] else rows).append(row)
    key = lambda r: (r["arch"], r["shape"])
    rows.sort(key=key)
    rows_opt.sort(key=key)

    with open(args.json_out, "w") as f:
        json.dump({"baseline": rows, "optimized": rows_opt}, f, indent=1)
    md = ["## Baseline (paper-faithful first implementation)", "",
          to_markdown(rows, skipped)]
    if rows_opt:
        md += ["", "## Optimized (beyond-baseline, §Perf changes)", "",
               to_markdown(rows_opt, [])]
        # per-cell dominant-term improvement summary
        base_by = {key(r): r for r in rows}
        md += ["", "Dominant-term improvement (baseline -> optimized):"]
        for r in rows_opt:
            b = base_by.get(key(r))
            if not b:
                continue
            bd = max(b["t_compute_s"], b["t_memory_s"],
                     b["t_collective_s"])
            od = max(r["t_compute_s"], r["t_memory_s"],
                     r["t_collective_s"])
            md.append(f"* `{r['arch']} x {r['shape']}`: "
                      f"{fmt_s(bd)} -> {fmt_s(od)}  "
                      f"({bd / max(od, 1e-30):.1f}x)")
    text = "\n".join(md)
    with open(args.out, "w") as f:
        f.write(text)
    print(text)


if __name__ == "__main__":
    main()
