"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  Fig 9      whole-job reuse speedup        (whole_job_reuse)
  Figs 10+12 sub-job reuse speedup, 2 scales (subjob_reuse)
  Fig 11     Store-injection overhead, 2 scales (store_overhead)
  Figs 13+14 + Table 1  NH / H_C / H_A      (heuristics)
  Fig 16     projection data-reduction sweep (projection_sweep)
  Fig 17     filter selectivity sweep        (filter_sweep)
  beyond     budgeted-repository policy sweep (policy_bench)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks import (core_bench, delta_bench, distributed_bench,  # noqa
                        filter_sweep, heuristics, mqo_bench,
                        policy_bench, prefix_reuse_bench,
                        projection_sweep, semantic_reuse_bench,
                        service_bench, store_overhead, subjob_reuse,
                        tier_bench, whole_job_reuse)

SUITES = {
    "core": core_bench.run,
    "policy": policy_bench.run,
    "semantic": semantic_reuse_bench.run,
    "dist": distributed_bench.run,
    "delta": delta_bench.run,
    "service": service_bench.run,
    "tier": tier_bench.run,
    "mqo": mqo_bench.run,
    "prefix": prefix_reuse_bench.run,
    "fig9_whole_job": whole_job_reuse.run,
    "fig10_12_subjob": subjob_reuse.run,
    "fig11_overhead": store_overhead.run,
    "fig13_14_table1_heuristics": heuristics.run,
    "fig16_projection": projection_sweep.run,
    "fig17_filter": filter_sweep.run,
}

# suites that accept a --label (snapshots into BENCH_core.json)
LABELLED = {"core", "policy", "semantic", "dist", "delta", "service",
            "tier", "mqo", "prefix"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(SUITES) + [None])
    ap.add_argument("--label", default=None,
                    help="run label recorded in BENCH_core.json "
                         "(core/policy suites)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        if name in LABELLED:
            fn(label=args.label)
        else:
            fn()
        print(f"# suite {name} finished in {time.time() - t0:.1f}s",
              flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
