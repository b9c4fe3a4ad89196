"""Figs 10 + 12: reusing SUB-JOB outputs (aggressive heuristic), at two
data scales.  Paper: average speedup 3.0x @15GB, 24.4x @150GB — speedup
grows with scale because T_load dominates Eq. 2.
"""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

from benchmarks.common import emit, measure_query         # noqa: E402
from repro.workloads import pigmix                        # noqa: E402

QUERIES = ["L2", "L3", "L4", "L5", "L6", "L7", "L8", "L11"]


def run(n_small: int = 1 << 13, n_large: int = 1 << 15):
    for scale, n_rows in (("small", n_small), ("large", n_large)):
        speedups = []
        for q in QUERIES:
            m = measure_query(pigmix.QUERIES[q], n_rows, "aggressive")
            sp = m["t_plain"] / max(m["t_reuse"], 1e-9)
            speedups.append(sp)
            emit(f"fig10_12/subjob/{scale}/{q}", m["t_reuse"],
                 f"speedup={sp:.2f}")
        avg = sum(speedups) / len(speedups)
        emit(f"fig10_12/subjob/{scale}/average", 0.0,
             f"avg_speedup={avg:.2f};paper=3.0x_small_24.4x_large")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
