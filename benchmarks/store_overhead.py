"""Fig 11: overhead of injecting Store operators (aggressive heuristic),
at two data scales.  Paper: 2.4x @15GB vs 1.6x @150GB — RELATIVE overhead
shrinks as the data (and so T_load/T_sort) grows.
"""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

from benchmarks.common import emit, measure_query         # noqa: E402
from repro.workloads import pigmix                        # noqa: E402

QUERIES = ["L2", "L3", "L4", "L5", "L6", "L7", "L8", "L11"]


def run(n_small: int = 1 << 13, n_large: int = 1 << 15):
    for scale, n_rows in (("small", n_small), ("large", n_large)):
        overheads = []
        for q in QUERIES:
            m = measure_query(pigmix.QUERIES[q], n_rows, "aggressive")
            ov = m["t_store"] / max(m["t_plain"], 1e-9)
            overheads.append(ov)
            emit(f"fig11/overhead/{scale}/{q}", m["t_store"],
                 f"overhead={ov:.2f}")
        avg = sum(overheads) / len(overheads)
        emit(f"fig11/overhead/{scale}/average", 0.0,
             f"avg_overhead={avg:.2f};paper=2.4x_small_1.6x_large")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
