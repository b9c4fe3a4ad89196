"""Fig 17: QF template — overhead & speedup vs filter selectivity
(field6: 0.5% selected ... field12: 60% selected).  Paper: less selective
filters (more surviving data) => higher overhead, lower speedup.
"""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

from benchmarks.common import emit, measure_query         # noqa: E402
from repro.workloads import pigmix                        # noqa: E402


def run(n_rows: int = 1 << 14):
    results = []
    for field, frac in pigmix.FILTER_FIELDS.items():
        m = measure_query(lambda f=field: pigmix.QF(f), n_rows,
                          "aggressive", datasets="synth")
        ov = m["t_store"] / max(m["t_plain"], 1e-9)
        sp = m["t_plain"] / max(m["t_reuse"], 1e-9)
        results.append((frac, ov, sp))
        emit(f"fig17/filter/{field}_{int(frac * 1000)}permille",
             m["t_reuse"], f"overhead={ov:.2f};speedup={sp:.2f}")
    sp_first, sp_last = results[0][2], results[-1][2]
    emit("fig17/claims", 0.0,
         f"speedup_0.5pct={sp_first:.2f};speedup_60pct={sp_last:.2f};"
         f"more_selective_faster={sp_first >= sp_last}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
