"""Fig 16: QP template — overhead & speedup vs number of projected
fields.  Paper: more data reduction (fewer fields) => lower overhead,
higher speedup; monotone trend.
"""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

from benchmarks.common import emit, measure_query         # noqa: E402
from repro.workloads import pigmix                        # noqa: E402


def run(n_rows: int = 1 << 14):
    results = []
    for nf in range(1, 6):
        m = measure_query(lambda nf=nf: pigmix.QP(nf), n_rows,
                          "aggressive", datasets="synth")
        ov = m["t_store"] / max(m["t_plain"], 1e-9)
        sp = m["t_plain"] / max(m["t_reuse"], 1e-9)
        results.append((nf, ov, sp))
        emit(f"fig16/projection/{nf}_fields", m["t_reuse"],
             f"overhead={ov:.2f};speedup={sp:.2f}")
    # monotonicity claim (allowing measurement noise via trend check)
    sp_first, sp_last = results[0][2], results[-1][2]
    emit("fig16/claims", 0.0,
         f"speedup_1field={sp_first:.2f};speedup_5fields={sp_last:.2f};"
         f"fewer_fields_faster={sp_first >= sp_last}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
