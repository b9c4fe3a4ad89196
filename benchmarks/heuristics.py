"""Figs 13 + 14 + Table 1: No-Heuristic vs Conservative vs Aggressive.

Per query and heuristic: execution time with Store injection (Fig 14),
execution time when reusing the stored sub-jobs (Fig 13), and stored
bytes (Table 1).  Paper's findings to validate: H_A reuse ~= NH reuse;
H_C stores least and benefits least; NH stores far more bytes for no
extra benefit.
"""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

from benchmarks.common import emit, measure_query         # noqa: E402
from repro.workloads import pigmix                        # noqa: E402

QUERIES = ["L2", "L3", "L3F", "L4", "L5", "L6", "L7", "L8", "L11"]
HEURISTICS = ["none", "conservative", "aggressive"]   # none == paper's NH


def run(n_rows: int = 1 << 14):
    table1 = {}
    for q in QUERIES:
        row = {}
        for h in HEURISTICS:
            m = measure_query(pigmix.QUERIES[q], n_rows, h)
            tag = {"none": "NH", "conservative": "HC",
                   "aggressive": "HA"}[h]
            emit(f"fig14/store_time/{q}/{tag}", m["t_store"],
                 f"overhead={m['t_store'] / max(m['t_plain'], 1e-9):.2f}")
            emit(f"fig13/reuse_time/{q}/{tag}", m["t_reuse"],
                 f"speedup={m['t_plain'] / max(m['t_reuse'], 1e-9):.2f}")
            row[tag] = m["stored_bytes"]
        table1[q] = row
        emit(f"table1/stored_bytes/{q}", 0.0,
             f"HC={row['HC']};HA={row['HA']};NH={row['NH']}")
    # the paper's claims as checkable aggregates
    ha_le_nh = all(r["HA"] <= r["NH"] for r in table1.values())
    hc_le_ha = all(r["HC"] <= r["HA"] for r in table1.values())
    emit("table1/claims", 0.0,
         f"HA_bytes<=NH_bytes={ha_le_nh};HC_bytes<=HA_bytes={hc_le_ha}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
