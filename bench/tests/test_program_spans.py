"""Traced rehearsals of both cells on the CPU with the readers of the
program's own spans (``program_spans.py`` and their ``metrics/``
modules), which ``BENCHMARK.json`` lists among each cell's per-layer
metrics: each reads a value from the spans that ``repro.obs`` records
under the profiler, and the ring drops none."""
import pytest

import harness
from conftest import small

SPAN_METRICS = {
    "pig_pigmix_4m.serial": [
        ("flush_fetch_share.pig", "%", "store", "query_p95_s"),
        ("flush_compact_share.pig", "%", "store", "query_p95_s"),
        ("flush_encode_share.pig", "%", "store", "query_p95_s"),
        ("flush_write_share.pig", "%", "store", "query_p95_s"),
        ("engine_sync_share.pig", "%", "engine", "queries_per_s"),
    ],
    "restore_pigmix_4m.tenants_zipf": [
        ("service_queue_ms_p95.restore", "ms", "service", "queries_per_s.restore"),
        ("driver_compile_ms_p95.restore", "ms", "driver", "query_p95_ms.restore"),
        ("driver_reuse_ms_p95.restore", "ms", "driver", "query_p95_ms.restore"),
    ],
}
CONFIG = {"pig_pigmix_4m.serial": "pig_pigmix_4m",
          "restore_pigmix_4m.tenants_zipf": "restore_pigmix_4m"}


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_traced_rehearsal_reads_program_spans(no_compile_cache, cell):
    from repro import obs
    bench = harness.benchmark()
    listed = {m["name"]: m for m in harness.cell_metrics(bench, cell, True)}
    for name, unit, layer, moves in SPAN_METRICS[cell]:
        assert listed[name] == {"name": name, "unit": unit, "better": "lower",
                                "source": "program_span", "layer": layer,
                                "moves": moves, "workloads": [cell]}
    out = harness.run_cell(cell, 2**31 + 5, 1.5, True, require_chip=False,
                           config=small(CONFIG[cell]))
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert obs.dropped() == 0
    new = {name for name, *_ in SPAN_METRICS[cell]}
    assert new <= set(out["metrics"])
    assert set(out["metrics"]) <= set(listed)
    for name in new:
        assert out["metrics"][name]["value"] >= 0
