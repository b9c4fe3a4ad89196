"""Traced rehearsals of both cells on the CPU with the readers of the
program's own spans (``program_spans.py`` and their ``metrics/``
modules) added to the cell's per-layer metrics: each reads a value from
the spans that ``repro.obs`` records under the profiler, and the ring
drops none."""
import pytest

import harness
from conftest import small

SPAN_METRICS = {
    "pig_pigmix_4m.serial": [
        ("flush_fetch_share.pig", "%", "store", "query_p95_s"),
        ("flush_compact_share.pig", "%", "store", "query_p95_s"),
        ("flush_encode_share.pig", "%", "store", "query_p95_s"),
        ("flush_write_share.pig", "%", "store", "query_p95_s"),
        ("flush_upload_share.pig", "%", "store", "query_p95_s"),
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


def with_span_metrics(bench: dict, cell: str) -> dict:
    """``bench`` with the span readers of ``cell`` appended to its
    per-layer metrics, in the form of ``BENCHMARK.json``."""
    extra = [{"name": name, "unit": unit, "better": "lower",
              "source": "host_clock", "layer": layer, "moves": moves,
              "workloads": [cell]}
             for name, unit, layer, moves in SPAN_METRICS[cell]]
    return dict(bench, per_layer=bench["per_layer"] + extra)


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_traced_rehearsal_reads_program_spans(no_compile_cache, monkeypatch,
                                              cell):
    from repro import obs
    bench = harness.benchmark()
    before = {m["name"] for m in harness.cell_metrics(bench, cell, True)}
    monkeypatch.setattr(harness, "benchmark",
                        lambda: with_span_metrics(bench, cell))
    out = harness.run_cell(cell, 2**31 + 5, 1.5, True, require_chip=False,
                           config=small(CONFIG[cell]))
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert obs.dropped() == 0
    new = {name for name, *_ in SPAN_METRICS[cell]}
    assert new <= set(out["metrics"])
    assert set(out["metrics"]) - new <= before
    for name in new:
        assert out["metrics"][name]["value"] >= 0
