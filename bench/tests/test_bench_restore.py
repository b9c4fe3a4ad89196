"""Rehearsals of the restore_pigmix_4m.tenants_zipf cell on the CPU at a small size: the
whole run but the look for a chip, with the configuration passed as
data.  The answers are checked against the reference, and a planted
fault has to make the run come out not correct."""
import pytest

import harness
from conftest import small
from faults import altered_answers

CELL = "restore_pigmix_4m.tenants_zipf"


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_is_correct(no_compile_cache, capsys, trace):
    out = harness.run_cell(CELL, 2**31 + 3, 1.5, trace,
                           require_chip=False, config=small("restore_pigmix_4m"))
    log = capsys.readouterr().out
    assert out["correct"], (out["checks"], [
        line for line in log.splitlines() if line.startswith(("failed", "set-up "))])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "compile events inside the window: 0 " in log
    want = (["driver_ms_p95.restore", "service_wait_ms_p95.restore",
             "device_cache_hit_share.restore", "stored_bytes_ratio",
             "service_queue_ms_p95.restore", "driver_compile_ms_p95.restore",
             "driver_reuse_ms_p95.restore"]
            if trace else
            ["query_p95_ms.restore", "queries_per_s.restore", "setup_s"])
    assert sorted(out["metrics"]) == sorted(want)
    assert list(out)[-1] == "checks"


def test_altered_answers_are_not_correct(no_compile_cache, monkeypatch):
    with altered_answers(monkeypatch):
        out = harness.run_cell(CELL, 5, 1.0, False, require_chip=False,
                               config=small("restore_pigmix_4m"))
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["failed"] > 0
