"""Rehearsals of the pig_pigmix_4m.serial cell on the CPU at a small size: the
whole run but the look for a chip, with the configuration passed as
data.  The answers are checked against the reference, and each planted
fault (an altered answer, a skipped durability flush) has to make the
run come out not correct."""
import pytest

import harness
from conftest import small
from faults import altered_answers, deferred_flush

CELL = "pig_pigmix_4m.serial"


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_is_correct(no_compile_cache, capsys, trace):
    out = harness.run_cell(CELL, 2**31 + 3, 1.5, trace,
                           require_chip=False, config=small("pig_pigmix_4m"))
    log = capsys.readouterr().out
    assert out["correct"], (out["checks"], [
        line for line in log.splitlines() if line.startswith(("failed", "set-up "))])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "compile events inside the window: 0 " in log
    want = (["store_write_share.pig", "flush_fetch_share.pig",
             "flush_compact_share.pig", "flush_encode_share.pig",
             "flush_write_share.pig", "engine_sync_share.pig"]
            if trace else ["query_p95_s", "queries_per_s", "setup_s"])
    assert sorted(out["metrics"]) == sorted(want)
    assert list(out)[-1] == "checks"


def test_altered_answers_are_not_correct(no_compile_cache, monkeypatch):
    with altered_answers(monkeypatch):
        out = harness.run_cell(CELL, 5, 1.0, False, require_chip=False,
                               config=small("pig_pigmix_4m"))
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0
    assert out["failed"] > 0


def test_deferred_flush_is_not_correct(no_compile_cache, monkeypatch):
    with deferred_flush(monkeypatch):
        out = harness.run_cell(CELL, 6, 1.0, False, require_chip=False,
                               config=small("pig_pigmix_4m"))
    assert out["correct"] is False
    assert out["checks"]["disk_mismatch"]["value"] > 0
    assert out["checks"]["wrong_answers"]["value"] == 0
