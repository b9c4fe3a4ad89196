"""Autotuner unit tests (DESIGN.md §14): persistence round-trip,
deterministic selection under a stubbed measurement, graceful fallback
on missing/corrupt tables, and the inert-by-default runtime hook."""
import json

import pytest

from repro.kernels import autotune
from repro.kernels.autotune import TuningTable, rows_bucket


@pytest.fixture
def tmp_table(tmp_path, monkeypatch):
    """Point the module at a throwaway table file + clear its cache."""
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv(autotune.DEFAULT_TABLE_ENV, path)
    autotune._cache.clear()
    yield path
    autotune._cache.clear()


def test_rows_bucket_size_classes():
    assert rows_bucket(0) == 0
    assert rows_bucket(1) == 1
    assert rows_bucket(65535) == rows_bucket(65536) == 65536
    assert rows_bucket(65537) == 131072


def test_persist_round_trip(tmp_table):
    t = TuningTable()
    t.put("exchange", 0, "row", "skew", 1.25)
    t.put("partition_scatter", 60000, "uint32", "tile_n", 2048)
    t.save(tmp_table)
    back = TuningTable.load(tmp_table)
    assert back.entries == t.entries
    # same size class, different row count: one entry covers both
    assert back.get("partition_scatter", 65536, "uint32", "tile_n") == 2048
    assert back.get("partition_scatter", 70000, "uint32", "tile_n") is None


def test_load_missing_or_corrupt_is_empty(tmp_path):
    assert TuningTable.load(str(tmp_path / "nope.json")).entries == {}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert TuningTable.load(str(bad)).entries == {}
    # valid JSON, wrong shape: non-dict root and non-dict values dropped
    lst = tmp_path / "list.json"
    lst.write_text("[1, 2]")
    assert TuningTable.load(str(lst)).entries == {}
    mixed = tmp_path / "mixed.json"
    mixed.write_text(json.dumps({"a|0|row": {"skew": 2.0}, "b|0|row": 7}))
    assert TuningTable.load(str(mixed)).entries == {"a|0|row": {"skew": 2.0}}


def test_tune_deterministic_and_tie_break():
    calls = []

    def measure(c):
        calls.append(c)
        return {256: 3.0, 512: 1.0, 1024: 1.0}[c]

    t = TuningTable()
    best = autotune.tune("op", 100, "uint32", "tile_n", [256, 512, 1024],
                         measure, table=t, reps=3)
    assert best == 512, "ties break toward the earlier candidate"
    assert t.get("op", 100, "uint32", "tile_n") == 512
    assert len(calls) == 9, "reps measurements per candidate"


def test_tune_price_prunes_before_measuring():
    measured = []

    def measure(c):
        measured.append(c)
        return float(c)

    best = autotune.tune("op", 0, "d", "p", [4, 3, 2, 1], measure,
                         price=lambda c: float(c), top_k=2, reps=1)
    assert best == 1
    assert sorted(measured) == [1, 2], "only the top_k cheapest are timed"


def test_tune_empty_candidates_raises():
    with pytest.raises(ValueError):
        autotune.tune("op", 0, "d", "p", [], lambda c: 0.0)


def test_choose_inert_unless_enabled(tmp_table, monkeypatch):
    t = TuningTable()
    t.put("exchange", 0, "row", "skew", 1.25)
    t.save(tmp_table)
    monkeypatch.delenv(autotune.ENABLE_ENV, raising=False)
    assert autotune.choose("exchange", 0, "row", "skew", 4.0) == 4.0
    monkeypatch.setenv(autotune.ENABLE_ENV, "0")
    assert autotune.choose("exchange", 0, "row", "skew", 4.0) == 4.0
    monkeypatch.setenv(autotune.ENABLE_ENV, "1")
    assert autotune.choose("exchange", 0, "row", "skew", 4.0) == 1.25


def test_choose_missing_entry_falls_back(tmp_table, monkeypatch):
    monkeypatch.setenv(autotune.ENABLE_ENV, "1")
    # no table file at all: defaults survive
    assert autotune.choose("exchange", 0, "row", "skew", 4.0) == 4.0
    assert autotune.choose("join_probe", 4096, "uint32", "slack", 4) == 4


def test_choose_coerces_to_default_type(tmp_table, monkeypatch):
    monkeypatch.setenv(autotune.ENABLE_ENV, "1")
    t = TuningTable()
    t.put("partition_scatter", 100, "uint32", "tile_n", 512.9)
    t.put("exchange", 0, "row", "skew", "junk")
    t.save(tmp_table)
    autotune.get_table(refresh=True)
    v = autotune.choose("partition_scatter", 100, "uint32", "tile_n", 256)
    assert v == 512 and isinstance(v, int)
    # uncoercible value: the default survives a hand-edited table
    assert autotune.choose("exchange", 0, "row", "skew", 4.0) == 4.0


def test_scatter_tile_price_monotone_dispatch_tradeoff():
    """The roofline price must penalise tiny tiles (dispatch-bound) and
    keep the working-set term finite — a sanity pin for the consumer in
    roofline/analysis.py, not a performance claim."""
    price = autotune.scatter_tile_price(1 << 16, 8, kind="TPU v5 lite")
    costs = {t: price(t) for t in (64, 256, 1024, 4096)}
    assert all(c > 0 for c in costs.values())
    assert costs[64] > costs[4096], "dispatch overhead dominates tiny tiles"


def test_table_keys_by_device_kind():
    """A table tuned on one backend never steers another's knobs."""
    t = TuningTable()
    t.put("partition_scatter", 4096, "uint32", "tile_n", 2048, kind="cpu")
    assert t.get("partition_scatter", 4096, "uint32", "tile_n",
                 kind="cpu") == 2048
    assert t.get("partition_scatter", 4096, "uint32", "tile_n",
                 default=1024, kind="TPU v5 lite") == 1024


def test_price_of_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        autotune.scatter_tile_price(1 << 16, 8, kind="cpu")(1024)
