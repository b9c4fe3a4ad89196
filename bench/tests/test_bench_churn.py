"""Base-table churn in a traffic mix, on the CPU at small sizes: the data
sets a churn cycles through, the mix key and its validation, the
loader's clock, the check of each answer against the data of the
versions it could have read, the durability hold of each answer, and a
rehearsal of a churn mix through ``ReStoreService``.

No mix file of the benchmark churns yet: the reload cadence of a cell
needs a published source.  ``CHURN`` is the churn these tests use."""
import hashlib
import json
import re
import types

import numpy as np
import pytest

import datagen
import harness
import loadgen
import queries
import reference
from conftest import small

# the templates whose answer no per-row value moves at 2^18 rows: every
# user has rows of both actions, and L11 is the set of users
UNCHANGED = ("L4", "L11")
# the sha256 of every column of datagen.tables() at SMALL_ROWS, as the
# generator was before data sets were added
TABLES_SHA256 = {
    3: "ee88aff5c16a4a0e3e1e4102f8fcadaa43e75638300a6758f75ef50ffe94760a",
    2**31 + 9: "4c40cace200379cb1852483ea552203cf99088256792cc6175c01e28148537db",
}
CHURN = {"table": "page_views", "every_s": 5, "data_sets": 3}


def churn_mix(**churn) -> dict:
    """The tenants_zipf mix with ``CHURN`` (updated by ``churn``)."""
    mix = loadgen.load_mix(harness.mix_path("tenants_zipf"))
    mix["churn"] = dict(CHURN, **churn)
    return mix


def _digest(tables: dict) -> str:
    h = hashlib.sha256()
    for t in sorted(tables):
        for c in sorted(tables[t]):
            a = np.ascontiguousarray(tables[t][c])
            h.update(f"{t}.{c}{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(TABLES_SHA256))
def test_data_set_0_is_the_tables_of_today(seed):
    config = small("restore_pigmix_4m")
    tables = datagen.tables(config, seed)
    assert _digest(tables) == TABLES_SHA256[seed]
    pv = datagen.page_views(config, seed, 0)
    assert all(np.array_equal(pv[c], tables["page_views"][c]) for c in pv)


def _counts(pv: dict, *cols) -> list:
    rows = np.concatenate([pv[c].reshape(len(pv[c]), -1).view(np.uint8)
                           for c in cols], axis=1)
    return sorted(np.unique(rows, axis=0, return_counts=True)[1])


def test_data_sets_keep_users_and_move_every_other_answer():
    config = dict(small("restore_pigmix_4m"), page_views_rows=1 << 18)
    seed = 2**31 + 13
    host = datagen.tables(config, seed)
    sets = [datagen.page_views(config, seed, d) for d in range(3)]
    users = {bytes(r) for r in host["users"]["name"]}
    answers = []
    for pv in sets:
        assert {c: (a.shape, a.dtype) for c, a in pv.items()} == {
            c: (a.shape, a.dtype) for c, a in host["page_views"].items()}
        assert {bytes(r) for r in pv["user"]} == users
        ref = reference.Reference(dict(host, page_views=pv))
        answers.append({t: ref.answer(t) for t in queries.TEMPLATES})
    for a, b in zip(sets, sets[1:]):
        # rows per user and per (user, term): group and join sizes
        assert _counts(a, "user") == _counts(b, "user")
        assert _counts(a, "user", "query_term") == \
            _counts(b, "user", "query_term")
        for c in ("action", "timespent", "timestamp", "estimated_revenue",
                  "ip_addr", "page_info", "page_links", "user"):
            assert not np.array_equal(a[c], b[c]), c
    for x, y in zip(answers, answers[1:]):
        for t in queries.TEMPLATES:
            why, err = reference.compare(x[t], y[t],
                                         queries.FLOAT_COLS.get(t, ()))
            same = why is None and err == 0.0
            assert same == (t in UNCHANGED), t


def _churn(first: int = 0, data_sets: int = 3):
    catalog = types.SimpleNamespace(version=lambda name: first)
    return harness.Churn(dict(CHURN, data_sets=data_sets),
                         types.SimpleNamespace(catalog=catalog),
                         [None] * data_sets)


def test_versions_map_to_the_data_sets_an_answer_could_read():
    c = _churn(first=4)
    assert [c.data_set(v) for v in range(4, 10)] == [0, 1, 2, 0, 1, 2]
    assert c.data_sets(4, 4) == ((0,), ())
    assert c.data_sets(5, 5) == ((1,), (0,))
    assert c.data_sets(5, 6) == ((1, 2), (0,))
    assert c.data_sets(9, 9) == ((2,), (0, 1))
    assert c.data_sets(8, 11) == ((0, 1, 2), ())


PLANTED = ["L2", "L3_sum", "L5", "L6", "L8", "busy_users"]


@pytest.fixture(scope="module")
def two_versions():
    config = small("restore_pigmix_4m")
    host = datagen.tables(config, 17)
    refs = [reference.Reference(dict(host, page_views=datagen.page_views(
        config, 17, d))) for d in range(2)]
    return config, refs


@pytest.mark.parametrize("case, data_set, versions, wrong, stale", [
    ("fresh", 1, (1, 1), 0, 0),
    ("stale: computed on v-1, submitted after v", 0, (1, 1), 0, 1),
    ("straddles a churn, on the older", 0, (0, 1), 0, 0),
    ("straddles a churn, on the newer", 1, (0, 1), 0, 0),
    ("altered", None, (1, 1), 1, 0),
])
def test_planted_answers(two_versions, case, data_set, versions, wrong,
                         stale):
    config, refs = two_versions
    c = _churn()
    answers = {}
    for t in PLANTED:
        if data_set is None:
            cols = dict(refs[1].answer(t))
            first = sorted(cols)[0]
            cols[first] = cols[first].copy()
            cols[first][0] += 1
        else:
            cols = refs[data_set].answer(t)
        answers[(t, *c.data_sets(*versions))] = [(cols, 2)]
    checks, bad, stale_at = harness.check_versioned(
        answers, lambda d: refs[d], config["limits"], churn=True)
    assert list(checks) == ["wrong_answers", "stale_answers",
                            "float_rel_err"]
    assert checks["wrong_answers"]["value"] == wrong * len(PLANTED)
    assert checks["stale_answers"]["value"] == stale * len(PLANTED)
    assert bad == 3 * (wrong + stale) * len(PLANTED)
    assert stale_at == ({(k, 0) for k in answers} if stale else set())
    if not wrong:
        assert checks["float_rel_err"]["value"] <= \
            checks["float_rel_err"]["limit"]


def test_check_without_churn_reports_no_stale_answers(two_versions):
    config, refs = two_versions
    answers = {t: [(refs[0].answer(t), 0)] for t in PLANTED}
    checks, bad = harness.check_answers(answers, None, config["limits"],
                                        reference=refs[0])
    assert list(checks) == ["wrong_answers", "float_rel_err"] and bad == 0
    stale = {t: [(refs[1].answer(t), 0)] for t in PLANTED}
    checks, bad = harness.check_answers(stale, None, config["limits"],
                                        reference=refs[0])
    assert checks["wrong_answers"]["value"] == len(PLANTED)


def test_held_names_follow_the_versions_read():
    client = harness.Client(types.SimpleNamespace(
        store=types.SimpleNamespace(aliases={})), None, None, False)
    client.churn = _churn()
    names = client.held_names("L3_sum", (2, 3))
    assert names == [(client.output_of("L3_sum", 3), True),
                     (client.output_of("L3_sum", 2), True),
                     (client.output_of("L3_sum"), False)]
    assert len({n for n, _ in names}) == 3
    # the plan as submitted loads version 0: in range where 0 is
    assert client.output_of("L3_sum", 0) == client.output_of("L3_sum")
    assert client.held_names("L3_sum", (0, 1)) == [
        (client.output_of("L3_sum", 1), True),
        (client.output_of("L3_sum"), True)]
    client.churn = None
    assert client.held_names("L3_sum", (0, 0)) == [
        (client.output_of("L3_sum"), True)]


def test_a_fresh_answer_whose_version_never_reached_disk_is_caught(
        tmp_path):
    """Version 3 is data set 0 again, so its answer equals the output of
    version 0, which set-up left on disk; its own output never reached
    disk.  Judged fresh, it fails the durability check; judged stale, the
    as-submitted output may hold it."""
    from repro.dataflow.table import Table
    from repro.store.artifacts import ArtifactStore
    root, held = tmp_path / "store", tmp_path / "held"
    root.mkdir()
    held.mkdir()
    store = ArtifactStore(root=str(root), cache_bytes=0, write_behind=False)
    try:
        client = harness.Client(types.SimpleNamespace(store=store), None,
                                None, False)
        client.churn = _churn()
        answer = Table.from_numpy({"x": np.arange(8, dtype=np.int32)})
        store.put(client.output_of("L3_sum"), answer)
        sampler = harness.Sampler(1, 4, str(root), str(held))
        keys = {}
        for versions in [(0, 0), (3, 3)]:
            keys[versions] = client.answer_key("L3_sum", versions)
            sampler.offer(keys[versions], answer,
                          lambda v=versions: client.held_names("L3_sum", v))
        assert keys[3, 3] == ("L3_sum", (0,), (1, 2))
        assert harness._durable_mismatch(sampler) == 1
        assert harness._durable_mismatch(sampler, {(keys[3, 3], 0)}) == 0
        store.put(client.output_of("L3_sum", 3), answer)
        sampler.offer(keys[3, 3], Table.from_numpy(answer.to_numpy()),
                      lambda: client.held_names("L3_sum", (3, 3)))
        assert harness._durable_mismatch(sampler) == 1
        assert len(sampler.kept[keys[3, 3]]) == 2
    finally:
        store.close()


def test_load_mix_takes_a_churn(tmp_path):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(churn_mix()))
    mix = loadgen.load_mix(str(path))
    zipf = loadgen.load_mix(harness.mix_path("tenants_zipf"))
    assert mix.pop("churn") == CHURN and mix == zipf


@pytest.mark.parametrize("bad", [
    {"kind": "replace"}, {"table": "users"}, {"every_s": 0},
    {"every_s": 2.5}, {"data_sets": 1}, {"data_sets": True},
    {"every_s": True},
])
def test_load_mix_refuses_a_bad_churn(tmp_path, bad):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(churn_mix(**bad)))
    with pytest.raises(ValueError, match="churn"):
        loadgen.load_mix(str(path))


def test_loader_churns_on_the_window_clock():
    """Churns fall at every ``every_s`` seconds from the window's start
    and before its end; ones already due are applied at once, and
    ``last_version`` is the version the last one registers."""
    import time

    from repro.dataflow.table import Table
    from repro.store.artifacts import Catalog
    sets = [{"x": np.full(8, d, np.int32)} for d in range(3)]
    catalog = Catalog(None)
    catalog.register("page_views", Table.from_numpy(sets[0]))
    churn = harness.Churn(dict(CHURN, every_s=1),
                          types.SimpleNamespace(catalog=catalog), sets)
    now = time.perf_counter()
    t0, seconds = now - 2.5, 3.8
    last = churn.last_version(seconds)
    churn.start(t0, t0 + seconds)
    try:
        time.sleep(0.2)
        assert churn.applied == 2          # due at t0 + 1 and t0 + 2
        time.sleep(1.0)
        assert churn.applied == 3          # at t0 + 3; none at t0 + 4
    finally:
        churn.stop()
    assert churn.error is None and churn.done == last == 3
    assert int(np.asarray(catalog.get("page_views").columns["x"])[0]) == 0


# the first 12 draws of each client at seed 2**31 + 77, as the generator
# drew them before churn was added
STREAMS = {
    ("serial", 0): ["L4", "L11", "L8", "L6", "L7", "L2", "L3_sum", "L5",
                    "L5", "L8", "L7", "L11"],
    ("tenants_zipf", 0): ["busy_users", "L11", "L5", "hi_rev", "L11", "L11",
                          "hi_rev", "hi_rev", "hi_rev", "L3_mean",
                          "busy_users", "L5"],
    ("tenants_zipf", 1): ["L11", "L3_mean", "L5", "L3F", "busy_users",
                          "L3_mean", "L3_sum", "L5", "L5", "L3_sum", "L5",
                          "L8"],
    ("tenants_zipf", 2): ["L2", "L2", "L11", "L4", "L7", "L4", "L2", "L3F",
                          "L4", "hi_rev", "L4", "L6"],
    ("tenants_zipf", 3): ["L8", "L3F", "L4", "L3F", "L7", "L3F", "L3F",
                          "L3_sum", "L6", "hi_rev", "L5", "L3_sum"],
}


@pytest.mark.parametrize("mix, client", sorted(STREAMS))
def test_streams_of_the_existing_mixes_are_unchanged(mix, client):
    def draws(name):
        s = loadgen.client_stream(loadgen.load_mix(harness.mix_path(name)),
                                  2**31 + 77, client)
        return [next(s) for _ in range(12)]
    assert draws(mix) == STREAMS[mix, client]
    if mix == "tenants_zipf":
        s = loadgen.client_stream(churn_mix(), 2**31 + 77, client)
        assert [next(s) for _ in range(12)] == STREAMS[mix, client]


def test_churn_rehearsal(no_compile_cache, capsys):
    """A churn mix through the service, with a churn every second so that
    a short window on a CPU holds several; whether the program's answers
    are fresh is not asserted here."""
    out = harness.run_cell("restore_pigmix_4m.tenants_zipf", 2**31 + 21,
                           3.5, False, require_chip=False,
                           config=small("restore_pigmix_4m"),
                           mix=churn_mix(every_s=1))
    log = capsys.readouterr().out
    applied = int(re.search(r"churn: (\d+) re-registrations of page_views "
                            r"in the window, 1 in set-up", log).group(1))
    assert applied >= 2
    kept = re.search(r"kept answers could read data sets \[([\d, ]+)\]",
                     log).group(1)
    assert len(kept.split(",")) >= 2
    assert list(out["checks"]) == ["failed_queries", "wrong_answers",
                                   "stale_answers", "float_rel_err",
                                   "disk_mismatch"]
    assert out["checks"]["failed_queries"]["value"] == 0
    # a program that names a fresh answer's output after another version
    # fails disk_mismatch too, so neither number is asserted here
    assert "compile events inside the window: 0 " in log
    assert list(out)[-1] == "checks"


def test_a_table_read_while_churning_lies_within_the_versions_recorded():
    """Readers record ``done`` before they read the catalog's table and
    ``started`` after, with thread switches forced often, while the
    loader re-registers: the data set read is always one of those the
    two versions allow."""
    import sys
    import threading

    from repro.dataflow.table import Table
    from repro.store.artifacts import Catalog
    sets = [{"x": np.full(8, d, np.int32)} for d in range(3)]
    catalog = Catalog(None)
    catalog.register("page_views", Table.from_numpy(sets[0]))
    churn = harness.Churn(CHURN, types.SimpleNamespace(catalog=catalog),
                          sets)
    bad, reads, stop = [], [0], threading.Event()

    def read():
        while not stop.is_set():
            v_submit = churn.done
            d = int(np.asarray(catalog.get("page_views").columns["x"])[0])
            fresh, _ = churn.data_sets(v_submit, churn.started)
            reads[0] += 1
            if d not in fresh:
                bad.append((v_submit, d))

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        churn.apply()                     # data set 1 as version 1
        readers = [threading.Thread(target=read) for _ in range(4)]
        for t in readers:
            t.start()
        for _ in range(60):
            churn.apply()
        stop.set()
        for t in readers:
            t.join(30)
    finally:
        stop.set()
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in readers)
    assert churn.done == churn.started == 61 and reads[0] > 0
    assert bad == []
