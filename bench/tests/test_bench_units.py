"""The benchmark's own arithmetic, on hand-built inputs: the trace
reduction, the kernels' byte counts, the traffic generator, the
reference's comparison and its control, and BENCHMARK.json against the
files it names."""
import json
import os
import re
import types

import numpy as np
import pytest

import counts
import harness
import loadgen
import queries
import reference
import trace
from conftest import small

MS = 1_000_000          # ns


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def _profile(device_events, host_events):
    return types.SimpleNamespace(planes=[
        _plane("/host:CPU", [_line("python", host_events)]),
        _plane("/device:TPU:0", [
            _line("XLA Modules", [_ev("jit_fn", 0, 100 * MS)]),
            _line("XLA Ops", device_events)]),
    ])


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (40, 50)]
    assert trace.union_ns(iv, 0, 45) == 15 + 10 + 5
    assert trace.union_ns(iv, 12, 25) == 3 + 5
    assert trace.gaps_ns(iv, 0, 60) == [(15, 20), (30, 40), (50, 60)]
    assert trace.gaps_ns([], 3, 7) == [(3, 7)]


def test_reduce_busy_kernels_and_gaps():
    dev = [
        _ev("fusion.1", 5 * MS, 10 * MS),                   # clipped at 10
        _ev("%segment_sum_sorted.2 = f32[1,32768,128]{2,1,0} custom-call("
            "s32[32768,128] %fusion.3)", 20 * MS, 10 * MS),
        _ev("%join_probe = s32[32768,128]{1,0} custom-call(s32[200] %a)",
            25 * MS, 10 * MS),                              # overlaps
        _ev("%fusion.9 = s32[4194304] fusion(s32[32768,128] %join_probe)",
            40 * MS, 0),                                    # a consumer
        _ev("fusion.2", 80 * MS, 40 * MS),                  # clipped at 100
    ]
    host = [_ev("bench.window", 10 * MS, 90 * MS),
            _ev("bench.driver", 10 * MS, 60 * MS),
            _ev("bench.store.flush", 40 * MS, 20 * MS),
            _ev("bench.client.query", 70 * MS, 10 * MS)]
    out = trace.reduce(_profile(dev, host),
                       kernels=("segment_sum_sorted", "join_probe"))
    assert out["window_s"] == pytest.approx(0.090)
    # busy: [10,15] + [20,35] + [80,100]
    assert out["busy_s"] == pytest.approx(0.005 + 0.015 + 0.020)
    assert out["kernel_s"]["segment_sum_sorted"] == pytest.approx(0.010)
    assert out["kernel_s"]["join_probe"] == pytest.approx(0.010)
    gaps = dict(out["idle_gaps"])
    # [15,20] mid 17.5 driver; [35,80] mid 57.5 store.flush
    assert gaps == {"bench.driver": pytest.approx(0.005),
                    "bench.store.flush": pytest.approx(0.045)}
    ops = dict(out["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.020)
    assert ops["fusion.1"] == pytest.approx(0.005)
    assert trace.op_name("%join_probe.1 = s32[8] custom-call()") == \
        "join_probe.1"


def test_reduce_without_device_plane():
    pd = types.SimpleNamespace(planes=[
        _plane("/host:CPU", [_line("python", [
            _ev("bench.window", 0, 10 * MS)])])])
    out = trace.reduce(pd, kernels=("join_probe",))
    assert out["busy_s"] is None and out["window_s"] == pytest.approx(0.01)
    with pytest.raises(ValueError):
        trace.reduce(types.SimpleNamespace(planes=[]))


def test_label_points_takes_innermost_span():
    spans = [(0, 100, "outer"), (10, 20, "inner"), (50, 60, "mid")]
    assert trace.label_points(spans, [15, 55, 5, 200, 99]) == [
        "inner", "mid", "outer", "no span", "outer"]


def test_kernel_byte_counts():
    assert counts.segment_sum_bytes(1024, 1, 1024) == 1024 * 8 + 1024 * 4
    assert counts.segment_sum_bytes(10, 2, 3) == 10 * 12 + 3 * 8
    assert counts.join_probe_bytes(1000, 200) == 1000 * 8 + 200 * 4
    hbm = 819e9
    # 819 MB in 1 ms at 819 GB/s is the roofline itself
    assert counts.roofline_share(819e6, 1e-3, hbm) == pytest.approx(100.0)
    assert counts.roofline_share(819e6, 2e-3, hbm) == pytest.approx(50.0)
    assert counts.roofline_share(0, 1e-3, hbm) is None
    assert counts.roofline_share(10, 0.0, hbm) is None


def test_kernel_bytes_from_traced_programs():
    import spans
    rec = spans.Recorder(annotate=False)
    prog = object()
    spans._TRACED_KERNELS[id(prog)] = (prog, [
        ("segment_sum_sorted", (4096, 1, 4096)),
        ("segment_sum_sorted", (4096, 1, 4096)),
        ("join_probe", (4096, 200))])
    try:
        rec.program_runs[id(prog)] = 3
        got = spans.kernel_bytes(rec)
    finally:
        del spans._TRACED_KERNELS[id(prog)]
    assert got == {
        "segment_sum_sorted": 3 * 2 * counts.segment_sum_bytes(4096, 1,
                                                               4096),
        "join_probe": 3 * counts.join_probe_bytes(4096, 200)}


def test_streams_are_seeded_and_shaped():
    serial = loadgen.load_mix(harness.mix_path("serial"))
    s = loadgen.client_stream(serial, 2**31 + 5, 0)
    rounds = [[next(s) for _ in serial["templates"]] for _ in range(3)]
    assert all(sorted(r) == sorted(serial["templates"]) for r in rounds)
    again = loadgen.client_stream(serial, 2**31 + 5, 0)
    assert [next(again) for _ in range(24)] == sum(rounds, [])
    zipf = loadgen.load_mix(harness.mix_path("tenants_zipf"))
    draws = [next(loadgen.client_stream(zipf, 7, 1)) for _ in range(1)]
    a = loadgen.client_stream(zipf, 7, 1)
    b = loadgen.client_stream(zipf, 7, 2)
    da = [next(a) for _ in range(2000)]
    db = [next(b) for _ in range(2000)]
    assert da[0] == draws[0] and da != db
    top = max(set(da), key=da.count)
    assert da.count(top) > 2000 / len(zipf["templates"]) * 2


def test_compare_exact_and_float_parts():
    want = {"user": np.array([[1, 2], [3, 4]], np.uint8),
            "n": np.array([5, 6]), "total": np.array([100.0, 200.0])}
    got = {"user": want["user"][::-1].copy(), "n": want["n"][::-1].copy(),
           "total": np.array([200.002, 100.0], np.float32)}
    why, err = reference.compare(got, want, floats=("total",))
    assert why is None and err == pytest.approx(1e-5, rel=1e-2)
    got["n"] = np.array([6, 6])
    assert reference.compare(got, want, floats=("total",))[0] is not None
    why, err = reference.compare({"n": np.array([1])}, {"n": np.array([1, 2])})
    assert why is not None and err is None


def test_control_fails_and_reference_passes_at_small_size():
    import control
    config = small("restore_pigmix_4m")
    mix = loadgen.load_mix(harness.mix_path("tenants_zipf"))
    for seed in (3, 4, 2**31 + 11):
        out = control.readings(config, mix["templates"], seed)
        assert out["correct"] is False
        assert out["checks"]["float_rel_err"]["value"] > \
            config["limits"]["float_rel_err"]
    import datagen
    host = datagen.tables(config, 3)
    ref = reference.Reference(host)
    answers = {t: [(ref.answer(t), 0)] for t in mix["templates"]}
    checks, wrong = harness.check_answers(answers, host, config["limits"])
    assert wrong == 0 and checks["float_rel_err"]["value"] == 0.0


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    bench = harness.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    root = harness.ROOT
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        cfg = harness.config_of(bench, c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(k in cfg for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        mix = loadgen.load_mix(harness.mix_path(w["traffic"]))
        assert set(mix["templates"]) <= set(queries.TEMPLATES)
    cells = {w["name"] for w in bench["workloads"]}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert set(m.get("workloads", cells)) <= cells
        assert callable(harness.metric_module(m["name"]).read)
        assert os.path.exists(os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        # every cell that reports the layer metric reports what it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        assert harness.cell_metrics(bench, cell, False)
        assert harness.cell_metrics(bench, cell, True)


def test_query_templates_build_and_name_their_outputs():
    for name in queries.TEMPLATES:
        p = queries.plan(name)
        assert [s.params["name"] for s in p.sinks] == [queries.OUTPUT[name]]
    json.dumps(queries.FLOAT_COLS)


def test_data_keeps_its_sizes_across_seeds_and_its_widths():
    import datagen
    config = small("pig_pigmix_4m")
    a, b = datagen.tables(config, 3), datagen.tables(config, 2**31 + 9)
    w = config["widths"]
    for t in ("page_views", "users", "power_users"):
        for c, col in a[t].items():
            assert col.shape == b[t][c].shape
            if c in w:
                assert col.shape[1] == w[c] and col.dtype == np.uint8
    pv = a["page_views"]
    assert len(pv["user"]) == config["page_views_rows"]
    assert not np.array_equal(pv["user"], b["page_views"]["user"])
    assert len(a["power_users"]["name"]) == config["power_users_rows"]
    # users are the distinct users of page_views; power users are users
    users = {bytes(r) for r in a["users"]["name"]}
    assert {bytes(r) for r in pv["user"]} == users
    assert {bytes(r) for r in a["power_users"]["name"]} <= users
    # zipf keys: the busiest user has many rows, the same count each seed
    counts = [sorted(np.unique(t["page_views"]["user"], axis=0,
                               return_counts=True)[1]) for t in (a, b)]
    assert counts[0] == counts[1] and counts[0][-1] > 50 * counts[0][0]
    ref_a, ref_b = reference.Reference(a), reference.Reference(b)
    for name in queries.TEMPLATES:
        got = [len(next(iter(r.answer(name).values()))) for r in (ref_a, ref_b)]
        assert got[0] == got[1], name
