"""Process-wide plan-fingerprint jit cache + kernel padding paths.

Benchmarks build a fresh ``Engine`` per arm; identical plans must
trace/compile exactly once per process.  The padded Pallas path must give
identical results to the dense fallback at capacities that are not tile
multiples.
"""
import jax
import numpy as np
import pytest

from repro.core import plan as P
from repro.dataflow import physical as PH
from repro.dataflow.compiler import compile_workflow
from repro.dataflow.executor import GLOBAL_JIT_CACHE, Engine
from repro.dataflow.physical import execute_plan
from repro.dataflow.table import Table, encode_strings
from repro.store.artifacts import ArtifactStore, Catalog


def _catalog(n=512, seed=0):
    rng = np.random.default_rng(seed)
    t = Table.from_numpy({
        "k": rng.integers(0, 16, n).astype(np.int32),
        "v": rng.random(n).astype(np.float32)})
    store = ArtifactStore()
    cat = Catalog(store)
    cat.register("t", t)
    return cat, store


def _plan():
    g = P.groupby(P.load("t"), ["k"], {"s": ("sum", "v")})
    return P.PhysicalPlan([P.store(g, "out")])


def test_identical_plans_compile_once_across_engines():
    GLOBAL_JIT_CACHE.clear()
    cat1, store1 = _catalog()
    cat2, store2 = _catalog()
    wf1 = compile_workflow(_plan())
    wf2 = compile_workflow(_plan())

    eng1 = Engine(cat1, store1)
    res1, stats1 = eng1.run_workflow(wf1)
    misses_after_first = GLOBAL_JIT_CACHE.misses
    assert misses_after_first >= 1

    eng2 = Engine(cat2, store2)     # fresh engine, identical plan
    res2, stats2 = eng2.run_workflow(wf2)
    assert GLOBAL_JIT_CACHE.misses == misses_after_first, \
        "identical plan in a second Engine must not re-trace"
    assert GLOBAL_JIT_CACHE.hits >= 1

    # per-op stats must be keyed by the CURRENT plan's uids even when
    # the jitted fn (and its stats) came from the first plan's closure
    wf2_uids = {op.uid for j in wf2.jobs for op in j.plan.topo()}
    for st in stats2:
        assert st.op_rows, "op_rows lost through the shared jit cache"
        assert set(st.op_rows) <= wf2_uids
    for st1, st2 in zip(stats1, stats2):
        assert sorted(st1.op_rows.values()) == sorted(st2.op_rows.values())

    # results agree
    a = np.sort(np.asarray(res1["out"].to_numpy()["s"]))
    b = np.sort(np.asarray(res2["out"].to_numpy()["s"]))
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_different_plans_get_distinct_cache_entries():
    GLOBAL_JIT_CACHE.clear()
    cat, store = _catalog()
    eng = Engine(cat, store)
    eng.run_workflow(compile_workflow(_plan()))
    m1 = GLOBAL_JIT_CACHE.misses
    other = P.PhysicalPlan([P.store(
        P.groupby(P.load("t"), ["k"], {"m": ("mean", "v")}), "out2")])
    eng.run_workflow(compile_workflow(other))
    assert GLOBAL_JIT_CACHE.misses > m1


def _odd_capacity_tables():
    # 300 and 320 are > 256 and not multiples of 256: before the padding
    # change these capacities silently bailed to the dense fallback
    rng = np.random.default_rng(7)
    n_l, n_r = 320, 300
    left = Table.from_numpy({
        "key": encode_strings([f"k{i % 40}" for i in range(n_l)]),
        "val": rng.random(n_l).astype(np.float32)})
    right = Table.from_numpy({
        "key": encode_strings([f"k{i}" for i in range(n_r)]),
        "payload": rng.integers(0, 100, n_r).astype(np.int32)})
    return left, right


def _sorted_cols(res):
    return {c: np.sort(np.asarray(v).astype(np.float64), axis=0)
            for c, v in res.to_numpy().items()}


def test_pallas_padded_matches_fallback_at_odd_capacity():
    left, right = _odd_capacity_tables()
    gplan = P.PhysicalPlan([P.store(P.groupby(
        P.load("t"), ["key"], {"s": ("sum", "val"),
                               "c": ("count", "val")}), "out")])
    jplan = P.PhysicalPlan([P.store(P.join(
        P.load("t"), P.load("r"), ["key"], ["key"]), "out")])
    datasets = {"t": left, "r": right}
    ref_g, _ = execute_plan(gplan, datasets)
    ref_j, _ = execute_plan(jplan, datasets)
    PH.set_use_pallas(True)
    try:
        got_g, _ = execute_plan(gplan, datasets)
        got_j, _ = execute_plan(jplan, datasets)
    finally:
        PH.set_use_pallas(False)
    for ref, got in ((ref_g, got_g), (ref_j, got_j)):
        r, g = _sorted_cols(ref["out"]), _sorted_cols(got["out"])
        assert sorted(r) == sorted(g)
        for c in r:
            np.testing.assert_allclose(r[c], g[c], atol=1e-3)


def test_compact_is_stable_and_sort_free_correct():
    """Table.compact() must move valid rows to a prefix preserving
    their order."""
    rng = np.random.default_rng(11)
    n = 513                              # deliberately not a power of two
    v = np.zeros(n, bool)
    v[rng.choice(n, 200, replace=False)] = True
    t = Table.from_numpy({"a": np.arange(n, dtype=np.int32)})
    t = Table(t.columns, jax.numpy.asarray(v))
    c = t.compact()
    got_valid = np.asarray(c.valid)
    assert got_valid[:200].all() and not got_valid[200:].any()
    np.testing.assert_array_equal(np.asarray(c.col("a"))[:200],
                                  np.flatnonzero(v).astype(np.int32))


def _masks(n=100):
    scattered = np.zeros(n, bool)
    scattered[np.random.default_rng(4).choice(n, 41, replace=False)] = True
    first, last, pow2 = (np.zeros(n, bool) for _ in range(3))
    first[0] = last[-1] = True
    pow2[np.random.default_rng(5).choice(n, 32, replace=False)] = True
    return {"all": np.ones(n, bool), "none": np.zeros(n, bool),
            "scattered": scattered, "first": first, "last": last,
            "pow2": pow2}


@pytest.mark.parametrize("mask", sorted(_masks()))
def test_compact_to_static_capacity_matches_numpy(mask):
    """``compact_to`` at the store's capacity (power of two over the
    valid rows, at least 8, at most the table's) equals the host-side
    compaction bit for bit: valid rows in order, then zero rows, for
    every column shape and dtype a Table holds."""
    from repro.dataflow.table import compact_to
    n = 100
    v = _masks(n)[mask]
    rng = np.random.default_rng(13)
    f = rng.standard_normal(n).astype(np.float32)
    f[:4] = [-0.0, np.nan, 1e-40, -np.inf]       # bits, not values
    cols = {"i": rng.integers(-2**31, 2**31 - 1, n, dtype=np.int32),
            "f": f, "s": rng.integers(0, 256, (n, 20), dtype=np.uint8),
            # token rows (train/data.py) and flags
            "t": rng.integers(-2**31, 2**31 - 1, (n, 3), dtype=np.int32),
            "b": rng.random(n) < 0.5}
    nvalid = int(v.sum())
    cap = min(n, max(8, 1 << (max(nvalid, 1) - 1).bit_length()))
    t = Table(Table.from_numpy(cols).columns, jax.numpy.asarray(v))
    got, got_valid = compact_to(t, capacity=cap).to_host()
    np.testing.assert_array_equal(got_valid, np.arange(cap) < nvalid)
    for k, a in cols.items():
        a = a[v][:cap]
        want = np.pad(a, [(0, cap - len(a))] + [(0, 0)] * (a.ndim - 1))
        assert got[k].dtype == want.dtype and got[k].shape == want.shape
        assert got[k].tobytes() == want.tobytes(), k


def test_hash_cache_shares_across_operators():
    """A fan-out hitting GROUPBY + JOIN on the same key column must hash
    each (columns, seed) pair once per plan execution: the GROUPBY's h1
    (seed 0) is the JOIN's probe hash."""
    calls = {"n": 0}
    orig = PH.hash_columns

    def counting(table, names, seed=0):
        calls["n"] += 1
        return orig(table, names, seed=seed)

    rng = np.random.default_rng(3)
    t = Table.from_numpy({"k": rng.integers(0, 8, 256).astype(np.int32),
                          "v": rng.random(256).astype(np.float32)})
    r = Table.from_numpy({"k": np.arange(8, dtype=np.int32),
                          "w": rng.random(8).astype(np.float32)})
    src = P.load("t")
    g = P.groupby(src, ["k"], {"s": ("sum", "v")})
    j = P.join(src, P.load("r"), ["k"], ["k"])
    plan = P.PhysicalPlan([P.store(g, "g"), P.store(j, "j")])

    PH.hash_columns = counting
    try:
        execute_plan(plan, {"t": t, "r": r})
        with_cache = calls["n"]
        calls["n"] = 0
        # same ops called directly, no shared cache
        PH.op_groupby(t, ["k"], {"s": ("sum", "v")})
        PH.op_join(t, r, ["k"], ["k"])
        without_cache = calls["n"]
    finally:
        PH.hash_columns = orig
    # groupby: (k,0) (k,101); join: (k,0) shared + right (k,0)
    assert with_cache == without_cache - 1, \
        "plan execution must share key hashes across operators"
