"""Mesh-sharded relational execution (DESIGN.md §11).  Like
tests/test_distributed.py, these spawn SUBPROCESSES that set
XLA_FLAGS=--xla_force_host_platform_device_count before importing jax —
the main pytest process must keep seeing 1 device."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from repro.dataflow.table import Table, encode_strings, decode_strings

def canon(tb):
    d = tb.to_numpy()
    order = np.lexsort(tuple(d[c] for c in sorted(d, reverse=True)))
    return {c: d[c][order] for c in sorted(d)}

def assert_rows_equal(a, b, label=""):
    ca, cb = canon(a), canon(b)
    assert sorted(ca) == sorted(cb), (label, sorted(ca), sorted(cb))
    for c in ca:
        assert np.array_equal(ca[c], cb[c]), (label, c, ca[c], cb[c])
"""


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", _PRELUDE + code], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_join_matches_single_device():
    run_sub("""
from repro.dataflow.physical import op_join
from repro.dataflow.shuffle import distributed_join

rng = np.random.default_rng(0)
left = Table.from_numpy({"k": rng.integers(0, 16, 256).astype(np.int32),
                         "a": rng.integers(0, 9, 256).astype(np.int32)})
right = Table.from_numpy({"rk": np.arange(16, dtype=np.int32),
                          "b": (np.arange(16) * 3 % 7).astype(np.int32)})
ref, _ = op_join(left, right, ["k"], ["rk"])
mesh = jax.make_mesh((8,), ("data",))
with mesh:
    got, sh_ovf, ovf = jax.jit(lambda l, r: distributed_join(
        l, r, ["k"], ["rk"], mesh, skew_factor=8.0))(left, right)
assert int(sh_ovf) == 0 and int(ovf) == 0, (int(sh_ovf), int(ovf))
assert_rows_equal(ref, got, "join")

# rename-chain edge: the right side carries BOTH "v" and "v_r", and the
# left carries "v" — op_join renames sequentially (v -> v_r -> v_r_r),
# and the shard_map out_specs must agree
left2 = Table.from_numpy({"k": np.arange(16, dtype=np.int32),
                          "v": np.arange(16, dtype=np.int32)})
right2 = Table.from_numpy({"k2": np.arange(16, dtype=np.int32),
                           "v": (np.arange(16) * 2).astype(np.int32),
                           "v_r": (np.arange(16) * 3).astype(np.int32)})
ref2, _ = op_join(left2, right2, ["k"], ["k2"])
with mesh:
    got2, so2, o2 = jax.jit(lambda l, r: distributed_join(
        l, r, ["k"], ["k2"], mesh, skew_factor=8.0))(left2, right2)
assert int(so2) == 0 and int(o2) == 0
assert_rows_equal(ref2, got2, "join-rename-chain")
print("OK")
""")


def test_distributed_distinct_and_cogroup_match_single_device():
    run_sub("""
from repro.dataflow.physical import op_cogroup, op_distinct
from repro.dataflow.shuffle import distributed_cogroup, distributed_distinct

rng = np.random.default_rng(1)
dt = Table.from_numpy({"x": rng.integers(0, 12, 512).astype(np.int32),
                       "y": rng.integers(0, 3, 512).astype(np.int32)})
mesh = jax.make_mesh((8,), ("data",))
with mesh:
    got, ovf = jax.jit(lambda t: distributed_distinct(
        t, mesh, skew_factor=8.0))(dt)
assert int(ovf) == 0
assert_rows_equal(op_distinct(dt), got, "distinct")

a = Table.from_numpy({"u": rng.integers(0, 10, 256).astype(np.int32),
                      "v": rng.integers(0, 50, 256).astype(np.float32)})
b = Table.from_numpy({"w": rng.integers(0, 10, 128).astype(np.int32),
                      "z": rng.integers(0, 50, 128).astype(np.float32)})
al = {"sv": ("sum", "v"), "cv": ("count", "v")}
ar = {"sz": ("sum", "z")}
ref = op_cogroup(a, b, ["u"], ["w"], al, ar)
with mesh:
    got, ovf = jax.jit(lambda x, y: distributed_cogroup(
        x, y, ["u"], ["w"], al, ar, mesh, skew_factor=8.0))(a, b)
assert int(ovf) == 0
assert_rows_equal(ref, got, "cogroup")
print("OK")
""")


def test_mesh_restore_warm_run_skips_shuffle_and_matches_plain():
    """End to end: a mesh ReStore run reuses the join artifact of a
    prior query AND skips the group-by exchange, because the artifact is
    co-partitioned on the grouping key; results stay bit-identical to
    the single-device plain run (integer-valued data).  The
    partition-blind ablation reuses without skipping."""
    run_sub("""
from repro.core import plan as P
from repro.core.restore import ReStore
from repro.store.artifacts import ArtifactStore, Catalog

def fact(n=512):
    rng = np.random.default_rng(0)
    return Table.from_numpy({
        "k": rng.integers(0, 24, n).astype(np.int32),
        "v": rng.integers(0, 100, n).astype(np.int32),
        "w": rng.integers(0, 50, n).astype(np.float32)})

def dim():
    ks = np.arange(24, dtype=np.int32)
    return Table.from_numpy({"dk": ks, "e": (ks * 7 % 5).astype(np.int32)})

def q(aggs):
    j = P.join(P.load("fact"), P.load("dim"), ["k"], ["dk"])
    g = P.groupby(j, ["k"], aggs)
    return P.PhysicalPlan([P.store(g, "out")])

def fresh(**kw):
    s = ArtifactStore(); c = Catalog(s)
    c.register("fact", fact()); c.register("dim", dim())
    return ReStore(c, s, **kw)

A1 = {"s": ("sum", "w")}
A2 = {"s": ("sum", "w"), "n": ("count", "w"), "m": ("max", "v")}
rs0 = fresh(heuristic="off", rewrite_enabled=False, semantic=False)
ref1, _ = rs0.run_plan(q(A1))
ref2, _ = rs0.run_plan(q(A2))

mesh = jax.make_mesh((8,), ("data",))
rs = fresh(heuristic="aggressive", mesh=mesh, skew_factor=8.0)
got1, rep1 = rs.run_plan(q(A1))
assert_rows_equal(ref1["out"], got1["out"], "cold")
assert all(j.stats.shuffle_overflow == 0 and j.stats.join_overflow == 0
           for j in rep1.jobs if j.stats)
got2, rep2 = rs.run_plan(q(A2))
assert_rows_equal(ref2["out"], got2["out"], "warm")
assert rep2.n_reused > 0
assert any(j.stats.shuffles_skipped > 0 for j in rep2.jobs if j.stats), \\
    "co-partitioned reuse must skip the group-by exchange"
e = next(e for e in rs.repo.entries if e.partitioning)
assert e.partitioning["keys"] == ["k"]

blind = fresh(heuristic="aggressive", mesh=mesh, skew_factor=8.0,
              partition_aware=False)
blind.run_plan(q(A1))
got3, rep3 = blind.run_plan(q(A2))
assert_rows_equal(ref2["out"], got3["out"], "blind")
assert rep3.n_reused > 0
assert all(j.stats.shuffles_skipped == 0 for j in rep3.jobs if j.stats)
print("OK")
""")


def test_mesh_restore_disk_store_repartition_on_read():
    """A repository artifact stored with P=4 shards answers a P=8 mesh:
    the engine re-partitions on read and the consumer still skips its
    exchange.  Also covers the disk-backed sharded write path under
    mesh execution."""
    run_sub("""
import tempfile
from repro.core import plan as P
from repro.core.restore import ReStore
from repro.store.artifacts import ArtifactStore, Catalog

def fact(n=512):
    rng = np.random.default_rng(0)
    return Table.from_numpy({
        "k": rng.integers(0, 24, n).astype(np.int32),
        "w": rng.integers(0, 50, n).astype(np.float32)})

def dim():
    ks = np.arange(24, dtype=np.int32)
    return Table.from_numpy({"dk": ks, "e": (ks * 7 % 5).astype(np.int32)})

def q(aggs):
    j = P.join(P.load("fact"), P.load("dim"), ["k"], ["dk"])
    g = P.groupby(j, ["k"], aggs)
    return P.PhysicalPlan([P.store(g, "out")])

root = tempfile.mkdtemp(prefix="mesh_repart_")
store = ArtifactStore(root=root)
cat = Catalog(store)
store.put("fact", fact())
store.put("dim", dim())

A1 = {"s": ("sum", "w")}
A2 = {"s": ("sum", "w"), "n": ("count", "w")}
# the reference runs against its OWN store: sharing one would leave
# A2's final artifact behind and turn the probe run into the whole-job
# fast path (nothing executed, nothing to skip)
ref_store = ArtifactStore()
ref_store.put("fact", fact()); ref_store.put("dim", dim())
ref_rs = ReStore(Catalog(ref_store), ref_store, heuristic="off",
                 rewrite_enabled=False, semantic=False)
ref, _ = ref_rs.run_plan(q(A2))

# seed on a 4-shard mesh: the stored join artifact is P=4-partitioned
mesh4 = jax.make_mesh((4,), ("data",))
rs4 = ReStore(cat, store, heuristic="aggressive", mesh=mesh4,
              skew_factor=4.0)
rs4.run_plan(q(A1))
store.flush()
parts = [store.partitioning(n) for n in store.names()
         if store.partitioning(n)]
assert any(p["n_parts"] == 4 and p["keys"] == ["k"] for p in parts), parts

# consume on an 8-shard mesh: P mismatch -> re-partition on read,
# the group-by exchange is STILL skipped
mesh8 = jax.make_mesh((8,), ("data",))
rs8 = ReStore(cat, store, repository=rs4.repo, heuristic="aggressive",
              mesh=mesh8, skew_factor=8.0)
got, rep = rs8.run_plan(q(A2))
assert_rows_equal(ref["out"], got["out"], "repart")
assert rep.n_reused > 0
assert any(j.stats.shuffles_skipped > 0 for j in rep.jobs if j.stats), \\
    "re-partitioned-on-read artifact must still skip the exchange"
print("OK")
""")


def test_union_query_on_data_mesh_matches_single_device():
    """PigMix L11 (two DISTINCTs and a UNION) on the repo's data mesh.
    ``jax.make_mesh`` defaults to explicit axes, under which the UNION's
    concatenation of two row-sharded inputs is refused; the mesh
    builders ask for auto axes."""
    run_sub("""
from jax.sharding import AxisType
from repro.core.restore import ReStore
from repro.launch.mesh import make_data_mesh
from repro.store.artifacts import ArtifactStore, Catalog
from repro.workloads import pigmix

mesh = make_data_mesh(4)
assert mesh.axis_types == (AxisType.Auto,), mesh.axis_types

def run(mesh):
    store = ArtifactStore()
    cat = Catalog(store)
    pigmix.register_all(cat, n_rows=1000)
    res, _ = ReStore(cat, store, mesh=mesh).run(pigmix.L11())
    return sorted(decode_strings(res["L11_power_users_out"]
                                 .to_numpy()["user"]))

one, four = run(None), run(mesh)
assert len(one) > 150 and one == four, (len(one), len(four))
print("OK")
""", devices=4)
