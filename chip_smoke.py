#!/usr/bin/env python3
"""Drive the PigMix reuse path once on a TPU and check its answers.

One chip (the default):

    python chip_smoke.py [--rows 4194304] [--seed 0] [--cache-share 0.25]

generates PigMix ``page_views`` (``--rows`` rows) with ``users`` and
``power_users`` from ``--seed``, runs L2-L8 and L11 through
``ReStoreService.submit`` twice (the cold pass stores, the warm pass
reuses), checks every answer against a plain numpy reference over the
same rows, checks that the warm answers equal the cold ones bit for bit,
reports which Pallas kernels the compiled job programs contain, and
checks each kernel against its ``ref.py`` at full size.

Four chips:

    python chip_smoke.py --chips 4

runs only the mesh path: L3, L6 and L11 on a 4-device data mesh, cold,
then warm with co-partitioned reuse (exchanges skipped), each compared
with the same queries on one device in the same process and with the
numpy reference.

The last line of standard output is a JSON object with ``"ok": true``
and the device JAX reports.  Any failed phase exits nonzero without it.
A backend other than TPU also fails: with no ``--rows`` it stops at
once, and with ``--rows`` (a CPU rehearsal, e.g. ``--rows 65536``) it
fails after all phases have run.  The compile cache is the repository's
(``repro.launch.compile_cache``); autotuning stays off.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.pop("RESTORE_AUTOTUNE", None)      # built-in tiles, no table

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

DEFAULT_ROWS = 1 << 22
# the paper's PigMix page_views data set (ReStore, section 7)
PAPER_BYTES = 15e9
STORE_DIR = os.path.join(ROOT, ".smoke_store")
KERNELS = ("segment_sum_sorted", "join_probe", "partition_scatter")
# the duration event JAX records for each backend (XLA) compilation
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# float aggregates are float32 sums over up to millions of rows, in an
# order the reference does not share
RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- reference


def _ids(*arrays):
    """Shared integer ids for the rows of same-width uint8 string arrays
    (one id space across all of them); returns one id array each."""
    import numpy as np
    width = arrays[0].shape[1]
    void = [np.ascontiguousarray(a).view(np.dtype((np.void, width))).ravel()
            for a in arrays]
    _, inv = np.unique(np.concatenate(void), return_inverse=True)
    out, at = [], 0
    for v in void:
        out.append(inv[at:at + len(v)])
        at += len(v)
    return out


class Reference:
    """Plain numpy answers for the PigMix queries over the generated rows
    — independent of the engine: no hashing, sorting network or kernel."""

    def __init__(self, pv, users, power):
        import numpy as np
        self.np = np
        self.pv, self.users, self.power = pv, users, power
        pu, uu, wu = _ids(pv["user"], users["name"], power["name"])
        self.pu, self.uu, self.wu = pu, uu, wu
        self.n_ids = int(max(pu.max(), uu.max(), wu.max())) + 1
        self.name_of = np.zeros((self.n_ids, pv["user"].shape[1]), np.uint8)
        self.name_of[pu] = pv["user"]
        self.name_of[uu] = users["name"]
        self.name_of[wu] = power["name"]

    def _per_user(self, weights=None):
        return self.np.bincount(self.pu, weights=weights,
                                minlength=self.n_ids)

    def _users_in(self, ids):
        mask = self.np.zeros(self.n_ids, bool)
        mask[ids] = True
        return mask

    def L2(self):
        keep = self._users_in(self.wu)[self.pu]
        return {"user": self.pv["user"][keep],
                "estimated_revenue": self.pv["estimated_revenue"][keep],
                "name": self.pv["user"][keep]}

    def _joined_users(self):
        present = (self._per_user() > 0) & self._users_in(self.uu)
        return self.np.flatnonzero(present)

    def L3(self, agg="sum"):
        ids = self._joined_users()
        if agg == "sum":
            val = self._per_user(self.pv["estimated_revenue"])
        else:
            val = self._per_user()
        return {"user": self.name_of[ids], "total": val[ids]}

    def L3F(self):
        ids = self._joined_users()
        total = self._per_user(self.pv["estimated_revenue"])[ids]
        cnt = self._per_user()[ids]
        return {"user": self.name_of[ids], "avg_rev": total / cnt}

    def L4(self):
        np = self.np
        pairs = np.unique(self.pu * 4 + self.pv["action"])
        n = np.bincount(pairs // 4, minlength=self.n_ids)
        ids = np.flatnonzero(n)
        return {"user": self.name_of[ids], "n_actions": n[ids]}

    def L5(self):
        np = self.np
        keep = self._users_in(self.uu)[self.pu]
        row_of = np.full(self.n_ids, -1)
        row_of[self.uu] = np.arange(len(self.uu))
        r = row_of[self.pu[keep]]
        return {"user": self.pv["user"][keep],
                "timespent": self.pv["timespent"][keep],
                "name": self.users["name"][r],
                "phone": self.users["phone"][r],
                "zip": self.users["zip"][r]}

    def L6(self):
        np = self.np
        (tid,) = _ids(self.pv["query_term"])
        n_t = int(tid.max()) + 1
        key = self.pu * n_t + tid
        tot = np.bincount(key, weights=self.pv["timespent"],
                          minlength=self.n_ids * n_t)
        hit = np.bincount(key, minlength=self.n_ids * n_t) > 0
        ks = np.flatnonzero(hit)
        term_of = np.zeros((n_t, self.pv["query_term"].shape[1]), np.uint8)
        term_of[tid] = self.pv["query_term"]
        return {"user": self.name_of[ks // n_t],
                "query_term": term_of[ks % n_t], "total_time": tot[ks]}

    def L7(self):
        np = self.np
        ts, hour = self.pv["timespent"], self.pv["timestamp"]
        ids = np.flatnonzero(self._per_user() > 0)
        m = self._per_user(np.where(hour < 12, ts, 0))[ids]
        a = self._per_user(np.where(hour >= 12, ts, 0))[ids]
        return {"user": self.name_of[ids], "m": m, "a": a}

    def L8(self):
        np = self.np
        return {"all": np.array([1]),
                "t": np.array([self.pv["timespent"].sum(dtype=np.float64)]),
                "r": np.array([self.pv["estimated_revenue"]
                               .mean(dtype=np.float64)])}

    def L11(self):
        np = self.np
        ids = np.union1d(np.unique(self.pu), self.wu)
        return {"user": self.name_of[ids]}


# (query name, builder args, output dataset, float columns)
QUERIES = {
    "L2": ((), "L2_out", ()),
    "L3": (("sum",), "L3_sum_out", ("total",)),
    "L4": ((), "L4_out", ()),
    "L5": ((), "L5_out", ()),
    "L6": ((), "L6_out", ("total_time",)),
    "L7": ((), "L7_out", ("m", "a")),
    "L8": ((), "L8_out", ("t", "r")),
    "L11": (("power_users",), "L11_power_users_out", ()),
}
MESH_COLD = ("L3", "L6", "L11")
# warm extras that reuse L3's stored join, which the mesh run stores
# co-partitioned on the grouping key: their GROUP BY skips its exchange
EXTRA = {
    "L3count": (("L3", ("count",)), "L3_count_out", ()),
    "L3F": (("L3F", ()), "L3F_out", ("avg_rev",)),
}


def plan_of(name):
    from repro.workloads import pigmix
    if name in QUERIES:
        return getattr(pigmix, name)(*QUERIES[name][0])
    (base, args), _, _ = EXTRA[name]
    return getattr(pigmix, base)(*args)


def out_of(name):
    return (QUERIES.get(name) or EXTRA[name])[1]


def float_cols(name):
    return (QUERIES.get(name) or EXTRA[name])[2]


def reference_of(ref, name):
    if name == "L3count":
        return ref.L3("count")
    return getattr(ref, name)()


def _canonical(cols, order_by):
    """Rows sorted by ``order_by`` (strings by their bytes)."""
    import numpy as np
    keys = []
    for c in reversed(order_by):
        a = np.asarray(cols[c])
        if a.ndim == 2:
            keys.extend(a[:, j] for j in reversed(range(a.shape[1])))
        else:
            keys.append(a)
    idx = np.lexsort(keys) if keys else np.arange(0)
    return {c: np.asarray(v)[idx] for c, v in cols.items()}


def compare(got, want, floats, exact_floats=False):
    """None if ``got`` matches ``want``, else the reason.  Columns not in
    ``floats`` must match exactly; float columns within RTOL (or exactly
    with ``exact_floats``)."""
    import numpy as np
    if set(got) != set(want):
        return f"columns {sorted(got)} != {sorted(want)}"
    n_got = len(next(iter(got.values())))
    n_want = len(next(iter(want.values())))
    if n_got != n_want:
        return f"{n_got} rows != {n_want}"
    order_by = [c for c in sorted(got) if c not in floats] or sorted(got)
    g, w = _canonical(got, order_by), _canonical(want, order_by)
    for c in sorted(got):
        a, b = np.asarray(g[c]), np.asarray(w[c])
        if c in floats and not exact_floats:
            if not np.allclose(a.astype(np.float64), b.astype(np.float64),
                               rtol=RTOL, atol=1e-6):
                err = np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30))
                return f"column {c}: max relative error {err:.3g}"
        elif a.shape != b.shape or not np.array_equal(
                a.astype(np.float64) if a.dtype.kind == "f" else a,
                b.astype(np.float64) if b.dtype.kind == "f" else b):
            return f"column {c} differs"
    return None


# ------------------------------------------------------------- instruments


def _abstract(x):
    """Shape, dtype and — where the array is committed to devices — the
    sharding of a job input, so that lowering it again reproduces the
    program the engine compiled (and finds it in the compile cache)."""
    import jax
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=x.sharding if x.committed else None)


class Programs:
    """Records the abstract inputs of every job program the engine runs
    (through the process-wide jit cache), so the compiled HLO can be
    inspected afterwards.  Also sums backend compile seconds."""

    def __init__(self):
        import jax
        from repro.dataflow import executor

        self.compile_s = 0.0
        self.calls = {}

        def on_event(event, duration, **_):
            if event == COMPILE_EVENT:
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)
        outer = self

        class Recording(executor.JitCache):
            def get(self, key, build):
                fn, uid_by_fp = super().get(key, build)

                def call(datasets):
                    outer.calls[key] = (fn, jax.tree.map(_abstract,
                                                         datasets))
                    return fn(datasets)
                return call, uid_by_fp

        executor.GLOBAL_JIT_CACHE = Recording()

    def kernels(self):
        """kernel name -> number of job programs whose compiled HLO
        calls it as a Mosaic custom call."""
        found = {k: 0 for k in KERNELS}
        c0 = self.compile_s
        for fn, abstract in self.calls.values():
            text = fn.lower(abstract).compile().as_text()
            calls = [ln for ln in text.splitlines()
                     if "tpu_custom_call" in ln]
            for k in KERNELS:
                found[k] += any(k in ln for ln in calls)
        log(f"   compile seconds to read back {len(self.calls)} job "
            f"programs: {self.compile_s - c0:.6f}")
        return found


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ phases


class Smoke:
    def __init__(self):
        self.failed = []

    def phase(self, name, fn, *args):
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            log(f"   FAILED {name} after {time.perf_counter() - t0:.3f} s")
            self.failed.append(name)
            return None
        log(f"   {name}: ok in {time.perf_counter() - t0:.3f} s")
        return out

    def check(self, cond, what):
        if not cond:
            raise AssertionError(what)


def make_data(rows, seed):
    import numpy as np
    from repro.workloads import pigmix
    pv = pigmix.gen_page_views(rows, seed)
    users = pigmix.gen_users(seed + 1)
    power = pigmix.gen_power_users(seed + 2)
    host = {n: t.to_numpy() for n, t in
            (("page_views", pv), ("users", users), ("power_users", power))}
    row_bytes = sum(np.asarray(c).itemsize * (c.shape[1] if c.ndim == 2
                                              else 1)
                    for c in host["page_views"].values()) + 1
    gb = rows * row_bytes / 1e9
    log(f"   page_views: {rows} rows x {row_bytes} B = {gb:.6f} GB, "
        f"a cut of 1/{PAPER_BYTES / 1e9 / gb:.1f} from the paper's 15 GB "
        f"PigMix data set; users {users.capacity} rows, power_users "
        f"{power.capacity} rows (seed {seed})")
    return {"page_views": pv, "users": users, "power_users": power}, host


def make_service(tables, dev, share, sub, mesh=None):
    from repro.service.service import ReStoreService
    from repro.store.artifacts import ArtifactStore, Catalog
    from repro.roofline.analysis import peaks
    hbm = (dev.memory_stats() or {}).get("bytes_limit")
    of = f"{hbm} B device memory"
    if hbm is None:           # a rehearsal backend: budget as on a v5e
        hbm = int(peaks("TPU v5 lite")["hbm_bytes"])
        of = f"{hbm} B, the HBM of a TPU v5e ({dev.platform} reports none)"
    budget = int(hbm * share)
    root = os.path.join(STORE_DIR, sub)
    store = ArtifactStore(root=root, cache_bytes=budget)
    catalog = Catalog(store)
    for name, t in tables.items():
        catalog.register(name, t)
    log(f"   store {sub}: device-cache budget {budget} B = {share} of {of}")
    return ReStoreService(catalog, store, n_workers=1, mesh=mesh)


def run_pass(svc, names, label, programs, dev):
    out = {}
    for q in names:
        c0 = programs.compile_s
        t0 = time.perf_counter()
        results, report = svc.submit(plan_of(q)).result()
        wall = time.perf_counter() - t0
        table = results[out_of(q)]
        got = table.to_numpy()
        executed = sum(1 for j in report.jobs if j.executed)
        reused = sorted({a for j in report.jobs for a in j.reused_artifacts})
        skipped = sum(j.stats.shuffles_skipped for j in report.jobs
                      if j.stats)
        sh_ovf = sum(j.stats.shuffle_overflow for j in report.jobs
                     if j.stats)
        out[q] = {"cols": got, "executed": executed,
                  "jobs": len(report.jobs), "reused": reused,
                  "skipped": skipped, "shuffle_overflow": sh_ovf}
        log(f"   {label} {q}: wall {wall:.6f} s, compile "
            f"{programs.compile_s - c0:.6f} s, jobs executed {executed}/"
            f"{len(report.jobs)}, artifacts reused {len(reused)}, "
            f"exchanges skipped {skipped}, shuffle_overflow {sh_ovf}, "
            f"rows {len(next(iter(got.values())))}, peak_bytes_in_use "
            f"{peak_bytes(dev)}")
    return out


def check_answers(smoke, ref, passes, names):
    for q in names:
        want = reference_of(ref, q)
        for label, res in passes.items():
            why = compare(res[q]["cols"], want, float_cols(q))
            smoke.check(why is None, f"{label} {q} vs numpy: {why}")
        log(f"   {q}: matches numpy reference ({len(passes)} passes)")


def check_bit_identical(smoke, a, b, names, what):
    for q in names:
        why = compare(a[q]["cols"], b[q]["cols"], float_cols(q),
                      exact_floats=True)
        smoke.check(why is None, f"{what} {q}: {why}")
    log(f"   {what}: bit-identical for {', '.join(names)}")


def check_kernels_in_hlo(smoke, programs, expect):
    from repro import kernels
    from repro.dataflow.physical import use_pallas
    found = programs.kernels()
    for k in KERNELS:
        log(f"   {k}: compiled into {found[k]} of {len(programs.calls)} "
            f"job programs")
    if not use_pallas() or kernels.interpret():
        log("   kernels are not compiled on this backend: none expected")
        return
    for k in expect:
        smoke.check(found[k] > 0, f"{k} not compiled into any job program")


def kernel_parity(smoke, rows, seed):
    """Each kernel against its ref.py at full size, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.hash_join.hash_join import MAX_BUILD_ROWS
    from repro.kernels.hash_join.ops import probe
    from repro.kernels.radix_partition.ops import partition, scatter_slots
    from repro.kernels.segment_reduce.ops import segment_sum

    from repro import kernels
    mode = "interpreted" if kernels.interpret() else "compiled"
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.integers(0, 1 << 32, rows, dtype=np.uint32))
    valid = jnp.asarray(rng.random(rows) < 0.9)

    def same(name, a, b):
        a, b = jax.tree.leaves(a), jax.tree.leaves(b)
        ok = all(np.array_equal(np.asarray(x), np.asarray(y))
                 for x, y in zip(a, b))
        smoke.check(ok, f"{name}: pallas != ref")
        log(f"   {name} ({mode}) == ref at {rows} rows")

    # integer-valued payloads: float sums are then exact in any order
    vals = jnp.asarray(rng.integers(-50, 50, (rows, 1)).astype(np.float32))
    sid = jnp.asarray(np.cumsum(rng.random(rows) < 0.01).astype(np.int32))
    n_seg = int(sid[-1]) + 1
    same("segment_sum_sorted",
         segment_sum(vals, sid, num_segments=n_seg, impl="pallas"),
         segment_sum(vals, sid, num_segments=n_seg, impl="ref"))
    build = jnp.sort(jnp.asarray(
        rng.integers(0, 1 << 32, MAX_BUILD_ROWS, dtype=np.uint32)))
    left = jnp.where(jnp.arange(rows) % 2 == 0, h,
                     jnp.take(build, jnp.arange(rows) % MAX_BUILD_ROWS))
    same("join_probe", probe(left, build, impl="pallas"),
         probe(left, build, impl="ref"))
    same("radix_partition",
         partition(h, valid, n_parts=8, impl="pallas", tile_n=1024),
         partition(h, valid, n_parts=8, impl="ref", tile_n=1024))
    bucket = rows // 4 - rows // 64        # some rows overflow
    same("partition_scatter",
         scatter_slots(h, valid, n_parts=4, bucket=bucket, impl="pallas"),
         scatter_slots(h, valid, n_parts=4, bucket=bucket, impl="ref"))


# -------------------------------------------------------------------- main


def one_chip(smoke, args, dev, programs):
    rows = args.rows or DEFAULT_ROWS
    data = smoke.phase("generate data", make_data, rows, args.seed)
    if data is None:
        return
    tables, host = data
    ref = smoke.phase("numpy reference", Reference, host["page_views"],
                      host["users"], host["power_users"])
    svc = smoke.phase("service", make_service, tables, dev,
                      args.cache_share, "one")
    if svc is None:
        return
    names = list(QUERIES)
    try:
        cold = smoke.phase("cold pass", run_pass, svc, names, "cold",
                           programs, dev)
        warm = smoke.phase("warm pass", run_pass, svc, names, "warm",
                           programs, dev)
    finally:
        svc.stop()
    log(f"   compile seconds, all job programs: {programs.compile_s:.6f}")
    if cold and warm:
        def reuse():
            n_skip = sum(warm[q]["jobs"] - warm[q]["executed"]
                         for q in names)
            n_art = sum(len(warm[q]["reused"]) for q in names)
            log(f"   warm pass: {n_skip} jobs not executed, "
                f"{n_art} artifacts reused")
            smoke.check(n_skip > 0 and n_art > 0, "warm pass reused nothing")
        smoke.phase("warm reuse", reuse)
        if ref is not None:
            smoke.phase("answers", check_answers, smoke, ref,
                        {"cold": cold, "warm": warm}, names)
        smoke.phase("warm == cold", check_bit_identical, smoke, cold, warm,
                    names, "warm vs cold")
    smoke.phase("kernels in job HLO", check_kernels_in_hlo, smoke,
                programs, ("segment_sum_sorted", "join_probe"))
    smoke.phase("kernel parity", kernel_parity, smoke, rows, args.seed)


def four_chips(smoke, args, dev, programs):
    import jax
    from repro.launch.mesh import make_data_mesh
    rows = args.rows or DEFAULT_ROWS
    smoke.check(len(jax.devices()) >= 4,
                f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    data = smoke.phase("generate data", make_data, rows, args.seed)
    if data is None:
        return
    tables, host = data
    ref = smoke.phase("numpy reference", Reference, host["page_views"],
                      host["users"], host["power_users"])
    mesh = make_data_mesh(4)
    warm_names = list(MESH_COLD) + list(EXTRA)
    msvc = smoke.phase("mesh service", make_service, tables, dev,
                       args.cache_share, "mesh", mesh)
    ssvc = smoke.phase("one-device service", make_service, tables, dev,
                       args.cache_share, "single")
    if msvc is None or ssvc is None:
        return
    try:
        cold = smoke.phase("mesh cold pass", run_pass, msvc, MESH_COLD,
                           "mesh-cold", programs, dev)
        warm = smoke.phase("mesh warm pass", run_pass, msvc, warm_names,
                           "mesh-warm", programs, dev)
        single = smoke.phase("one-device pass", run_pass, ssvc, warm_names,
                             "one-device", programs, dev)
    finally:
        msvc.stop()
        ssvc.stop()
    if not (cold and warm and single):
        return

    def exchange():
        skipped = sum(warm[q]["skipped"] for q in EXTRA)
        ovf = sum(p[q]["shuffle_overflow"] for p in (cold, warm)
                  for q in p)
        log(f"   warm co-partitioned reuse: {skipped} exchanges skipped; "
            f"shuffle_overflow over all mesh jobs {ovf}")
        smoke.check(skipped > 0, "co-partitioned reuse skipped no exchange")
        smoke.check(all(warm[q]["reused"] for q in EXTRA),
                    "warm queries reused no artifact")
    smoke.phase("exchange skips", exchange)
    smoke.phase("warm == cold", check_bit_identical, smoke, cold, warm,
                MESH_COLD, "mesh warm vs cold")

    def mesh_vs_single():
        for q in warm_names:
            why = compare(warm[q]["cols"], single[q]["cols"], float_cols(q))
            smoke.check(why is None, f"mesh {q} vs one device: {why}")
        log(f"   mesh == one device for {', '.join(warm_names)}")
    smoke.phase("mesh vs one device", mesh_vs_single)
    if ref is not None:
        smoke.phase("answers", check_answers, smoke, ref,
                    {"mesh-cold": cold}, MESH_COLD)
        smoke.phase("answers", check_answers, smoke, ref,
                    {"mesh-warm": warm, "one-device": single}, warm_names)
    smoke.phase("kernels in job HLO", check_kernels_in_hlo, smoke,
                programs, ("partition_scatter", "join_probe"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"page_views rows (default {DEFAULT_ROWS} on a "
                         "TPU; required elsewhere, as a rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cache-share", type=float, default=0.25,
                    help="device-cache budget as a share of device memory")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache_dir} holding {warm} entries at start")
    if dev.platform != "tpu" and args.rows is None:
        log("no TPU: give --rows for a rehearsal on this backend")
        return 2

    programs = Programs()
    smoke = Smoke()
    shutil.rmtree(STORE_DIR, ignore_errors=True)
    try:
        run = four_chips if args.chips == 4 else one_chip
        smoke.phase(f"{args.chips}-chip path", run, smoke, args, dev,
                    programs)
    finally:
        shutil.rmtree(STORE_DIR, ignore_errors=True)
    log(f"peak_bytes_in_use: {peak_bytes(dev)}")
    if smoke.failed:
        log(f"FAILED phases: {', '.join(smoke.failed)}")
        return 1
    if dev.platform != "tpu":
        log(f"all phases passed, but {dev.platform} is not a TPU")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
