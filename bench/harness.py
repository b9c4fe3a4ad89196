"""Run one benchmark cell: set up, measure a window, check the answers.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: one
configuration (``configs/<config>.json``) under one traffic mix
(``traffic/<mix>.json``).  Everything particular to a configuration, a
mix or a metric lives in those files and in ``metrics/<metric>.py``;
this module only drives them.

The timed path is the program's entry point, ``ReStoreService.submit``.
A query is timed from ``submit`` until its answer's tables are ready on
the device and the client holds the answer's row count, which a device
reduction over the answer's validity mask gives.  Reading answers to the
host for the check happens after the window.

A mix may churn a base table (``Churn``): a loader thread re-registers
it with its next data set on a fixed clock while the clients run, and
each answer is checked against the data of the versions it could have
read.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the one the environment names, else a fixed one inside the checkout:
# the directory is part of JAX's cache key
COMPILE_CACHE_DIR = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                     or os.path.join(ROOT, ".jax_cache"))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# an answer that has not come this long after the window closed never comes
LATE_S = 60.0
PAPER_PAGE_VIEWS_BYTES = 15e9


class NoChip(RuntimeError):
    """The cell's chips are not there; no result is printed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_of(bench: dict, workload: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == workload:
            return c
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config_of(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _read_json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix_path(traffic: str) -> str:
    return os.path.join(HERE, "traffic", f"{traffic}.json")


def _load(path: str, prefix: str):
    name = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_module(name: str):
    """``metrics/<name>.py``, which defines ``read(run)``."""
    return _load(os.path.join(HERE, "metrics", f"{name}.py"),
                 "bench_metric_")


def loop_module(name: str):
    """``loops/<name>.py``, which defines ``drive(ask, stream, client,
    deadline, record)``: one client's share of the traffic."""
    return _load(os.path.join(HERE, "loops", f"{name}.py"), "bench_loop_")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # cache every program, however quickly it compiled: a later run then
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit and so no eviction: with eviction on, JAX's cache
    # reads every entry's access-time file on each write, and one entry
    # whose file is missing makes every later write fail
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileEvents:
    """Counts backend compiles (each one a program compiled or loaded
    from the persistent cache) and persistent-cache hits, in set-up and
    inside the window."""

    def __init__(self):
        import jax
        self.in_window = False
        self.setup = {"programs": 0, "cache_hits": 0, "seconds": 0.0}
        self.window = {"programs": 0, "cache_hits": 0, "seconds": 0.0}

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                c = self.window if self.in_window else self.setup
                c["programs"] += 1
                c["seconds"] += duration

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                c = self.window if self.in_window else self.setup
                c["cache_hits"] += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listeners[0])
        jax.monitoring.unregister_event_listener(self._listeners[1])


def _manifest_dirs(root: str) -> dict:
    """Artifact name -> its directory under a store's ``root``, read from
    the manifests the store publishes."""
    out = {}
    for d in os.listdir(root):
        try:
            with open(os.path.join(root, d, "manifest.json")) as f:
                out[json.load(f)["name"]] = d
        except (OSError, ValueError, KeyError):
            continue
    return out


def hold_on_disk(root: str, artifact: str, dest: str, dirs: dict) -> bool:
    """Hard-link the files of ``artifact`` as they are on disk now into
    ``dest`` (a store root of its own); False where it is not on disk.
    A link costs no copy, and later deletes or rewrites of the store's
    files leave it as it was.  ``dirs`` is ``_manifest_dirs(root)``."""
    d = dirs.get(artifact)
    if d is None:
        return False
    src, dst = os.path.join(root, d), os.path.join(dest, d)
    os.makedirs(dst)
    for fn in os.listdir(src):
        try:
            os.link(os.path.join(src, fn), os.path.join(dst, fn))
        except OSError:
            shutil.copy2(os.path.join(src, fn), os.path.join(dst, fn))
    return True


class Sampler:
    """The answers kept for the check, by key: ``(template, data sets the
    answer may have been computed on, data sets only older versions
    had)`` (``Client.answer_key``).  Answers that are the very arrays of
    one already kept under the key (whole-job reuse serves the stored
    artifact) are covered by it; of the others, ``keep`` per key are
    kept, drawn from the seed by reservoir sampling.  The artifacts that
    may hold each kept answer are held as they were on disk when the
    answer returned (``hold_on_disk``), for the durability check after
    the window, each with whether it is the output of a version the
    answer could have read (``Client.held_names``)."""

    def __init__(self, seed: int, keep: int, store_root: str,
                 held_root: str):
        self.rng = np.random.default_rng([seed, 7])
        self.keep = keep
        self.store_root, self.held_root = store_root, held_root
        self.lock = threading.Lock()
        self.kept: dict = {}   # key -> [[table, covered, held], ...]
        self.distinct: dict = {}
        self.offered = 0
        self._slots = 0

    def _hold(self, artifacts: list):
        """(slot directory, or None where none was on disk; ``(artifact,
        in_range, held)`` for each of ``artifacts``)."""
        self._slots += 1
        dest = os.path.join(self.held_root, str(self._slots))
        os.makedirs(dest)
        dirs = _manifest_dirs(self.store_root)
        arts = [(a, in_range, hold_on_disk(self.store_root, a, dest, dirs))
                for a, in_range in artifacts]
        return (dest if any(h for *_, h in arts) else None), arts

    def offer(self, key: tuple, table, artifacts) -> None:
        """``artifacts()`` lists ``(artifact, in_range)`` for the
        artifacts that may hold the answer, resolved through the store's
        aliases."""
        with self.lock:
            self.offered += 1
            kept = self.kept.setdefault(key, [])
            for k in kept:
                if k[0].valid is table.valid:
                    k[1] += 1
                    return
            n = self.distinct[key] = self.distinct.get(key, 0) + 1
            if len(kept) < self.keep:
                kept.append([table, 0, self._hold(artifacts())])
            else:
                j = int(self.rng.integers(n))
                if j < self.keep:
                    old = kept[j][2][0]
                    if old:
                        shutil.rmtree(old, ignore_errors=True)
                    kept[j] = [table, 0, self._hold(artifacts())]


class Query:
    __slots__ = ("template", "client", "t_submit", "t_done", "latency_s",
                 "n_executed", "n_reused", "spans", "error", "versions")

    def __init__(self, template, client):
        self.template, self.client = template, client
        self.error = None
        self.spans = {}
        self.n_executed = self.n_reused = 0
        # the churned table's versions at submit and at return
        self.versions = None


class Run:
    """What one run measured; the metric readers read this."""

    def __init__(self):
        self.setup_s = None
        self.window_s = None
        self.queries = []         # Query records completed in the window
        self.late = []            # completed after the window closed
        self.attempted = 0
        self.failed = 0
        self.stored_bytes = None  # store bytes at the window's end
        self.base_bytes = None
        self.store_io = {}        # store IO counters over the window
        self.trace = None         # trace.reduce() output, traced runs
        self.kernel_bytes = {}
        self.job_bytes = 0
        self.peaks = None


def _count_rows():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda valid: jnp.sum(valid.astype(jnp.int32)))


def _resolve(store, name: str) -> str:
    """The artifact ``name`` stands for, through the store's aliases."""
    seen = set()
    while name in store.aliases and name not in seen:
        seen.add(name)
        name = store.aliases[name]
    return name


class Churn:
    """A mix's base-table churn (``loadgen``'s ``churn``).  A loader
    thread re-registers the table in the service's catalog with its next
    data set every ``every_s`` seconds of the window, on the window's
    clock, cycling through the data sets, whose host columns set-up
    made; the upload is the loader's.  It uses the system's ingest call
    alone: no eviction, no maintenance.  Keeping answers fresh is the
    program's work.

    ``done`` is the table's newest catalog version whose registration
    had returned, ``started`` the newest whose registration had begun:
    a query that read ``done`` at its submit and ``started`` at its
    return may have been computed on any version between them."""

    def __init__(self, spec: dict, svc, sets: list):
        self.table = spec["table"]
        self.every = int(spec["every_s"])
        self.svc, self.sets = svc, sets
        self.first = self.started = self.done = svc.catalog.version(
            self.table)
        self.applied = 0          # in the window
        self.seconds = []         # each churn's upload and registration
        self.error = None
        self._stop = threading.Event()
        self._thread = None

    def data_set(self, version: int) -> int:
        return (version - self.first) % len(self.sets)

    def data_sets(self, v_submit: int, v_done: int) -> tuple:
        """(the data sets of versions ``v_submit`` to ``v_done``, those
        of older versions only), each sorted."""
        fresh = {self.data_set(v) for v in range(v_submit, v_done + 1)}
        older = {self.data_set(v) for v in range(
            max(self.first, v_submit - len(self.sets)), v_submit)}
        return tuple(sorted(fresh)), tuple(sorted(older - fresh))

    def last_version(self, seconds: float) -> int:
        """The newest version a window of ``seconds`` can reach from the
        current one."""
        return self.done + math.ceil(seconds / self.every) - 1

    def apply(self) -> None:
        """Registers the table's next data set."""
        from repro.dataflow.table import Table
        t0 = time.perf_counter()
        table = Table.from_numpy(self.sets[self.data_set(self.done + 1)])
        self.started += 1
        self.svc.catalog.register(self.table, table)
        v = self.svc.catalog.version(self.table)
        if v != self.started:
            raise RuntimeError(f"{self.table} registered as version {v}, "
                               f"not {self.started}")
        self.done = v
        self.seconds.append(time.perf_counter() - t0)

    def _load(self, t0: float, deadline: float) -> None:
        try:
            while True:
                at = t0 + (self.applied + 1) * self.every
                if at >= deadline or self._stop.wait(
                        max(0.0, at - time.perf_counter())):
                    return
                self.apply()
                self.applied += 1
        except Exception as e:            # reported when it stops
            self.error = e

    def start(self, t0: float, deadline: float) -> None:
        """Churns at ``t0 + every_s``, ``t0 + 2 every_s``, ... before
        ``deadline``; a churn that falls behind its time is applied at
        once."""
        self._thread = threading.Thread(target=self._load,
                                        args=(t0, deadline),
                                        name="bench-churn")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()


class Client:
    """Asks the service one query at a time and waits for the answer;
    the traffic's loop driver (``loops/<loop>.py``) calls ``ask``."""

    def __init__(self, svc, rec, count_rows, delete_outputs: bool):
        self.svc, self.rec = svc, rec
        self.count_rows = count_rows
        self.delete_outputs = delete_outputs
        self.sampler = None      # set for the window
        self.churn = None        # set where the mix churns a table
        self._outputs: dict = {}

    def output_of(self, name: str, version: int = None) -> str:
        """The dataset that holds template ``name``'s answer: that of the
        plan as submitted, or with the churned table's loads stamped with
        ``version``, as the program names its outputs."""
        if (name, version) not in self._outputs:
            from queries import OUTPUT, plan
            from repro.core.plan import rebind_load_versions
            from repro.dataflow.compiler import compile_workflow
            p = plan(name)
            if version is not None:
                p = rebind_load_versions(p, {self.churn.table: version})
            self._outputs[name, version] = compile_workflow(
                p).final_outputs[OUTPUT[name]]
        return self._outputs[name, version]

    def answer_key(self, name: str, versions) -> tuple:
        """(template, data sets the answer may have been computed on,
        data sets only older versions had)."""
        if self.churn is None:
            return name, (0,), ()
        return (name, *self.churn.data_sets(*versions))

    def held_names(self, name: str, versions) -> list:
        """``(artifact, in_range)`` for the artifacts that may hold the
        answer, through the store's aliases: under churn, the outputs of
        the plan stamped with each version it could have read, newest
        first, in range; then that of the plan as submitted, in range
        only where it is one of those.  Names come from ``output_of``'s
        cache, which set-up fills for every version the window can
        reach."""
        names = []
        if self.churn is not None:
            v_submit, v_done = versions
            names = [(self.output_of(name, v), True)
                     for v in range(v_done, v_submit - 1, -1)]
        names.append((self.output_of(name), self.churn is None))
        out: dict = {}
        for n, in_range in names:
            out.setdefault(_resolve(self.svc.store, n), in_range)
        return list(out.items())

    def ask(self, name: str, client: int):
        from queries import OUTPUT, plan
        q = Query(name, client)
        p = plan(name)
        before = set(self.svc.store.names()) if self.delete_outputs else ()
        table = None
        churn = self.churn
        v_submit = churn.done if churn else 0
        q.t_submit = time.perf_counter()
        try:
            with self.rec.span("bench.client.query"):
                ticket = self.svc.submit(p, tenant=f"tenant{client}")
                results, report = ticket.result(timeout=LATE_S * 5)
                q.versions = (v_submit, churn.started if churn else 0)
                table = results[OUTPUT[name]]
                # the client holds the answer's row count: the tables
                # are ready, and the device has done the client's work
                int(self.count_rows(table.valid))
            q.n_executed, q.n_reused = report.n_executed, report.n_reused
        except Exception as e:            # a query that fails is counted
            q.error = f"{type(e).__name__}: {e}"
        q.t_done = time.perf_counter()
        q.latency_s = q.t_done - q.t_submit
        q.spans = self.rec.query(p)
        if table is not None and self.sampler is not None:
            versions = q.versions
            self.sampler.offer(self.answer_key(name, versions), table,
                               lambda: self.held_names(name, versions))
        if self.delete_outputs:
            # the client of a system without reuse removes what the
            # workflow wrote once it has its answer
            for art in set(self.svc.store.names()) - before:
                self.svc.repo.drop_artifact(art)
                self.svc.store.delete(art)
        return q, table


def warm_passes(templates):
    """Set-up's passes over the templates, where the program has the
    hooks they need (``spans``): for the first job of each workflow and
    for the others, whether the flusher's swap of a job's compacted
    output is held back and whether the store is flushed after the job.
    Together they show every program and answer both shapes of what the
    job before wrote.  The first pass also stores what a reuse
    configuration keeps."""
    from queries import plan
    from repro.dataflow.compiler import compile_workflow
    compacted, as_written = (False, True), (True, False)
    multi = [t for t in templates
             if len(compile_workflow(plan(t)).jobs) > 1]
    return [(compacted, compacted, templates),
            (as_written, as_written, templates),
            (compacted, as_written, multi),
            ((True, True), compacted, multi)]


def first_order(mix: dict, seed: int) -> list:
    """The templates in the order client 0's stream first draws them;
    any it has not drawn in 100,000 draws follow in the mix's order."""
    from loadgen import client_stream
    stream, seen = client_stream(mix, seed, 0), {}
    for _ in range(100_000):
        if len(seen) == len(mix["templates"]):
            break
        seen.setdefault(next(stream), None)
    return list(dict.fromkeys([*seen, *mix["templates"]]))


def _device_tables(host: dict):
    from repro.dataflow.table import Table
    return {n: Table.from_numpy(cols) for n, cols in host.items()}


def make_service(config: dict, tables: dict, root: str, dev):
    from repro.service.service import ReStoreService
    from repro.store.artifacts import ArtifactStore, Catalog
    from peaks import peaks
    hbm = (dev.memory_stats() or {}).get("bytes_limit")
    of = f"{hbm} B of device memory"
    if hbm is None:       # a rehearsal backend: budget as on a TPU v5e
        hbm = int(peaks("TPU v5 lite")["hbm_bytes"])
        of = f"{hbm} B, a TPU v5e's HBM ({dev.platform} reports none)"
    store_cfg = config["store"]
    budget = int(hbm * store_cfg["device_cache_share"])
    store = ArtifactStore(root=root, cache_bytes=budget)
    catalog = Catalog(store)
    for name, t in tables.items():
        catalog.register(name, t)
    svc_cfg = config["service"]
    svc = ReStoreService(catalog, store, n_workers=svc_cfg["n_workers"],
                         singleflight=svc_cfg["singleflight"],
                         **config["restore"])
    log(f"store: device-cache budget {budget} B = "
        f"{store_cfg['device_cache_share']} of {of}; service "
        f"{svc_cfg['n_workers']} workers, singleflight "
        f"{svc_cfg['singleflight']}; driver {config['restore']}")
    return svc


def _written_bytes():
    """Bytes this process has caused to be written to storage, where the
    kernel reports it."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def _print_window(run: Run, compiles: CompileEvents, clients: int) -> None:
    by_t: dict = {}
    for q in run.queries:
        t = by_t.setdefault(q.template, [0, 0, 0, 0])
        t[0] += 1
        t[1] += q.n_executed
        t[2] += q.n_reused
        t[3] += q.error is not None
    for name in sorted(by_t):
        n, ex, re_, err = by_t[name]
        log(f"window {name}: {n} queries, jobs executed {ex}, artifacts "
            f"reused {re_}, failed {err}")
    for c in range(clients):
        mine = sorted((q for q in run.queries + run.late if q.client == c),
                      key=lambda q: q.t_submit)
        gaps = [b.t_submit - a.t_done for a, b in zip(mine, mine[1:])]
        if gaps:
            log(f"client {c} lateness (its own time between an answer and "
                f"its next submit): median {np.median(gaps) * 1e3:.6f} ms,"
                f" max {max(gaps) * 1e3:.6f} ms over {len(gaps)} gaps")
    errors: dict = {}
    for q in run.queries + run.late:
        if q.error:
            errors[q.error] = errors.get(q.error, 0) + 1
    for e, n in sorted(errors.items(), key=lambda kv: -kv[1])[:3]:
        log(f"failed queries: {n} x {e[:300]}")
    log(f"compile events inside the window: {compiles.window['programs']}"
        f" ({compiles.window['cache_hits']} from the persistent cache)")


def check_answers(answers: dict, host: dict, limits: dict,
                  reference=None) -> tuple:
    """Compare ``answers`` (template -> [(host columns, covered), ...],
    ``covered`` counting further answers that were the same arrays) with
    the numpy reference over ``host``.  Returns the compared numbers with
    their limits, and how many answers were wrong."""
    from reference import Reference
    ref = reference or Reference(host)
    checks, bad_served, _ = check_versioned(
        {(name, (0,), ()): kept for name, kept in answers.items()},
        lambda d: ref, limits, churn=False)
    return checks, bad_served


def check_versioned(answers: dict, reference_of, limits: dict,
                    churn: bool) -> tuple:
    """Compare ``answers`` (``(template, fresh, older)`` -> [(host
    columns, covered), ...]) with the numpy reference over each data set
    (``reference_of(d)``).  An answer is correct where it equals the
    reference over one of the ``fresh`` data sets, those of the versions
    it could have read; stale where it equals only that of an ``older``
    one; else wrong.  Returns the compared numbers with their limits
    (``stale_answers`` where ``churn``), how many answers served were
    wrong or stale, and the ``(key, index)`` of the stale ones."""
    from queries import FLOAT_COLS
    from reference import compare
    limit = limits["float_rel_err"]
    wants: dict = {}

    def verdict(name, cols, d):
        if (d, name) not in wants:
            wants[d, name] = reference_of(d).answer(name)
        why, err = compare(cols, wants[d, name], FLOAT_COLS.get(name, ()))
        return why, err, why is None and err <= limit

    wrong = bad_served = 0
    stale = set()
    float_err = 0.0
    for key in sorted(answers):
        name, fresh, older = key
        for i, (cols, covered) in enumerate(answers[key]):
            found = [verdict(name, cols, d) for d in fresh]
            ok = [f for f in found if f[2]]
            if ok:
                float_err = max(float_err, ok[0][1])
                continue
            why, err, _ = found[0]
            bad_served += 1 + covered
            was = [d for d in older if verdict(name, cols, d)[2]]
            if was:
                log(f"check {name}: stale, the answer over data set "
                    f"{was[0]}, not over {list(fresh)}")
                stale.add((key, i))
                continue
            if err is not None:
                float_err = max(float_err, err)
            log(f"check {name}: {why or f'float error {err!r}'}")
            wrong += 1
    checks = {"wrong_answers": {"value": wrong, "limit": 0}}
    if churn:
        checks["stale_answers"] = {"value": len(stale), "limit": 0}
    checks["float_rel_err"] = {"value": float_err, "limit": limit}
    return checks, bad_served, stale


def _check(run: Run, sampler: Sampler, host: dict, config: dict,
           churn: Churn = None) -> dict:
    """The numbers compared, each with its limit."""
    from reference import Reference
    answers = {key: [(t.to_numpy(), covered) for t, covered, _ in kept]
               for key, kept in sampler.kept.items()}
    refs: dict = {}

    def reference_of(d: int):
        if d not in refs:
            refs[d] = Reference(host if churn is None else
                                dict(host, **{churn.table: churn.sets[d]}))
        return refs[d]

    t0 = time.perf_counter()
    found, wrong_served, stale = check_versioned(answers, reference_of,
                                                 config["limits"],
                                                 churn is not None)
    run.failed += wrong_served
    log(f"checked {sum(map(len, answers.values()))} answers against the "
        f"numpy reference, covering {sampler.offered} served in the window")
    if churn is not None:
        kept = sorted({d for _, fresh, _ in answers for d in fresh})
        log(f"churn: kept answers could read data sets {kept}; the "
            f"reference over data sets {sorted(refs)} took "
            f"{time.perf_counter() - t0:.6f} s")
    checks = {"failed_queries": {"value": sum(q.error is not None for q in
                                              run.queries + run.late),
                                 "limit": 0}}
    checks.update(found)
    checks["disk_mismatch"] = {"value": _durable_mismatch(sampler, stale),
                               "limit": 0}
    return checks


def _held_differs(held: str, art: str, served: dict):
    """Why the files of ``art`` held under ``held``, read by a store
    opened afresh on them, are not the ``served`` answer; None where
    they are."""
    from reference import compare
    from repro.store.artifacts import ArtifactError, ArtifactStore
    fresh = ArtifactStore(root=held, cache_bytes=0, write_behind=False)
    try:
        disk = fresh.get(art).to_numpy()
    except (ArtifactError, KeyError) as e:
        return f"{art} unreadable on disk: {e!r}"
    finally:
        fresh.close()
    why, _ = compare(disk, served)
    return None if why is None else f"{art} on disk: {why}"


def _durable_mismatch(sampler: Sampler, stale=frozenset()) -> int:
    """Kept answers none of whose artifacts was on disk when the answer
    returned, or whose files as they were then, read by a store opened
    afresh on them, differ from what was served.  An answer judged fresh
    has to be in the output of a version it could have read; one in
    ``stale`` (``(key, index)``, from ``check_versioned``) may be in any
    artifact held for it."""
    bad = n = 0
    for key, kept in sorted(sampler.kept.items()):
        name = key[0]
        for i, (table, _, (held, arts)) in enumerate(kept):
            n += 1
            if (key, i) not in stale:
                arts = [a for a in arts if a[1]]
            on_disk = [a for a, _, h in arts if h]
            if not on_disk:
                log(f"durability {name}: none of {[a for a, *_ in arts]} "
                    f"was on disk when its answer returned")
                bad += 1
                continue
            served = table.to_numpy()
            why = [_held_differs(held, art, served) for art in on_disk]
            if all(why):
                log(f"durability {name}: {'; '.join(why)}")
                bad += 1
    log(f"durability: read {n} kept answers back from the files on disk "
        f"when they returned, {bad} missing or different")
    return bad


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float = None, require_chip: bool = True,
             config: dict = None, mix: dict = None) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``config`` and ``mix`` replace the cell's files (rehearsals)."""
    t_start = time.perf_counter() if t_start is None else t_start
    seed = int(seed) % (1 << 63)
    bench = benchmark()
    cell = cell_of(bench, workload)
    config = config or config_of(bench, cell["config"])
    from loadgen import client_stream, load_mix
    mix = mix or load_mix(mix_path(cell["traffic"]))

    enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(devices) < cell["chips"]):
        raise NoChip(f"{workload} needs {cell['chips']} TPU chip(s); JAX "
                     f"sees {len(devices)} {dev.platform} device(s)")
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {COMPILE_CACHE_DIR}")
    compiles = CompileEvents()

    import datagen
    from peaks import peaks
    from spans import KERNELS, Recorder, instrument, kernel_bytes
    host = datagen.tables(config, seed)
    churn_spec = mix.get("churn")
    sets = None
    if churn_spec:
        churned = churn_spec["table"]
        sets = [host[churned]] + [
            datagen.DATA_SETS[churned](config, seed, d)
            for d in range(1, churn_spec["data_sets"])]
    tables = _device_tables(host)
    run = Run()
    run.base_bytes = sum(t.nbytes() for t in tables.values())
    pv_bytes = config["page_views_rows"] * datagen.row_bytes(
        host["page_views"])
    n_rows = {n: len(next(iter(c.values()))) for n, c in host.items()}
    log(f"data: page_views {n_rows['page_views']} rows of "
        f"{datagen.row_bytes(host['page_views'])} B, {pv_bytes} B, 1/"
        f"{PAPER_PAGE_VIEWS_BYTES / pv_bytes:.6f} of the paper's 15 GB "
        f"PigMix set; users {n_rows['users']} rows, power_users "
        f"{n_rows['power_users']} rows (seed {seed})")

    root = tempfile.mkdtemp(prefix="bench-store-")
    held_root = tempfile.mkdtemp(prefix="bench-held-")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = Recorder(annotate=trace)
    undo = instrument(rec)
    svc = churn = None
    try:
        svc = make_service(config, tables, root, dev)
        client = Client(svc, rec, _count_rows(),
                        config["client"]["delete_outputs"])
        warm_failed = 0
        passes = (warm_passes(mix["templates"]) if rec.shapes_policy
                  else [(None, None, mix["templates"])] * 2)
        for first, rest, names in passes:
            rec.job_policy = (None if first is None else
                              lambda i, f=first, r=rest: f if i == 0 else r)
            for name in names:
                q, _ = client.ask(name, 0)
                warm_failed += q.error is not None
                if q.error:
                    log(f"set-up {name}: {q.error}")
        rec.job_policy, rec.hold_swaps = None, False
        if churn_spec:
            # one churn, then every template after it in the order the
            # mix first draws them and reversed: the programs a reload
            # brings are compiled here, not in the window
            churn = client.churn = Churn(churn_spec, svc, sets)
            churn.apply()
            order = first_order(mix, seed)
            for name in order + order[::-1]:
                q, _ = client.ask(name, 0)
                warm_failed += q.error is not None
                if q.error:
                    log(f"set-up after a churn {name}: {q.error}")
        for name in mix["templates"]:
            client.output_of(name)
            if churn is not None:
                for v in range(churn.first, churn.last_version(seconds) + 1):
                    client.output_of(name, v)
        n_clients = int(mix["clients"])
        client.sampler = Sampler(seed, int(config["client"]["answers_kept"]),
                                 root, held_root)
        io0 = svc.store.io_stats()
        loop = loop_module(mix["loop"])
        if trace:
            jax.profiler.start_trace(trace_dir)

        lock = threading.Lock()
        deadline = [None]

        def record(q) -> None:
            with lock:
                run.attempted += 1
                (run.queries if q.t_done <= deadline[0]
                 else run.late).append(q)
                run.failed += q.error is not None

        def drive(c: int) -> None:
            loop.drive(client.ask, client_stream(mix, seed, c), c,
                       deadline[0], record)

        threads = [threading.Thread(target=drive, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        run.setup_s = t0 - t_start
        deadline[0] = t0 + seconds
        rec.in_window = compiles.in_window = True
        with rec.span("bench.window"):
            if churn is not None:
                churn.start(t0, deadline[0])
            for t in threads:
                t.start()
            time.sleep(max(0.0, deadline[0] - time.perf_counter()))
        rec.in_window = compiles.in_window = False
        run.window_s = time.perf_counter() - t0
        run.stored_bytes = svc.store.total_bytes()
        if churn is not None:
            churn.stop()
            if churn.error is not None:
                raise RuntimeError(f"churn of {churn.table}: "
                                   f"{churn.error!r}") from churn.error
        for t in threads:
            t.join(LATE_S)
        if any(t.is_alive() for t in threads):
            raise RuntimeError(f"a client's answer did not come within "
                               f"{LATE_S} s of the window's end")
        if trace:
            jax.profiler.stop_trace()
        run.store_io = {k: v - io0[k]
                        for k, v in svc.store.io_stats().items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)}
        run.kernel_bytes = kernel_bytes(rec)
        run.job_bytes = rec.job_bytes
        svc.stop()
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"peak_bytes_in_use: {peak}")
        log(f"peak host RSS: "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
        log(f"set-up: {compiles.setup['programs']} programs compiled or "
            f"loaded ({compiles.setup['cache_hits']} from the persistent "
            f"cache) in {compiles.setup['seconds']:.6f} s; set-up failures "
            f"{warm_failed}")
        _print_window(run, compiles, n_clients)
        if churn is not None:
            log(f"churn: {churn.applied} re-registrations of {churn.table} "
                f"in the window, {len(churn.seconds) - churn.applied} in "
                f"set-up; upload and registration median "
                f"{np.median(churn.seconds) * 1e3:.6f} ms, max "
                f"{max(churn.seconds) * 1e3:.6f} ms; catalog version "
                f"{churn.done} at the window's end")
        if trace:
            import trace as trace_mod
            run.trace = trace_mod.reduce(trace_mod.load(trace_dir), KERNELS)
        try:
            run.peaks = peaks(dev.device_kind)
        except KeyError:
            run.peaks = None
        checks = _check(run, client.sampler, host, config, churn)
        checks["failed_queries"]["value"] += warm_failed
        if compiles.window["programs"]:
            log("WARNING: programs compiled inside the window")
        log(f"disk: this process wrote {_written_bytes()} B")
    finally:
        if churn is not None:
            churn.stop()
        if svc is not None:
            svc.stop(timeout=LATE_S)
        undo()
        compiles.close()
        shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(held_root, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = metric_module(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks.values()),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if trace and run.trace and run.trace["busy_s"] is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None, t_start: float = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
