"""Harness spans around the calls into each layer of the program.

The program has no spans of its own, so the benchmark wraps the public
methods through which one layer calls the next, for the length of one
run:

    bench.driver      ReStore.run          (service -> driver)
    bench.engine      Engine.run_job       (driver -> engine)
    bench.store.put   ArtifactStore.put    (engine -> store)
    bench.store.flush ArtifactStore.flush  (driver -> store durability)

Every span is timed on the host clock and summed into the record of the
query whose driver call is running on that thread.  In a traced run each
span is also a ``jax.profiler.TraceAnnotation``, so that device idle
gaps can be labelled with what the host was doing.

Three hooks reach inside the program, and each is optional: where the
program no longer has what a hook wraps, the hook is left out and the
run goes on without it.

- Set-up only: the store's write-behind flusher swaps a job's compacted
  output into the device cache while the workflow's next job, or the
  gathering of the answer, may already be reading it, so each sees one
  of two shapes.  Set-up warms every combination by a policy that says,
  for each job of a workflow by its index, whether the swap is held back
  (``DeviceCache.swap_if``) and whether the store is flushed after the
  job (``ReStore._process_job``).  Without them set-up runs its passes
  as they come, and a window may compile.
- The kernel byte counts: the job programs the engine runs
  (``Engine._jitted``) and the shapes of the calls to the kernels' entry
  points, which are seen while a job program is traced.  Without them
  the kernels' roofline readers find nothing and report nothing.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax

KERNELS = ("segment_sum_sorted", "join_probe")

# The kernel calls seen the last time each job program was traced, by
# id of the program, which the entry keeps alive.  A program is traced
# once per process and input shape (the program's jit cache lives as
# long as the process), so these records must live as long too.
_TRACED_KERNELS: dict = {}


class Recorder:
    """Per-query span sums and window counters of one run."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.in_window = False
        self._tls = threading.local()
        self._lock = threading.Lock()
        self.queries: dict = {}     # id(plan) -> span sums of its run
        self.program_runs: dict = {}  # program -> runs in the window
        self.job_bytes = 0            # bytes in + out of window jobs
        # set-up only: job index -> (hold_swaps, flush_after), see above;
        # shapes_policy says whether the program has what it needs
        self.job_policy = None
        self.hold_swaps = False
        self.shapes_policy = False

    @contextlib.contextmanager
    def span(self, name: str, **kw):
        if self.annotate:
            with jax.profiler.TraceAnnotation(name, **kw):
                yield
        else:
            yield

    def query(self, plan) -> dict:
        """The span sums of the driver run of ``plan`` (empty where no
        driver ran it, as for a singleflight follower)."""
        with self._lock:
            return self.queries.pop(id(plan), {})

    def _add(self, key: str, dt: float) -> None:
        q = getattr(self._tls, "query", None)
        if q is not None:
            q[key] = q.get(key, 0.0) + dt


def _timed(rec: Recorder, name: str, key: str):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with rec.span(name):
                    return fn(*args, **kwargs)
            finally:
                rec._add(key, time.perf_counter() - t0)
        return wrapper
    return deco


def instrument(rec: Recorder):
    """Wrap the layer boundaries for ``rec``; returns the undo."""
    from repro.core.restore import ReStore
    from repro.dataflow.executor import Engine
    from repro.kernels.hash_join import ops as join_ops
    from repro.kernels.segment_reduce import ops as seg_ops
    from repro.store.artifacts import ArtifactStore, DeviceCache

    saved = []

    def patch(owner, attr, new):
        saved.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    run = ReStore.run

    @functools.wraps(run)
    def driver_run(self, query):
        if getattr(rec._tls, "query", None) is not None:
            return run(self, query)           # nested: the outer run owns
        q = rec._tls.query = {}
        t0 = time.perf_counter()
        try:
            with rec.span("bench.driver"):
                return run(self, query)
        finally:
            q["driver_s"] = time.perf_counter() - t0
            rec._tls.query = None
            with rec._lock:
                rec.queries[id(query)] = q

    patch(ReStore, "run", driver_run)
    patch(ArtifactStore, "put",
          _timed(rec, "bench.store.put", "store_write_s")(ArtifactStore.put))
    patch(ArtifactStore, "flush",
          _timed(rec, "bench.store.flush", "store_write_s")(
              ArtifactStore.flush))

    process_job = getattr(ReStore, "_process_job", None)
    swap_if = getattr(DeviceCache, "swap_if", None)
    rec.shapes_policy = process_job is not None and swap_if is not None
    if rec.shapes_policy:
        @functools.wraps(process_job)
        def driver_job(self, job):
            if rec.job_policy is None:
                return process_job(self, job)
            q = rec._tls.query
            i = q["jobs"] = q.get("jobs", 0) + 1
            rec.hold_swaps, flush_after = rec.job_policy(i - 1)
            out = process_job(self, job)
            if flush_after:
                self.store.flush()
            return out

        @functools.wraps(swap_if)
        def cache_swap_if(self, *args, **kwargs):
            if not rec.hold_swaps:
                swap_if(self, *args, **kwargs)

        patch(ReStore, "_process_job", driver_job)
        patch(DeviceCache, "swap_if", cache_swap_if)

    run_job = Engine.run_job

    @functools.wraps(run_job)
    def engine_run_job(self, job, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            with rec.span("bench.engine"):
                outputs, stats = run_job(self, job, *args, **kwargs)
        finally:
            rec._add("engine_s", time.perf_counter() - t0)
        if rec.in_window:
            with rec._lock:
                rec.job_bytes += stats.bytes_in + stats.bytes_out
        return outputs, stats

    patch(Engine, "run_job", engine_run_job)

    jitted = getattr(Engine, "_jitted", None)

    @functools.wraps(jitted)
    def engine_jitted(self, *args, **kwargs):
        got = jitted(self, *args, **kwargs)
        if not (isinstance(got, tuple) and got and callable(got[0])):
            return got               # not the program this hook knows
        fn = got[0]

        def call(*a, **k):
            rec._tls.kernels = traced = []
            try:
                return fn(*a, **k)
            finally:
                rec._tls.kernels = None
                if traced:           # this call traced the program
                    _TRACED_KERNELS[id(fn)] = (fn, traced)
                if rec.in_window:
                    with rec._lock:
                        rec.program_runs[id(fn)] = \
                            rec.program_runs.get(id(fn), 0) + 1
        return (call,) + got[1:]

    if jitted is not None:
        patch(Engine, "_jitted", engine_jitted)

    def kernel_entry(module, attr, kernel, shape_of):
        entry = getattr(module, attr, None)
        if entry is None:
            return

        @functools.wraps(entry)
        def wrapper(*args, **kwargs):
            traced = getattr(rec._tls, "kernels", None)
            if kwargs.get("impl") == "pallas" and traced is not None:
                traced.append((kernel, shape_of(*args, **kwargs)))
            return entry(*args, **kwargs)
        patch(module, attr, wrapper)

    kernel_entry(seg_ops, "segment_sum", "segment_sum_sorted",
                 lambda values, seg_ids, num_segments, **_: (
                     int(values.shape[0]), int(values.shape[1]),
                     int(num_segments)))
    kernel_entry(join_ops, "probe", "join_probe",
                 lambda left, right, **_: (int(left.shape[0]),
                                           int(right.shape[0])))

    def undo():
        for owner, attr, old in reversed(saved):
            setattr(owner, attr, old)
    return undo


def kernel_bytes(rec: Recorder) -> dict:
    """Least bytes of each kernel's logical work over the window's runs
    of job programs (``counts``), by kernel name."""
    from counts import join_probe_bytes, segment_sum_bytes
    per = {"segment_sum_sorted": lambda s: segment_sum_bytes(*s),
           "join_probe": lambda s: join_probe_bytes(*s)}
    out = {}
    for pid, runs in rec.program_runs.items():
        for kernel, shape in _TRACED_KERNELS.get(pid, (None, []))[1]:
            out[kernel] = out.get(kernel, 0) + runs * per[kernel](shape)
    return out
