"""Program spans (``repro.obs``): recorded only under a profiler
session, nested by thread, carried by request id from a service worker
to the store's flusher thread, and written into the profiler's trace."""
import collections
import contextlib
import glob
import os
import time

import jax

from repro import obs
from repro.core.repository import Repository
from repro.service.service import ReStoreService
from repro.store.artifacts import ArtifactStore, Catalog
from repro.workloads import pigmix

# the spans the benchmark's per-layer metrics read
READ_BY_METRICS = (
    "restore.store.flush.fetch", "restore.store.flush.compact",
    "restore.store.flush.encode", "restore.store.flush.write",
    "restore.store.flush.upload", "restore.engine.stats",
    "restore.service.queue", "restore.driver.compile",
    "restore.driver.reuse")


@contextlib.contextmanager
def traced(trace_dir):
    """A profiler session around the block; yields the records the
    program's spans wrote in it."""
    got = []
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield got
    finally:
        jax.profiler.stop_trace()
        got.extend(r for r in obs.spans() if r[1] >= t0)


def trace_names(trace_dir) -> set:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(paths[0])
    return {e.name for plane in pd.planes for line in plane.lines
            for e in line.events}


def test_nothing_is_recorded_without_a_profiler_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("restore.test.off"):
        pass
    assert obs.begin("restore.test.off") is None
    assert all(r[0] != "restore.test.off" for r in obs.spans())


def test_nested_spans_have_parents_and_self_time(tmp_path):
    with traced(tmp_path) as got:
        with obs.request(7):
            with obs.span("restore.test.outer"):
                time.sleep(0.02)
                with obs.span("restore.test.inner"):
                    time.sleep(0.01)
            with obs.span("restore.test.after"):
                pass
    by = {r[0]: r for r in got}
    outer, inner = by["restore.test.outer"], by["restore.test.inner"]
    after = by["restore.test.after"]
    assert outer[4] is None and inner[4] == outer[3] and after[4] is None
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    self_s = (outer[2] - outer[1]) - (inner[2] - inner[1])
    assert self_s >= 0.02 and inner[2] - inner[1] >= 0.01
    assert {r[5] for r in (outer, inner, after)} == {7}
    assert {"restore.test.outer", "restore.test.inner"} <= \
        trace_names(tmp_path)


def test_ring_keeps_the_newest_and_counts_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=3))
    monkeypatch.setattr(obs, "_dropped", 0)
    with traced(tmp_path) as got:
        for i in range(5):
            with obs.span(f"restore.test.s{i}"):
                pass
    assert [r[0] for r in got] == [f"restore.test.s{i}" for i in (2, 3, 4)]
    assert obs.dropped() == 2
    assert obs.CAPACITY >= 200_000


def test_service_run_emits_the_spans_the_metrics_read(tmp_path):
    store = ArtifactStore(root=str(tmp_path / "store"))
    cat = Catalog(store)
    pigmix.register_all(cat, n_rows=512)
    svc = ReStoreService(cat, store, Repository(), n_workers=2)
    try:
        svc.run(pigmix.L2(), timeout=300)       # compiles off the trace
        with traced(tmp_path / "trace") as got:
            svc.run(pigmix.L3("sum"), timeout=300)  # executes, flushes
            svc.run(pigmix.L3("sum"), timeout=300)  # whole-job reuse
    finally:
        svc.stop()
    names = {r[0] for r in got}
    assert set(READ_BY_METRICS) <= names, set(READ_BY_METRICS) - names
    assert obs.dropped() == 0
    # each request's spans carry its ticket id, on the worker and on the
    # flusher thread that wrote what the request's jobs put
    executed = {r[5] for r in got if r[0] == "restore.service.execute"}
    assert len(executed) == 2 and None not in executed
    flusher = [r for r in got if r[0].startswith("restore.store.flush.")
               and r[0] != "restore.store.flush.wait"]
    assert flusher and {r[6] for r in flusher} == {"artifact-flusher"}
    assert {r[5] for r in flusher} <= executed
    queued = {r[5] for r in got if r[0] == "restore.service.queue"}
    assert queued == executed
    assert {"restore.service.execute", "restore.store.flush.fetch",
            "restore.service.queue"} <= trace_names(tmp_path / "trace")
