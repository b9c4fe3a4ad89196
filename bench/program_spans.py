"""The program's own spans (``repro.obs``) over a run's window, for the
metric readers of ``metrics/``.

The program records a span only while a profiler session is active, so
only traced runs have any.  The window runs from the first submit to the
last answer of the queries completed in it (host clock, the clock of the
spans).  A span counts where it lies wholly inside the window.  Where
the program records no spans, or its ring dropped some, every reader
here returns None.
"""
import numpy as np


def window(run):
    """``(name, t0, t1, span_id, parent_id, request_id, thread)`` records
    of the spans inside the window, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    if not run.queries or obs.dropped() > 0:
        return None
    lo = min(q.t_submit for q in run.queries)
    hi = max(q.t_done for q in run.queries)
    return [r for r in obs.spans() if r[1] >= lo and r[2] <= hi]


def share(run, name):
    """Per cent of the summed latency of the window's completed queries
    that spans ``name`` took, counted on whatever thread they ran."""
    records = window(run)
    total = sum(q.latency_s for q in run.queries if q.error is None)
    if records is None or total <= 0:
        return None
    seconds = [r[2] - r[1] for r in records if r[0] == name]
    return 100.0 * sum(seconds) / total if seconds else None


def per_request(run, name) -> dict:
    """Request id -> seconds of spans ``name`` of that request in the
    window, or None."""
    records = window(run)
    if records is None:
        return None
    out: dict = {}
    for r in records:
        if r[0] == name:
            out[r[5]] = out.get(r[5], 0.0) + (r[2] - r[1])
    return out


def ms_p95(run, name):
    """95th percentile over requests of their seconds in spans ``name``,
    in ms; None where no request has one."""
    by = per_request(run, name)
    if not by:
        return None
    return float(np.percentile([1e3 * s for s in by.values()], 95))
