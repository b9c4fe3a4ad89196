"""Arithmetic shared by the metric readers of ``metrics/``."""
import numpy as np


def latency_p95_s(run):
    """95th percentile of the latency of every query completed in the
    window, from submit until its answer is ready and counted (host
    clock), in seconds."""
    lat = [q.latency_s for q in run.queries if q.error is None]
    return float(np.percentile(lat, 95)) if lat else None


def completed_per_s(run):
    """Queries completed in the window over the window's seconds (host
    clock)."""
    done = sum(q.error is None for q in run.queries)
    return done / run.window_s if done else None
