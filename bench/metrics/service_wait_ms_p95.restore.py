"""95th percentile per query of its latency less the time inside the
driver (``ReStore.run``, harness span): admission, queueing, the hand-off
between threads and singleflight waits, in ms.  Layer: service.  Moves:
queries_per_s."""
import numpy as np


def read(run):
    ms = [1e3 * (q.latency_s - q.spans.get("driver_s", 0.0))
          for q in run.queries if q.error is None]
    return float(np.percentile(ms, 95)) if ms else None
