"""95th percentile, per query that a driver ran, of the driver's own
time: time inside ``ReStore.run`` less the time inside ``Engine.run_job``
and the store's ``put`` and ``flush`` (harness spans), in ms.  In the
reuse cell every windowed job takes the driver's whole-job fast path
(the store holds the job's outputs by name), so this is that path:
compiling the workflow, the name probe and the repository's use record.
Plan matching and rewriting do not run in the window.  Layer: driver.
Moves: query_p95_ms.restore."""
import numpy as np


def read(run):
    ms = [1e3 * (q.spans["driver_s"] - q.spans.get("engine_s", 0.0)
                 - q.spans.get("store_write_s", 0.0))
          for q in run.queries if q.error is None and "driver_s" in q.spans]
    return float(np.percentile(ms, 95)) if ms else None
