"""95th percentile per request of the driver's whole-job fast path,
summed over the request's jobs: the store's ``exists`` probes, the scan
of the repository's entries, ``record_use`` and the run's pins (program
span ``restore.driver.reuse``, one per job), in ms.  Layer: driver.
Moves: query_p95_ms.restore."""
from program_spans import ms_p95


def read(run):
    return ms_p95(run, "restore.driver.reuse")
