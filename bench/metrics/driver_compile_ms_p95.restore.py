"""95th percentile per request of the driver's compile of the query
into a workflow of jobs (``as_plan`` and ``compile_workflow``; program
span ``restore.driver.compile``), in ms.  Layer: driver.  Moves:
query_p95_ms.restore."""
from program_spans import ms_p95


def read(run):
    return ms_p95(run, "restore.driver.compile")
