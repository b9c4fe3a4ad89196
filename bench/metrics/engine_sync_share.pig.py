"""Per cent of the summed query latency that the engine spent reading
each job's row and overflow counts to the host after the job
(``num_valid``, ``rows_out``, overflow; program span
``restore.engine.stats``): device round trips on the critical path.
Layer: engine.  Moves: queries_per_s."""
from program_spans import share


def read(run):
    return share(run, "restore.engine.stats")
