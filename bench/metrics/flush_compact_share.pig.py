"""Per cent of the summed query latency that the store's flusher thread
spent in one step of its writes, the numpy mask-and-pad compaction to
the stored capacity (program span ``restore.store.flush.compact``).  The
flusher runs beside the engine, so this is flusher time, not time on the
critical path; its base is that of ``store_write_share.pig``.  Layer:
store.  Moves: query_p95_s."""
from program_spans import share


def read(run):
    return share(run, "restore.store.flush.compact")
