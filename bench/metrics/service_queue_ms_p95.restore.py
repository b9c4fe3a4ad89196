"""95th percentile, over the window's requests that were queued, of
their time in the service's queue, from the enqueue in ``submit`` until
a worker takes the ticket (program span ``restore.service.queue``), in
ms.  Singleflight followers join their leader and are not queued.
Layer: service.  Moves: queries_per_s.restore."""
from program_spans import ms_p95


def read(run):
    return ms_p95(run, "restore.service.queue")
