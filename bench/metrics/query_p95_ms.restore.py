"""``query_p95_s`` of the reuse cell in ms, under a name of its own so
that its bound follows its own spread."""
from readers import latency_p95_s


def read(run):
    s = latency_p95_s(run)
    return None if s is None else 1e3 * s
