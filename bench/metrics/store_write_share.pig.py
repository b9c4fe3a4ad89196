"""Per cent of the summed query latency spent inside
``ArtifactStore.put`` and ``ArtifactStore.flush`` (harness spans).
Layer: store.  Moves: query_p95_s."""


def read(run):
    qs = [q for q in run.queries if q.error is None]
    total = sum(q.latency_s for q in qs)
    if total <= 0:
        return None
    return 100.0 * sum(q.spans.get("store_write_s", 0.0) for q in qs) / total
