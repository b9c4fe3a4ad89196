"""Per cent of the bytes of artifact loads in the window that the device
cache served, from the store's tier-tagged IO counters
(``ArtifactStore.io_stats()``: memload against hostload, load and
remoteload).  Layer: store.  Moves: query_p95_ms.restore."""


def read(run):
    io = run.store_io
    tiers = ("memload_bytes", "hostload_bytes", "load_bytes",
             "remoteload_bytes")
    total = sum(io.get(k, 0) for k in tiers)
    if total <= 0:
        return None
    return 100.0 * io.get("memload_bytes", 0) / total
