"""Bytes the artifact store holds at the window's end, all tiers, base
tables excluded, over the bytes of the base tables: the storage that
reuse is traded against (paper Fig. 11).  Layer: store.  Moves:
query_p95_ms.restore, once the bytes pass the device cache's budget and
loads fall to the disk tier."""


def read(run):
    if not run.stored_bytes or not run.base_bytes:
        return None
    return run.stored_bytes / run.base_bytes
