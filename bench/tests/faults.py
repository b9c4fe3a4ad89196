"""Faults planted in the program for the benchmark's own tests: every
answer altered where the engine produces it, and the durability flush
skipped."""
import contextlib

import jax.numpy as jnp


def _alter(table):
    """The first valid row of every column changed: strings get a bit
    flipped, integers one added, floats scaled by 1.01."""
    from repro.dataflow.table import Table
    i = jnp.argmax(table.valid)
    cols = {}
    for name, c in table.columns.items():
        if c.ndim == 2:
            c = c.at[i, 0].set(c[i, 0] ^ 1)
        elif jnp.issubdtype(c.dtype, jnp.floating):
            c = c.at[i].multiply(1.01)
        else:
            c = c.at[i].add(1)
        cols[name] = c
    return Table(cols, table.valid)


@contextlib.contextmanager
def altered_answers(monkeypatch):
    from repro.dataflow import executor
    execute_plan = executor.execute_plan

    def faulty(*args, **kwargs):
        outputs, stats = execute_plan(*args, **kwargs)
        return {n: _alter(t) for n, t in outputs.items()}, stats

    # programs traced before the fault would run without it
    executor.GLOBAL_JIT_CACHE.clear()
    monkeypatch.setattr(executor, "execute_plan", faulty)
    try:
        yield
    finally:
        monkeypatch.setattr(executor, "execute_plan", execute_plan)
        executor.GLOBAL_JIT_CACHE.clear()


@contextlib.contextmanager
def deferred_flush(monkeypatch, delay_s: float = 0.3):
    """The durability flush skipped: ``flush`` returns at once and the
    write-behind flusher writes each artifact ``delay_s`` late, so an
    answer can return before its output is on disk."""
    import time

    from repro.store.artifacts import ArtifactStore
    write = ArtifactStore._write_to_disk

    def late_write(self, *args, **kwargs):
        time.sleep(delay_s)
        return write(self, *args, **kwargs)

    monkeypatch.setattr(ArtifactStore, "flush", lambda self: None)
    monkeypatch.setattr(ArtifactStore, "_write_to_disk", late_write)
    try:
        yield
    finally:
        monkeypatch.undo()
