"""Program spans on the profiler's clock.

A span names one step of one layer's work (``restore.<layer>.<step>``).
It records only while a profiler session is active.  Then it opens a
``jax.profiler.TraceAnnotation``, so that the span lies in the same
trace as the device's operations, on the same clock, and it appends a
record to a bounded in-memory ring:

    (name, t0, t1, span_id, parent_id, request_id, thread)

``t0`` and ``t1`` are ``time.perf_counter`` seconds.  The parent is the
span open on the same thread when this one opened; the request is the
one set for the thread by ``request``.  With no profiler session a span
costs one check and an empty context manager, and records nothing.

A span makes as few Python calls as it can (``span``, ``_Span.__init__``,
``__enter__``, ``__exit__``): a profiler session with its Python tracer
on records each of them too.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

import jax

# records kept: a traced 51 s window of the reuse cell writes about
# 200k, and an untraced rate with spans on would write about 600k
CAPACITY = 1 << 20

_enabled = jax.profiler.TraceAnnotation.is_enabled
_Note = jax.profiler.TraceAnnotation
_clock = time.perf_counter
_ids = itertools.count(1)
_tls = threading.local()
_OFF = contextlib.nullcontext()
_lock = threading.Lock()
# the newest records, and a count of those dropped to make room
_ring: "collections.deque[tuple]" = collections.deque(maxlen=CAPACITY)
_dropped = 0


class _Span:
    __slots__ = ("name", "request", "thread", "stack", "id", "parent",
                 "note", "t0")

    def __init__(self, name: str, request, attrs: dict, stack, thread):
        self.name, self.request = name, request
        self.stack, self.thread = stack, thread
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        if request is not None:
            attrs["request"] = request
        self.note = _Note(name, **attrs)
        self.note.__enter__()
        if stack is not None:
            stack.append(self.id)
        self.t0 = _clock()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        global _dropped
        t1 = _clock()
        if self.stack is not None:
            self.stack.pop()
        self.note.__exit__(None, None, None)
        record = (self.name, self.t0, t1, self.id, self.parent,
                  self.request, self.thread)
        with _lock:
            if len(_ring) == _ring.maxlen:
                _dropped += 1
            _ring.append(record)

    def end(self) -> None:
        self.__exit__(None, None, None)


def span(name: str, **attrs):
    """``with span(name):`` times the block as a span of the request set
    for this thread, nested in the span open around it."""
    if not _enabled():
        return _OFF
    t = _tls
    stack = getattr(t, "stack", None)
    if stack is None:
        stack = t.stack = []
        t.thread = threading.current_thread().name
    return _Span(name, getattr(t, "request", None), attrs, stack, t.thread)


def begin(name: str, request=None, **attrs):
    """A span that another thread ends with ``.end()``, such as a
    request's wait in a queue; it has no parent and is no parent, and
    its thread is the one that opened it.  None while no profiler
    session is active."""
    if not _enabled():
        return None
    return _Span(name, request, attrs, None,
                 threading.current_thread().name)


class request:
    """``with request(id):`` the spans this thread opens inside carry
    ``id``."""

    __slots__ = ("id", "_saved")

    def __init__(self, rid):
        self.id = rid

    def __enter__(self):
        self._saved = getattr(_tls, "request", None)
        _tls.request = self.id
        return self

    def __exit__(self, *exc):
        _tls.request = self._saved


def request_id():
    """The request set for this thread, or None."""
    return getattr(_tls, "request", None)


def spans() -> list:
    """The records kept, oldest first."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """Records dropped because the ring was full."""
    return _dropped
