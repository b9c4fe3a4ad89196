"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time, the top device operations and the idle gaps by host span.

Device events are those of the ``XLA Ops`` line of each ``/device:TPU:n``
plane.  Busy time is the union of their intervals inside the window that
the harness marks with a ``bench.window`` annotation, averaged over the
chips.  Each event's name is the text of the HLO instruction it ran; a
kernel's time is the summed duration of the events whose instruction is
named after the kernel.  Idle gaps are labelled with the
innermost ``bench.`` span open on any host thread at the gap's middle.
"""
from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10
OP_NAME_CHARS = 120


def load(trace_dir: str):
    """The ``ProfileData`` of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float):
    """The ``(start, end)`` stretches of ``[lo, hi]`` that no interval
    covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label_points(spans, points):
    """For each point, the name of the shortest ``(start, end, name)``
    span that contains it, or "no span"."""
    import heapq
    order = sorted(range(len(points)), key=points.__getitem__)
    spans = sorted(spans)
    out = ["no span"] * len(points)
    heap, i = [], 0
    for j in order:
        t = points[j]
        while i < len(spans) and spans[i][0] <= t:
            s, e, name = spans[i]
            heapq.heappush(heap, (e - s, e, name))
            i += 1
        # spans that ended before this point ended before every later one
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        if heap:
            out[j] = heap[0][2]
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event ran: its event name is
    the instruction's text, ``%name = shape op(operands), ...``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def _runs_kernel(event_name: str, kernel: str) -> bool:
    """A Pallas kernel's custom call is the instruction named after the
    kernel (``join_probe``, ``join_probe.1``); other instructions name
    it only as an operand."""
    name = op_name(event_name)
    return name == kernel or name.startswith(kernel + ".")


def reduce(pd, kernels=()) -> dict:
    """Busy and window seconds, per-kernel device seconds, the top device
    operations and the idle gaps by host span.  ``busy_s`` is None where
    the trace holds no device plane."""
    window = None
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(list(_events(line)))
            continue
        for line in plane.lines:
            for name, s, d in _events(line):
                if name == WINDOW:
                    window = (s, s + d)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((s, s + d, name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = window
    out = {"window_s": (hi - lo) * 1e-9, "busy_s": None,
           "kernel_s": {}, "device_ops": [], "idle_gaps": []}
    if not devices:
        return out
    busy = 0.0
    by_op: dict = {}
    kernel_ns = {k: 0.0 for k in kernels}
    all_gaps = []
    for events in devices:
        inside = [(s, s + d) for _, s, d in events
                  if s + d > lo and s < hi]
        busy += union_ns(inside, lo, hi)
        all_gaps.extend(gaps_ns(inside, lo, hi))
        for name, s, d in events:
            if s + d <= lo or s >= hi:
                continue
            d = min(s + d, hi) - max(s, lo)
            key = name[:OP_NAME_CHARS]   # instruction and result shape
            by_op[key] = by_op.get(key, 0.0) + d
            for k in kernels:
                if _runs_kernel(name, k):
                    kernel_ns[k] += d
    n = len(devices)
    out["busy_s"] = busy / n * 1e-9
    out["kernel_s"] = {k: v / n * 1e-9 for k, v in kernel_ns.items()}
    out["device_ops"] = [[k, v / n * 1e-9] for k, v in sorted(
        by_op.items(), key=lambda kv: -kv[1])[:TOP]]
    by_label: dict = {}
    for (s, e), label in zip(all_gaps, label_points(
            spans, [(s + e) / 2 for s, e in all_gaps])):
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    out["idle_gaps"] = [[k, v / n * 1e-9] for k, v in sorted(
        by_label.items(), key=lambda kv: -kv[1])[:TOP]]
    return out
