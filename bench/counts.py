"""The least bytes each kernel's logical work moves, from its shapes.

These are the operator's work, not the kernel's: what any implementation
must read and write at least once.  A roofline share is then the time
those bytes take at the chip's HBM bandwidth over the time the kernel's
events took on the device, and cannot pass 100% unless the bytes are
counted too high or the time leaves out part of the work.
"""
from __future__ import annotations

F32 = 4
I32 = 4
U32 = 4


def segment_sum_bytes(n_rows: int, n_cols: int, n_segments: int) -> int:
    """Sorted segment sum of ``n_rows`` rows of ``n_cols`` float32 values
    into ``n_segments`` segments: one int32 segment id and the values of
    each row read, each segment's values written."""
    return n_rows * (I32 + n_cols * F32) + n_segments * n_cols * F32


def join_probe_bytes(n_probe: int, n_build: int) -> int:
    """Probe of ``n_probe`` uint32 key hashes against ``n_build`` sorted
    build hashes: both key lanes read, one int32 position per probe row
    written."""
    return n_probe * U32 + n_build * U32 + n_probe * I32


def roofline_share(n_bytes: float, seconds: float, hbm_bw: float):
    """Per cent of the HBM roofline; None where nothing was timed."""
    if seconds <= 0 or n_bytes <= 0:
        return None
    return 100.0 * (n_bytes / hbm_bw) / seconds
