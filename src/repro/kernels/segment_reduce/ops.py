"""Jit'd wrapper with impl dispatch.  ``segment_sum`` accepts ANY row
count: the kernel pads to whole tiles with out-of-range segment ids,
which it drops exactly as the ref masks them."""
from ... import kernels
from .ref import segment_sum_ref
from .segment_reduce import segment_sum_sorted


def segment_sum(values, seg_ids, *, num_segments: int, impl: str = "ref",
                tile_n: int = 1024, interpret=None):
    if impl == "pallas":
        return segment_sum_sorted(
            values, seg_ids, num_segments=num_segments, tile_n=tile_n,
            interpret=kernels.resolve_interpret(interpret))
    return segment_sum_ref(values, seg_ids, num_segments=num_segments)
