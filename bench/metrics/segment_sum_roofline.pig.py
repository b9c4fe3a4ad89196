"""Per cent of its HBM roofline that the sorted segment sum
(``kernels/segment_reduce``) reaches: the least bytes of the operator's
logical work over the window's job runs (``counts.segment_sum_bytes``,
from the shapes of its calls) at the chip's HBM bandwidth, over the
device time of the ``segment_sum_sorted`` events.  Layer: kernels.
Moves: queries_per_s."""
from counts import roofline_share

KERNEL = "segment_sum_sorted"


def read(run):
    if not run.trace or run.peaks is None:
        return None
    return roofline_share(run.kernel_bytes.get(KERNEL, 0),
                          run.trace["kernel_s"].get(KERNEL, 0.0),
                          run.peaks["hbm_bw"])
