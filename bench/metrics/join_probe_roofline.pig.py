"""Per cent of its HBM roofline that the join probe
(``kernels/hash_join``) reaches: probe and build keys read and one
position per probe row written (``counts.join_probe_bytes``, from the
shapes of its calls) at the chip's HBM bandwidth, over the device time
of the ``join_probe`` events.  Layer: kernels.  Moves: query_p95_s (the
joins are L2, L3 and L5, the slowest queries)."""
from counts import roofline_share

KERNEL = "join_probe"


def read(run):
    if not run.trace or run.peaks is None:
        return None
    return roofline_share(run.kernel_bytes.get(KERNEL, 0),
                          run.trace["kernel_s"].get(KERNEL, 0.0),
                          run.peaks["hbm_bw"])
