"""Per cent of the HBM roofline that the engine's job programs
(``dataflow/executor.py``) reach: the input and output bytes of the jobs
executed in the traced window (``JobStats.bytes_in + bytes_out``) at the
chip's HBM bandwidth, over the device's busy time.  Layer: engine.
Moves: queries_per_s."""
from counts import roofline_share


def read(run):
    if not run.trace or run.trace["busy_s"] is None or run.peaks is None:
        return None
    return roofline_share(run.job_bytes, run.trace["busy_s"],
                          run.peaks["hbm_bw"])
