"""The pooled decode program of a retention model against its roofline: the
least time the chip could take for the traced chunks (weights, head and the
live rows' state read and written over the HBM peak, or the FLOPs over the
bf16 peak; HBM bounds it) over the device time the trace shows for them."""
from benchmark.readers import of_pooled, roofline_share


def read(run):
    return of_pooled(run, roofline_share, "retention_decode_step")
