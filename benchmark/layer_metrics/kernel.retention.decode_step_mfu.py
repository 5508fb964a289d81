"""The pooled decode program of a retention model, its share of the chip's
bf16 peak: the FLOPs of the traced chunks (weights, head, and the state's
update and read-out for the live rows) over what the peak does in the
device time the trace shows for them."""
from benchmark.readers import mfu_share, of_pooled


def read(run):
    return of_pooled(run, mfu_share, "retention_decode_step")
