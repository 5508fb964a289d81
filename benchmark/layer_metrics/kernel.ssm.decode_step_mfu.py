"""The pooled decode program of a model with state-space layers, its share
of the chip's bf16 peak: the FLOPs of the traced chunks (weights, head, the
recurrence and the attention layers for the live rows) over what the peak
does in the device time the trace shows for them."""
from benchmark.readers import mfu_share, of_pooled


def read(run):
    return of_pooled(run, mfu_share, "hybrid_ssm_decode_step")
