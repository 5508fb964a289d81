"""The pooled decode program of a ``cca_moe`` model, its share of the chip's
bf16 peak: the FLOPs of the traced chunks by ACTIVE parameters (one expert
a live row and layer) over what the peak does in the device time the trace
shows for them."""
from benchmark.readers import mfu_share, of_pooled


def read(run):
    return of_pooled(run, mfu_share, "cca_moe_decode_step")
