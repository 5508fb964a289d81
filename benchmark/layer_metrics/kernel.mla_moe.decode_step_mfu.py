"""The pooled decode program of an ``mla_moe`` model, its share of the chip's
bf16 peak: the FLOPs of the traced chunks by ACTIVE parameters (a routed
expert a pair, the shared expert and the absorbed attention a live row) over
what the peak does in the device time the trace shows for them."""
from benchmark.readers import mfu_share, of_pooled


def read(run):
    return of_pooled(run, mfu_share, "mla_moe_decode_step")
