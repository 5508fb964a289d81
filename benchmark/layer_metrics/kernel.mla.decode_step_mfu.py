"""The pooled decode program of an ``mla_scmoe`` model, its share of the
chip's bf16 peak: the FLOPs of the traced chunks by ACTIVE parameters (a held
expert a pair that landed here, the absorbed attention by the lengths) over
what the peak does in the device time the trace shows for them."""
from benchmark.readers import mfu_share, of_pooled


def read(run):
    return of_pooled(run, mfu_share, "mla_scmoe_decode_step")
