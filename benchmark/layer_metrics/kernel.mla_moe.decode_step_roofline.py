"""The pooled decode program of an ``mla_moe`` model against its roofline: the
least time the chip could take for the traced chunks (the weights outside the
routed experts, the head, the routed experts that got a pair and the live
rows' latent over the HBM peak, or the FLOPs by active parameters over the
bf16 peak; HBM bounds it) over the device time the trace shows for them."""
from benchmark.readers import of_pooled, roofline_share


def read(run):
    return of_pooled(run, roofline_share, "mla_moe_decode_step")
