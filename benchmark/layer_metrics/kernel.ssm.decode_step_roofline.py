"""The pooled decode program of a model with state-space layers against its
roofline: the least time the chip could take for the traced chunks (weights,
head, the live rows' state and tail read and written, the attention layers'
K/V over the HBM peak, or the FLOPs over the bf16 peak; HBM bounds it) over
the device time the trace shows for them."""
from benchmark.readers import of_pooled, roofline_share


def read(run):
    return of_pooled(run, roofline_share, "hybrid_ssm_decode_step")
