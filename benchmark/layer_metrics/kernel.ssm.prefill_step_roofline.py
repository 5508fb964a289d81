"""The prefill program of a model with state-space layers against its
roofline: useful FLOPs of the window's prefill dispatches (weights, the
recurrence and the attention layers over real tokens) over the bf16 peak,
or the weights over the HBM peak, against the device time of the traced
runs of ``jit__prefill_fn``."""
from benchmark.readers import is_prefill, roofline_share


def read(run):
    return roofline_share(run, "hybrid_ssm_prefill_step", is_prefill)
