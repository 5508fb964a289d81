"""The prefill program's share of its roofline: useful FLOPs of the
window's prefill dispatches (2 x weights x real tokens + causal attention)
over the bf16 peak, against the device time of the traced runs of
``jit__prefill_fn``."""
from benchmark.readers import is_prefill, roofline_share


def read(run):
    return roofline_share(run, "prefill_step", is_prefill)
