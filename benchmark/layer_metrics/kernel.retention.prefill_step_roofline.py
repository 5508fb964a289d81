"""The prefill program of a retention model against its roofline: useful
FLOPs of the window's prefill dispatches (weights and the chunked form's
products over real tokens) over the bf16 peak, against the device time of
the traced runs of ``jit__prefill_fn``."""
from benchmark.readers import is_prefill, roofline_share


def read(run):
    return roofline_share(run, "retention_prefill_step", is_prefill)
