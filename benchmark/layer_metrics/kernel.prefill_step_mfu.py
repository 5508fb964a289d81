"""The prefill program's share of the chip's bf16 peak: useful FLOPs of the
window's prefill dispatches (2 x weights x real tokens + causal attention)
over what the peak does in the device time of the traced runs of
``jit__prefill_fn``. FLOPs bound a prefill, so it reads as the roofline
share does while that holds."""
from benchmark.readers import is_prefill, mfu_share


def read(run):
    return mfu_share(run, "prefill_step", is_prefill)
