"""The prefill program of a model with state-space layers, its share of the
chip's bf16 peak: useful FLOPs of the window's prefill dispatches over what
the peak does in the device time of the traced runs of ``jit__prefill_fn``."""
from benchmark.readers import is_prefill, mfu_share


def read(run):
    return mfu_share(run, "hybrid_ssm_prefill_step", is_prefill)
