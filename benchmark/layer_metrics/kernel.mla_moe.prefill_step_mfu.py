"""The prefill program of an ``mla_moe`` model, its share of the chip's bf16
peak: useful FLOPs by active parameters over real tokens over what the peak
does in the device time of the traced runs of ``jit__prefill_fn``."""
from benchmark.readers import is_prefill, mfu_share


def read(run):
    return mfu_share(run, "mla_moe_prefill_step", is_prefill)
