"""The prefill program of a ``cca_moe`` model against its roofline: the
larger of its useful FLOPs (active parameters over real tokens) over the
bf16 peak and the bytes it must read (the weights outside the experts, the
head, the experts that got a token) over the HBM peak, against the device
time of the traced runs of ``jit__prefill_fn``. A bucket of a few hundred
tokens reads every expert for little work each: HBM bounds it."""
from benchmark.readers import is_prefill, roofline_share


def read(run):
    return roofline_share(run, "cca_moe_prefill_step", is_prefill)
