"""The prefill program of an ``mla_moe`` model against its roofline: the
larger of its useful FLOPs (active parameters over real tokens, the expanded
attention over the slice and what came before it) over the bf16 peak and the
bytes it must read (the weights outside the routed experts, the head, the
experts that got a pair, the latent) over the HBM peak, against the device
time of the traced runs of ``jit__prefill_fn``."""
from benchmark.readers import is_prefill, roofline_share


def read(run):
    return roofline_share(run, "mla_moe_prefill_step", is_prefill)
