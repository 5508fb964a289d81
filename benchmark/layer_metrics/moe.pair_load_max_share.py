"""The straggler among the routed experts: the fullest expert's pairs
(DispatchRecord ``expert_tokens_max``, summed over a chunk's steps and
layers) over all the pairs that landed (``expert_tokens``), over the window's
decode chunks: ``moe.load_max_share``'s reading for pairs. 96 pairs over 64
experts (16 live rows, top-6) put 4 or 5 on the fullest: some 5%. (A file of
its own because the accepted metric's cell list is pinned by its own test.)"""
from benchmark import spec


def read(run):
    return spec.load_module("layer_metrics", "moe.load_max_share").read(run)
