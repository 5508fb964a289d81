"""The straggler among the experts held: the fullest held expert's pairs
(DispatchRecord ``expert_tokens_max``, summed over a chunk's steps and
layers) over all the pairs that landed on a held expert (``expert_tokens``),
over the window's decode chunks: ``moe.load_max_share``'s reading for pairs.
With a handful of pairs over 16 experts a layer it is a fifth to a third
whatever the gate; a seeded gate that favours one held expert reads higher
on every seed. (A file of its own because the accepted metric's cell list is
pinned by its own test.)"""
from benchmark import spec


def read(run):
    return spec.load_module("layer_metrics", "moe.load_max_share").read(run)
