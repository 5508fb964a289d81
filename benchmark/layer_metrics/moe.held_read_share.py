"""How many of the experts it HOLDS a decode step has to read: held experts
that got at least one pair (DispatchRecord ``experts_read``, summed over a
chunk's steps and layers) over held experts x layers x steps, over the
window's decode chunks. The reading of ``moe.experts_read_share`` for a chip
that holds a share of a deployment's experts and routes top-k: a pair to
another chip's expert or to an identity expert reads nothing here. At 20
live rows and an even gate 5 pairs land on 16 experts a layer: some 27%. (A
file of its own because the accepted metric's cell list is pinned by its own
test.)"""
from benchmark import spec


def read(run):
    return spec.load_module("layer_metrics", "moe.experts_read_share").read(run)
