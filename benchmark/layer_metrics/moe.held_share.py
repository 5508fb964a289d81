"""Where the pairs went, 2: (token, expert) pairs that landed on an expert
held HERE (DispatchRecord ``expert_tokens``) over all the pairs the window's
decode chunks drew. A gate that spreads evenly reads 16 / 768: the share of
the expert work of the deployment that this chip does for its own rows. A
program whose records lack the fields reads nothing."""
from benchmark import spec


def read(run):
    held, identity, absent = spec.load_module("layer_metrics", "moe.identity_share").pairs(run)
    total = held + identity + absent
    return 100.0 * held / total if total else None
