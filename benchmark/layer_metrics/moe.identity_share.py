"""Where the pairs went, 1: (token, expert) pairs that chose an identity
expert (DispatchRecord ``identity_tokens``: they cost nothing) over all the
pairs the window's decode chunks drew (held + identity + absent = top-k x
live rows x layers x steps). A gate that spreads evenly reads 256 / 768. A
program whose records lack the fields reads nothing."""
from benchmark.readers import dispatches


def pairs(run):
    """(held, identity, absent) pair counts over the window's decode chunks."""
    chunks = [d for d in dispatches(run, ("decode_chunk",))
              if d.get("identity_tokens") is not None and d.get("absent_tokens") is not None]
    return tuple(sum(d[key] or 0 for d in chunks)
                 for key in ("expert_tokens", "identity_tokens", "absent_tokens"))


def read(run):
    held, identity, absent = pairs(run)
    total = held + identity + absent
    return 100.0 * identity / total if total else None
