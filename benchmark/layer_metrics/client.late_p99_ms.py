"""How late the load generator sent a request after it was due (its own
clock): a starved generator must not read as a fast server."""
from benchmark import metrics as M
from benchmark.readers import pct


def read(run):
    return pct([M.late_s(r) for r in run.measured], 99, 1e3)
