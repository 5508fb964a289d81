"""90th percentile of due-time to first token frame, over all requests."""
from benchmark import metrics as M
from benchmark.readers import pct


def read(run):
    return pct([M.ttft_s(r, run.deadline) for r in run.measured], 90, 1e3)
