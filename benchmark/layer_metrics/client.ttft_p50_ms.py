"""Median time from when a request was due to its first token frame."""
from benchmark import metrics as M
from benchmark.readers import pct


def read(run):
    return pct([M.ttft_s(r, run.deadline) for r in run.measured], 50, 1e3)
