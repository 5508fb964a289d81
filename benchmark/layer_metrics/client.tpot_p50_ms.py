"""Per request (last token - first token) / (tokens - 1); the median over
the window's requests."""
from benchmark import metrics as M
from benchmark.readers import pct


def read(run):
    return pct([t for t in map(M.tpot_s, run.measured) if t is not None], 50, 1e3)
