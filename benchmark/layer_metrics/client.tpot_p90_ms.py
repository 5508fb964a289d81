"""Per request (last token - first token) / (tokens - 1); 90th percentile
over the window's requests."""
from benchmark import metrics as M
from benchmark.readers import pct


def read(run):
    return pct([t for t in map(M.tpot_s, run.measured) if t is not None], 90, 1e3)
