"""All the decode time of the window's requests over all their decode steps:
sum of (last token - first token) over sum of (tokens - 1)."""
from benchmark import metrics as M


def read(run):
    value = M.tpot_mean_s(run.measured)
    return None if value is None else 1e3 * value
