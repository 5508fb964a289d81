"""Gaps between successive SSE token frames of a request, 99th percentile
(tokens leave in chunks of DECODE_CHUNK, so the gaps are bimodal)."""
from benchmark import metrics as M
from benchmark.readers import pct


def read(run):
    return pct([g for r in run.measured for g in M.frame_gaps_s(r)], 99, 1e3)
