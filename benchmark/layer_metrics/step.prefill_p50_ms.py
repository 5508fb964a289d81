"""Median host-timed duration of a prefill dispatch (whole or chunk)."""
from benchmark.readers import dispatches, pct


def read(run):
    return pct([d["duration_s"] for d in dispatches(run, ("prefill", "prefill_chunk"))
                if d["duration_s"] is not None], 50, 1e3)
