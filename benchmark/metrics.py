"""The end-to-end arithmetic, from the load generator's records alone.

A record is what ``benchmark/loadgen.py`` wrote for one request: when it
was due and sent, the arrival time of every token, how many were asked for,
and an error if any. All times are seconds on one monotonic clock.
"""

from __future__ import annotations

import math
from typing import Optional


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks (rank ``q/100 * (n - 1)``)."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = q / 100.0 * (len(ordered) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def is_failed(rec: dict) -> bool:
    """Errored, refused, cut short or short-counted."""
    return bool(rec["error"]) or rec["done"] is None or len(rec["tokens"]) != rec["asked"]


def ttft_s(rec: dict, deadline: float) -> float:
    """Due-time to first token frame; a request with no token waited until
    the run's deadline (it misses every limit)."""
    first = rec["times"][0] if rec["times"] else deadline
    return first - rec["due"]


def tpot_s(rec: dict) -> Optional[float]:
    """(last token - first token) / (tokens - 1); None under two tokens."""
    if len(rec["times"]) < 2:
        return None
    return (rec["times"][-1] - rec["times"][0]) / (len(rec["times"]) - 1)


def tpot_mean_s(records: list[dict]) -> Optional[float]:
    """All the decode time over all the decode steps: the sum over requests
    of (last token - first token) over the sum of (tokens - 1). A time per
    token taken over all the work, so it does not hang on which request
    happens to be the median one."""
    spans = [(r["times"][-1] - r["times"][0], len(r["times"]) - 1)
             for r in records if len(r["times"]) >= 2]
    steps = sum(n for _, n in spans)
    return sum(t for t, _ in spans) / steps if steps else None


def late_s(rec: dict) -> float:
    """How late the generator sent the request after it was due."""
    return (rec["sent"] if rec["sent"] is not None else rec["due"]) - rec["due"]


def frame_gaps_s(rec: dict) -> list[float]:
    """Gaps between successive token frames of one request."""
    return [b - a for a, b in zip(rec["times"], rec["times"][1:])]


def tokens_in_window(records: list[dict], w0: float, w1: float) -> int:
    """Output tokens that arrived inside ``[w0, w1)``, whoever asked."""
    return sum(1 for rec in records for t in rec["times"] if w0 <= t < w1)


def longest_silence_s(records: list[dict], w0: float, w1: float) -> Optional[float]:
    """The longest stretch inside ``[w0, w1)`` with a request in flight
    (sent, not yet done) and no token frame on any stream: a stall of the
    server, whatever its cause, shows here as one long gap."""
    marks = sorted({w0, w1, *(t for r in records for t in r["times"] if w0 <= t < w1)})
    busy = [(r["sent"], r["done"] if r["done"] is not None else w1)
            for r in records if r["sent"] is not None]
    gaps = [b - a for a, b in zip(marks, marks[1:])
            if any(sent <= a and end >= b for sent, end in busy)]
    return max(gaps) if gaps else None
