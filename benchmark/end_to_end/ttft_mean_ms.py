"""Time from when a request was due to its first token frame, averaged over
all the window's requests (a request with no token waited to the deadline)."""
from benchmark import metrics as M


def read(run):
    waits = [M.ttft_s(r, run.deadline) for r in run.measured]
    return 1e3 * sum(waits) / len(waits) if waits else None
