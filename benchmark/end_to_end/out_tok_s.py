"""Output tokens delivered inside the window, over its length."""
from benchmark import metrics as M


def read(run):
    return M.tokens_in_window(run.records, run.w0, run.w1) / run.seconds
