"""The straggler: the fullest expert's tokens (DispatchRecord
``expert_tokens_max``, summed over a chunk's steps and layers) over all
routed tokens (``expert_tokens``), over the window's decode chunks. 1 / 16
is an even router at many rows; 100% is one live row, or a router that
sends every row one way."""
from benchmark.readers import dispatches


def read(run):
    chunks = [d for d in dispatches(run, ("decode_chunk",)) if d.get("expert_tokens")]
    if not chunks:
        return None
    return 100.0 * sum(d["expert_tokens_max"] for d in chunks) / sum(
        d["expert_tokens"] for d in chunks)
