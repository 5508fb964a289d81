"""How many of its routed experts a decode step has to read when every pair
of a token lands here: experts with at least one pair (DispatchRecord
``experts_read``, summed over a chunk's steps and expert layers) over experts
x expert layers x steps, over the window's decode chunks. At 16 live rows and
top-6 of 64 an even gate reads 64 x (1 - (58/64)^16) = 51 of 64: four fifths.
A program whose records lack the field reads nothing. (A file of its own: the
accepted ``moe.experts_read_share`` divides by every layer, and this model's
dense layers hold no expert.)"""
from benchmark.readers import dispatches


def read(run):
    chunks = [d for d in dispatches(run, ("decode_chunk",)) if d.get("experts_read") is not None]
    if not chunks:
        return None
    sz = run.sizes
    steps = len(chunks) * int(run.server_env.get("DECODE_CHUNK", "8"))
    return 100.0 * sum(d["experts_read"] for d in chunks) / (
        sz["experts"] * (sz["layers"] - sz.get("dense_layers", 0)) * steps)
