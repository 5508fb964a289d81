"""How many of its experts a decode step has to read: experts that got at
least one token (DispatchRecord ``experts_read``, summed over a chunk's
steps and layers) over experts x layers x steps, over the window's decode
chunks. At one live row it is 1 in 16; it grows with the rows that ride a
step and with how evenly the router spreads them. A program whose records
lack the field reads nothing."""
from benchmark.readers import dispatches


def read(run):
    chunks = [d for d in dispatches(run, ("decode_chunk",)) if d.get("experts_read") is not None]
    if not chunks:
        return None
    steps = len(chunks) * int(run.server_env.get("DECODE_CHUNK", "8"))
    return 100.0 * sum(d["experts_read"] for d in chunks) / (
        run.sizes["experts"] * run.sizes["layers"] * steps)
