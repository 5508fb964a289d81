"""The mechanism's share of the decode step: bytes of state and convolution
tail the window's decode chunks had to read and write (DispatchRecord
``state_bytes``) over all bytes those chunks had to move (those, the weights
and head once a step, and the attention layers' K/V up to the live rows'
lengths)."""
from benchmark import spec
from benchmark.readers import dispatches


def read(run):
    chunks = [d for d in dispatches(run, ("decode_chunk",)) if d.get("state_bytes")]
    if not chunks:
        return None
    _, moved, _ = spec.load_module("kernels", "hybrid_ssm_decode_step").step_work(run)
    state = sum(d["state_bytes"] for d in chunks)
    steps = len(chunks) * int(run.server_env.get("DECODE_CHUNK", "8"))
    return 100.0 * state / (state + steps * moved)
