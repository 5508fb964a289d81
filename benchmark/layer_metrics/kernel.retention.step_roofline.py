"""The one-token state kernel alone against the HBM peak: the state bytes
the traced chunks' live rows had to read and write (DispatchRecord
``state_bytes``, the window's mean a chunk, times the traced runs of the
decode program) over the device time of the operations named
``retention_step*`` in the trace. It reads only while that kernel is among
the trace's largest operations (``breakdown.device_ops``)."""
from benchmark.readers import dispatches, pooled_program


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"] if name.startswith("retention_step"))
    pooled = pooled_program(run)
    moved = [d["state_bytes"] for d in dispatches(run, ("decode_chunk",)) if d.get("state_bytes")]
    if seconds <= 0 or pooled is None or not moved:
        return None
    least = run.trace["programs"][pooled]["runs"] * sum(moved) / len(moved) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
