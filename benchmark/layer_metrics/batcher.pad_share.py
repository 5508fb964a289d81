"""Pad tokens over all tokens the compiled prefill shapes computed
(DispatchRecords of kinds prefill and prefill_chunk)."""
from benchmark.readers import dispatches


def read(run):
    padded = total = 0
    for d in dispatches(run, ("prefill", "prefill_chunk")):
        width = (d["bucket"] or 0) * (d["batch_size"] or 1)
        total += width
        padded += d["padded_tokens"] if d["kind"] == "prefill" else max(width - d["tokens"], 0)
    return 100.0 * padded / total if total else None
