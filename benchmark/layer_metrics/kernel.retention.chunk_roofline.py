"""The chunked-form kernel alone against the bf16 peak: the chunked form's
FLOPs over the real tokens of the window's prefill dispatches (per traced
run of ``jit__prefill_fn``) over the device time of the operations named
``retention_chunk*`` in the trace. A program whose chunked form is not one
named kernel gives nothing to read."""
from benchmark import spec
from benchmark.readers import dispatches, is_prefill, program_seconds


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds = sum(s for name, s in run.trace["device_ops"] if name.startswith("retention_chunk"))
    _, runs = program_seconds(run, is_prefill)
    prefills = dispatches(run, ("prefill", "prefill_chunk"))
    if seconds <= 0 or not runs or not prefills:
        return None
    sheet = spec.load_module("kernels", "retention_prefill_step")
    tokens = sum(d["tokens"] if d["kind"] == "prefill_chunk"
                 else (d["bucket"] or 0) * (d["batch_size"] or 1) - d["padded_tokens"]
                 for d in prefills) / len(prefills)
    least = runs * sheet.retention_flops(run.sizes, tokens) / run.peaks["bf16_flops_per_s"]
    return 100.0 * least / seconds
