"""The routed expert product of a decode step alone against its roofline:
what routing made it read (``experts_read`` x one expert's bytes, the
window's mean a pooled chunk) over the HBM peak, or its tokens' FLOPs over
the bf16 peak, for the traced runs of the pooled decode program, over the
device time of that program's operations named ``moe_experts*`` in the
trace. They are told from a prefill's by the rows they write: a pooled
step's product writes ``[DECODE_SLOTS, width]`` (a prefill's, one row a
token of its bucket, is rarely among the trace's largest operations, so its
work is left out with its time). It reads only while the pooled ones are
among the largest operations (``breakdown.device_ops``)."""
from benchmark import spec
from benchmark.readers import pooled_program


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    rows = f"[{int(run.server_env.get('DECODE_SLOTS', '0'))},"
    seconds = sum(s for name, s in run.trace["device_ops"]
                  if name.startswith("moe_experts") and rows in name)
    pooled = pooled_program(run)
    if seconds <= 0 or pooled is None:
        return None
    flops, nbytes = spec.load_module("kernels", "moe_experts").work(
        run, run.trace["programs"][pooled]["runs"], 0)
    if nbytes <= 0:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
