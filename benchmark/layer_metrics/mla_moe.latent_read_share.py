"""The latent cache's share of an ``mla_moe`` model's decode step: bytes of
latent and shared rotated key the window's decode chunks' attention had to
read (DispatchRecord ``latent_bytes``: by the live rows' lengths, over all
layers) over all bytes those chunks had to move (those, the weights outside
the routed experts and the head once a step, the routed experts that got a
pair). A program whose records lack the field reads nothing. (PR 40's
``mla.latent_read_share`` takes the weights from LongCat's sheet, whose sizes
this architecture does not have.)"""
from benchmark import spec
from benchmark.readers import dispatches


def read(run):
    chunks = [d for d in dispatches(run, ("decode_chunk",)) if d.get("latent_bytes")]
    if not chunks:
        return None
    sheet = spec.load_module("kernels", "mla_moe_decode_step")
    experts = spec.load_module("kernels", "moe_experts")
    latent = sum(d["latent_bytes"] for d in chunks)
    steps = len(chunks) * int(run.server_env.get("DECODE_CHUNK", "8"))
    held = experts.expert_bytes(run.sizes) * sum(d.get("experts_read") or 0 for d in chunks)
    return 100.0 * latent / (latent + held + steps * sheet.weight_bytes(run.sizes))
