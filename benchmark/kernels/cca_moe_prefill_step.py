"""The prefill program of a ``cca_moe`` model (a cohort of whole prompts, or
one slice of a long prompt from a carried tail): the useful work of the
window's prefill dispatches, per run. FLOPs by ACTIVE parameters over real
tokens (the weights outside the experts, one expert a token and layer from
``expert_tokens``, the head on each row's last token, causal attention);
bytes: the weights outside the experts, the head, and the experts that got
a token (``experts_read``). Pad tokens are not useful work. As in
``prefill_step.py`` a slice's attention is counted as if nothing came
before it."""

from __future__ import annotations

from benchmark import model_work as mw
from benchmark import spec


def work(run, runs: int) -> tuple[float, float]:
    sz = run.sizes
    decode = spec.load_module("kernels", "cca_moe_decode_step")
    experts = spec.load_module("kernels", "moe_experts")
    matmul, other = decode.dense_params(sz)
    head = sz["vocab"] * sz["dim"]
    flops, n = 0.0, 0
    for d in run.dispatches:
        if d["status"] != "ok" or d["kind"] not in ("prefill", "prefill_chunk"):
            continue
        rows = d["batch_size"] or 1
        if d["kind"] == "prefill":
            tokens = (d["bucket"] or 0) * rows - d["padded_tokens"]
        else:
            tokens = d["tokens"]
        flops += (2.0 * sz["layers"] * matmul * tokens + 2.0 * head * rows
                  + mw.causal_attention_flops(sz, tokens / rows) * rows)
        n += 1
    if not n:
        return 0.0, 0.0
    expert_flops, expert_bytes = experts.mean_work(run, ("prefill", "prefill_chunk"))
    nbytes = 2.0 * (sz["layers"] * (matmul + other) + head) + expert_bytes
    return runs * (flops / n + expert_flops), runs * nbytes
