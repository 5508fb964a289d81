"""The prefill program of an ``mla_moe`` model (a cohort of whole prompts, or
one slice of a long prompt over the latent the slice before left): the useful
work of the window's prefill dispatches, per run. FLOPs by ACTIVE parameters
over REAL tokens: the weights outside the routed experts (the shared expert
among them), one routed expert a pair (``expert_tokens``), the head on each
row's last token, and causal attention in the expanded form (a 192-wide score
and a 128-wide weighted sum a head) over the slice's own tokens and over what
came before it (``latent_bytes`` says how far the row reaches). Keys and
values of the carried latent are counted as made ONCE, where their tokens
were prefilled. Bytes: the weights outside the routed experts, the head, the
experts that got a pair, the latent read. Pad tokens are not useful work."""

from __future__ import annotations

from benchmark import spec


def work(run, runs: int) -> tuple[float, float]:
    sz = run.sizes
    decode = spec.load_module("kernels", "mla_moe_decode_step")
    experts = spec.load_module("kernels", "moe_experts")
    matmul, _ = decode.stack_params(sz)
    head = sz["vocab"] * sz["dim"]
    token_bytes = decode.latent_token_bytes(sz)
    per_pair = 2.0 * (sz["nope"] + sz["rope"] + sz["v"]) * sz["heads"] * sz["layers"]
    flops = latent = 0.0
    n = 0
    for d in run.dispatches:
        if d["status"] != "ok" or d["kind"] not in ("prefill", "prefill_chunk"):
            continue
        rows = d["batch_size"] or 1
        if d["kind"] == "prefill":
            tokens = (d["bucket"] or 0) * rows - d["padded_tokens"]
        else:
            tokens = d["tokens"]
        reach = (d.get("latent_bytes") or 0) / token_bytes  # positions the rows end at, summed
        own, before = tokens / rows, max(reach - tokens, 0.0) / rows
        flops += (2.0 * matmul * tokens + 2.0 * head * rows
                  + per_pair * rows * (own * before + own * own / 2.0))
        latent += d.get("latent_bytes") or 0
        n += 1
    if not n:
        return 0.0, 0.0
    expert_flops, expert_bytes = experts.mean_work(run, ("prefill", "prefill_chunk"))
    nbytes = decode.weight_bytes(sz) + expert_bytes + latent / n
    return runs * (flops / n + expert_flops), runs * nbytes
