"""The prefill program of a ``hybrid_ssm`` model (a cohort of whole prompts,
or one slice of a long prompt from a carried state, tail and K/V rows): the
useful work of the window's prefill dispatches, per run. FLOPs over REAL
tokens: every matmul weight, the head on each row's last token, the
recurrence of the state-space layers and causal attention in the attention
layers alone; bytes: the served weights and the head once. Pad tokens are
not useful work. As in ``prefill_step.py`` a slice's attention is counted as
if nothing came before it."""

from __future__ import annotations

from benchmark import spec


def real_tokens(d: dict) -> float:
    if d["kind"] == "prefill":
        return (d["bucket"] or 0) * (d["batch_size"] or 1) - d["padded_tokens"]
    return d["tokens"]


def prefills(run) -> list[dict]:
    return [d for d in run.dispatches
            if d["status"] == "ok" and d["kind"] in ("prefill", "prefill_chunk")]


def work(run, runs: int) -> tuple[float, float]:
    sz = run.sizes
    decode = spec.load_module("kernels", "hybrid_ssm_decode_step")
    body, head = decode.matmul_params(sz)
    found = prefills(run)
    if not found:
        return 0.0, 0.0
    flops = 0.0
    for d in found:
        rows, tokens = d["batch_size"] or 1, real_tokens(d)
        per_row = tokens / rows
        flops += (2.0 * body * tokens + 2.0 * head * rows + decode.scan_flops(sz, tokens)
                  + rows * 4.0 * sz["head_dim"] * sz["heads"] * sz["attn_layers"]
                  * per_row * per_row / 2.0)
    return runs * flops / len(found), runs * decode.weight_bytes(sz)
