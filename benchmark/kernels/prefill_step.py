"""The prefill program (whole prompts in a cohort, or one chunk of a long
prompt): the useful work of the window's prefill dispatches, per run. Pad
tokens are not useful work, so padding shows as a lower share."""

from __future__ import annotations

from benchmark import model_work as mw


def work(run, runs: int) -> tuple[float, float]:
    flops, n = 0.0, 0
    for d in run.dispatches:
        if d["status"] != "ok" or d["kind"] not in ("prefill", "prefill_chunk"):
            continue
        rows = d["batch_size"] or 1
        if d["kind"] == "prefill":
            tokens = (d["bucket"] or 0) * rows - d["padded_tokens"]
        else:
            tokens = d["tokens"]
        per_row = tokens / rows
        flops += mw.forward_flops(run.sizes, tokens, rows)
        flops += rows * mw.causal_attention_flops(run.sizes, per_row)
        n += 1
    if not n:
        return 0.0, 0.0
    return runs * flops / n, runs * mw.weight_bytes(run.sizes)
