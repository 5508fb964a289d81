"""The prefill program of a retention model (a cohort of whole prompts, or
one chunk of a long prompt from a carried state): the useful work of the
window's prefill dispatches, per run. Per real token and layer: the weights
(2 x weights), the read-out of the state phi(q)^T S and phi(q) . z for every
query head, the update v phi(k)^T and phi(k) for every kv head, and the
pairwise weights inside a sub-chunk (q k^T and w v over its causal half).
Pad tokens are not useful work."""

from __future__ import annotations

from benchmark import model_work as mw

SUB_CHUNK = 128  # the program's ops/retention.py::SUB_CHUNK


def retention_flops(sz: dict, tokens: float) -> float:
    state = 2.0 * (sz["head_dim"] + 1) * sz["phi"] * (sz["heads"] + sz["kv_heads"])
    inside = 4.0 * sz["head_dim"] * sz["heads"] * SUB_CHUNK / 2.0
    return sz["layers"] * tokens * (state + inside)


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
        flops += mw.forward_flops(run.sizes, tokens, rows) + retention_flops(run.sizes, tokens)
        n += 1
    if not n:
        return 0.0, 0.0
    return runs * flops / n, runs * mw.weight_bytes(run.sizes)
