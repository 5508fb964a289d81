"""The pooled decode program: DECODE_CHUNK steps a run, each reading every
served weight once and the KV of every live token once."""

from __future__ import annotations

from benchmark import model_work as mw


def mean_live_kv_tokens(run) -> float:
    """Tokens held in live KV rows, averaged over the window, from the
    client's records: a request holds prompt + emitted tokens from its first
    token to its last."""
    total = 0.0
    for rec in run.records:
        times = rec["times"]
        for j in range(len(times) - 1):
            span = min(times[j + 1], run.w1) - max(times[j], run.w0)
            if span > 0:
                total += (rec["n_prompt"] + j + 1) * span
    return total / (run.w1 - run.w0)


def work(run, runs: int) -> tuple[float, float]:
    """(flops, bytes) the traced ``runs`` of the program had to do."""
    steps = runs * int(run.server_env.get("DECODE_CHUNK", "8"))
    chunks = [d for d in run.dispatches if d["kind"] == "decode_chunk"]
    rows = sum(d["batch_size"] or 0 for d in chunks) / max(len(chunks), 1)
    live = mean_live_kv_tokens(run)
    kv_bytes = 1 if run.server_env.get("MODEL_KV_DTYPE") == "f8" else 2
    nbytes = mw.weight_bytes(run.sizes) + mw.kv_bytes_per_token(run.sizes, kv_bytes) * live
    flops = mw.forward_flops(run.sizes, rows, rows) + 4.0 * run.sizes["head_dim"] * \
        run.sizes["heads"] * run.sizes["layers"] * live
    return steps * flops, steps * nbytes
