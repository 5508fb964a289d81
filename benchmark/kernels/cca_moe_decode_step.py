"""The pooled decode program of a ``cca_moe`` model: DECODE_CHUNK steps a
run. What a step MUST move: the weights outside the experts and the tied
head once, the experts that got a token (``experts_read`` x one expert's
bytes), the live rows' K and V up to their lengths, and their tails read
and written. Its FLOPs go by ACTIVE parameters: a live row multiplies the
weights outside the experts, the head and one expert a layer
(``expert_tokens``). A row that is not live owes nothing."""

from __future__ import annotations

import types

from benchmark import spec


def dense_params(sz: dict) -> tuple[int, int]:
    """(matmul weights a token multiplies a layer outside the experts,
    the other weights a layer reads): the four projections, the router's
    four matrices, the grouped convolution (d x d a head and tap) | the
    depthwise taps, biases, norms, carry and temperature."""
    d, r = sz["dim"], sz["router"]
    qd, kvd = sz["heads"] * sz["head_dim"], sz["kv_heads"] * sz["head_dim"]
    heads = sz["heads"] + sz["kv_heads"]
    matmul = (2 * d * qd + 2 * d * kvd + d * r + 2 * r * r + r * sz["experts"]
              + 2 * heads * sz["head_dim"] ** 2)
    other = 4 * (qd + kvd) + 2 * d + 5 * r + sz["kv_heads"]
    return matmul, other


def tail_values(sz: dict) -> int:
    kvd = sz["kv_heads"] * sz["head_dim"]
    return 2 * (sz["heads"] * sz["head_dim"] + kvd) + kvd // 2


def kv_bytes_per_token(run) -> int:
    sz = run.sizes
    width = 1 if run.server_env.get("MODEL_KV_DTYPE") == "f8" else 2
    return 2 * sz["layers"] * sz["kv_heads"] * sz["head_dim"] * width


def step_work(run) -> tuple[float, float]:
    """(flops, bytes) of ONE step at the mean live rows, live tokens and
    routing of the steps the trace holds (``moe_experts.traced_span``), or
    of the window where the run does not say which those were."""
    sz = run.sizes
    experts = spec.load_module("kernels", "moe_experts")
    chunk = int(run.server_env.get("DECODE_CHUNK", "8"))
    chunks = experts.routed(run, ("decode_chunk",), traced=True)
    rows = sum(d["batch_size"] or 0 for d in chunks) / max(len(chunks), 1)
    span = experts.traced_span(run)
    held = run if span is None else types.SimpleNamespace(
        records=run.records, w0=run.w0 + span[0], w1=run.w0 + span[1])
    live = spec.load_module("kernels", "decode_step").mean_live_kv_tokens(held)
    matmul, other = dense_params(sz)
    head = sz["vocab"] * sz["dim"]
    expert_flops, expert_bytes = (
        x / chunk for x in experts.mean_work(run, ("decode_chunk",), traced=True))
    nbytes = (2.0 * (sz["layers"] * (matmul + other) + head) + expert_bytes
              + kv_bytes_per_token(run) * live + rows * sz["layers"] * tail_values(sz) * 2 * 2)
    flops = (2.0 * rows * (sz["layers"] * matmul + head) + expert_flops
             + 4.0 * sz["head_dim"] * sz["heads"] * sz["layers"] * live)
    return flops, nbytes


def work(run, runs: int) -> tuple[float, float]:
    """(flops, bytes) the traced ``runs`` of the program had to do."""
    steps = runs * int(run.server_env.get("DECODE_CHUNK", "8"))
    flops, nbytes = step_work(run)
    return steps * flops, steps * nbytes
