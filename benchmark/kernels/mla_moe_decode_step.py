"""The pooled decode program of an ``mla_moe`` model: DECODE_CHUNK steps a
run. What a step MUST move: every weight outside the routed experts once
(each layer's attention, the dense layers' SwiGLU, each expert layer's gate
and shared expert), the untied head, the routed experts that got a pair
(``experts_read`` x one expert's bytes), and the live rows' latent and shared
rotated key up to their lengths (DispatchRecord ``latent_bytes``). Its FLOPs
go by ACTIVE parameters: a live row multiplies the weights outside the
routed experts and the head, one routed expert a pair (``expert_tokens``:
every pair lands here), and attends in the absorbed form: per head and live
position a 576-wide score and a 512-wide weighted sum, in every layer. A row
that is not live owes nothing."""

from __future__ import annotations

from benchmark import spec


def attention_params(sz: dict) -> int:
    """One layer's latent attention: q directly, kv_a, kv_b, o."""
    d, h = sz["dim"], sz["heads"]
    return (d * h * (sz["nope"] + sz["rope"]) + d * (sz["kv_rank"] + sz["rope"])
            + sz["kv_rank"] * h * (sz["nope"] + sz["v"]) + h * sz["v"] * d)


def shared_params(sz: dict) -> int:
    """An expert layer's shared experts: one SwiGLU of their joint width."""
    return 3 * sz["dim"] * sz["shared"] * sz["ffn"]


def stack_params(sz: dict) -> tuple[int, int]:
    """(matmul weights a token multiplies down the stack outside the routed
    experts, the other weights the stack reads): attention in every layer,
    the dense SwiGLU in the dense ones, the gate and the shared expert in
    the expert ones | three norms a layer and the gate's bias (float32:
    counted as two)."""
    d, dense = sz["dim"], sz["dense_layers"]
    routed = sz["layers"] - dense
    matmul = (sz["layers"] * attention_params(sz) + dense * 3 * d * sz["dense_ffn"]
              + routed * (d * sz["experts"] + shared_params(sz)))
    return matmul, sz["layers"] * (2 * d + sz["kv_rank"]) + routed * 2 * sz["experts"]


def weight_bytes(sz: dict) -> float:
    """One pass over the served weights outside the routed experts, and the
    head (the embedding is gathered by row)."""
    matmul, other = stack_params(sz)
    return 2.0 * (matmul + other + sz["vocab"] * sz["dim"])


def latent_token_bytes(sz: dict) -> int:
    """What one token holds in the cache over all layers (bf16)."""
    return sz["layers"] * (sz["kv_rank"] + sz["rope"]) * 2


def attention_flops(sz: dict, positions: float) -> float:
    """The absorbed form over ``positions`` live (query, key) pairs a layer:
    the score against latent and rotated key, the weighted sum of latents."""
    per = 2.0 * (sz["kv_rank"] + sz["rope"]) + 2.0 * sz["kv_rank"]
    return per * sz["heads"] * sz["layers"] * positions


def step_parts(run) -> tuple[float, dict]:
    """(flops, bytes by what they are) of ONE step at the mean live rows,
    routing and lengths of the chunks the trace holds
    (``moe_experts.traced_span``), or of the window's where the run does not
    say which those were: the routed experts that got a pair, the shared
    experts, the latent, the head, and everything else outside the experts."""
    sz = run.sizes
    experts = spec.load_module("kernels", "moe_experts")
    chunk = int(run.server_env.get("DECODE_CHUNK", "8"))
    chunks = experts.routed(run, ("decode_chunk",), traced=True)
    n = max(len(chunks), 1)
    rows = sum(d["batch_size"] or 0 for d in chunks) / n
    latent = sum(d.get("latent_bytes") or 0 for d in chunks) / n / chunk
    matmul, _ = stack_params(sz)
    head = sz["vocab"] * sz["dim"]
    expert_flops, expert_bytes = (
        x / chunk for x in experts.mean_work(run, ("decode_chunk",), traced=True))
    shared = 2.0 * (sz["layers"] - sz["dense_layers"]) * shared_params(sz)
    flops = (2.0 * rows * (matmul + head) + expert_flops
             + attention_flops(sz, latent / latent_token_bytes(sz)))
    return flops, {"experts": expert_bytes, "shared": shared, "latent": latent,
                   "head": 2.0 * head, "rest": weight_bytes(sz) - shared - 2.0 * head}


def work(run, runs: int) -> tuple[float, float]:
    """(flops, bytes) the traced ``runs`` of the program had to do."""
    steps = runs * int(run.server_env.get("DECODE_CHUNK", "8"))
    flops, parts = step_parts(run)
    return steps * flops, steps * sum(parts.values())
