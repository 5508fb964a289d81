"""The pooled decode program of an ``mla_scmoe`` model: DECODE_CHUNK steps a
run. What a step MUST move: every weight outside the experts once (the two
MLA sublayers' projections, the two dense SwiGLUs and the gate of every
layer), the untied head, the experts held here that got a pair
(``experts_read`` x one expert's bytes), and the live rows' latent and shared
rotated key up to their lengths (DispatchRecord ``latent_bytes``). Its FLOPs
go by ACTIVE parameters: a live row multiplies the weights outside the
experts and the head, one held expert a pair that landed here
(``expert_tokens``), and attends in the absorbed form: per head and live
position a 576-wide score and a 512-wide weighted sum, in every sublayer. An
identity pair and a pair to another chip's expert owe nothing; a row that is
not live owes nothing."""

from __future__ import annotations

from benchmark import spec


def layer_params(sz: dict) -> tuple[int, int]:
    """(matmul weights a token multiplies a layer outside the experts, the
    other weights a layer reads): two MLA sublayers (q_a, q_b, kv_a, kv_b,
    o), two dense SwiGLUs, the gate | four norms a sublayer and the gate's
    bias (float32: counted as two)."""
    d, h = sz["dim"], sz["heads"]
    mla = (d * sz["q_rank"] + sz["q_rank"] * h * (sz["nope"] + sz["rope"])
           + d * (sz["kv_rank"] + sz["rope"]) + sz["kv_rank"] * h * (sz["nope"] + sz["v"])
           + h * sz["v"] * d)
    gate = d * (sz["routed"] + sz["identity"])
    other = 2 * (2 * d + sz["q_rank"] + sz["kv_rank"]) + 2 * (sz["routed"] + sz["identity"])
    return 2 * mla + 2 * 3 * d * sz["dense_ffn"] + gate, other


def weight_bytes(sz: dict) -> float:
    """One pass over the served weights outside the experts, and the head
    (the embedding is gathered by row)."""
    matmul, other = layer_params(sz)
    return 2.0 * (sz["layers"] * (matmul + other) + sz["vocab"] * sz["dim"])


def latent_token_bytes(sz: dict) -> int:
    """What one token holds in the cache over all sublayers (bf16)."""
    return 2 * sz["layers"] * (sz["kv_rank"] + sz["rope"]) * 2


def attention_flops(sz: dict, positions: float) -> float:
    """The absorbed form over ``positions`` live (query, key) pairs a
    sublayer: the score against latent and rotated key, the weighted sum of
    latents."""
    per = 2.0 * (sz["kv_rank"] + sz["rope"]) + 2.0 * sz["kv_rank"]
    return per * sz["heads"] * 2 * sz["layers"] * positions


def step_work(run) -> tuple[float, float, float]:
    """(flops, bytes of weights, head and experts, bytes of latent) of ONE
    step at the mean live rows, routing and lengths of the chunks the trace
    holds (``moe_experts.traced_span``), or of the window's where the run
    does not say which those were."""
    sz = run.sizes
    experts = spec.load_module("kernels", "moe_experts")
    chunk = int(run.server_env.get("DECODE_CHUNK", "8"))
    chunks = experts.routed(run, ("decode_chunk",), traced=True)
    n = max(len(chunks), 1)
    rows = sum(d["batch_size"] or 0 for d in chunks) / n
    latent = sum(d.get("latent_bytes") or 0 for d in chunks) / n / chunk
    matmul, _ = layer_params(sz)
    head = sz["vocab"] * sz["dim"]
    expert_flops, expert_bytes = (
        x / chunk for x in experts.mean_work(run, ("decode_chunk",), traced=True))
    flops = (2.0 * rows * (sz["layers"] * matmul + head) + expert_flops
             + attention_flops(sz, latent / latent_token_bytes(sz)))
    return flops, weight_bytes(sz) + expert_bytes, latent


def work(run, runs: int) -> tuple[float, float]:
    """(flops, bytes) the traced ``runs`` of the program had to do."""
    steps = runs * int(run.server_env.get("DECODE_CHUNK", "8"))
    flops, moved, latent = step_work(run)
    return steps * flops, steps * (moved + latent)
