"""The pooled decode program of a ``hybrid_ssm`` model: DECODE_CHUNK steps a
run. What a step MUST move: every served weight and the tied head once, the
state and the convolution tail of every live row read and written in each
state-space layer, and the K and V of the attention layers alone up to the
live rows' lengths. Its FLOPs: a live row multiplies every matmul weight and
the head, advances ``d_state x d_inner`` entries of state a state-space
layer (``SCAN_FLOPS`` each, the ``exp`` counted as one), and attends in the
attention layers. A row that is not live owes nothing."""

from __future__ import annotations

from benchmark import spec

# per entry of state and token: delta * A, exp, the decay's product, (delta u) B,
# the sum of the two, s * C, the sum over the state's entries
SCAN_FLOPS = 7.0


def layer_params(sz: dict) -> dict:
    """Per layer of each kind: (matmul weights a token multiplies, bytes of
    everything the layer reads of its weights). A state-space layer reads
    its taps, its three inner norms and the two block norms in the model's
    type (2 B) and ``b_dt``, ``A_log`` [N, Di] and ``D`` in float32."""
    d, di, n, r, f = sz["dim"], sz["d_inner"], sz["d_state"], sz["dt_rank"], sz["ffn"]
    kv = sz["kv_heads"] * sz["head_dim"]
    ffn = 3 * d * f
    ssm = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    ssm_other = 2 * (sz["d_conv"] * di + di + r + 2 * n + 2 * d) + 4 * (di + n * di + di)
    attn = 2 * d * d + 2 * d * kv
    return {"ssm": (ssm + ffn, 2 * (ssm + ffn) + ssm_other),
            "softmax": (attn + ffn, 2 * (attn + ffn) + 2 * 2 * d)}


def weight_bytes(sz: dict) -> float:
    """One pass over the served weights: the layers of both kinds and the
    tied table read as the head (the embedding is gathered by row)."""
    per = layer_params(sz)
    return float(sz["ssm_layers"] * per["ssm"][1] + sz["attn_layers"] * per["softmax"][1]
                 + 2 * sz["vocab"] * sz["dim"])


def matmul_params(sz: dict) -> tuple[int, int]:
    per = layer_params(sz)
    return (sz["ssm_layers"] * per["ssm"][0] + sz["attn_layers"] * per["softmax"][0],
            sz["vocab"] * sz["dim"])


def state_row_bytes(run) -> tuple[int, int]:
    """(the state's, the convolution tail's) bytes of one row in ONE
    state-space layer: [N, Di] float32, [K - 1, Di] in the model's type."""
    sz = run.sizes
    return sz["d_state"] * sz["d_inner"] * 4, (sz["d_conv"] - 1) * sz["d_inner"] * 2


def kv_bytes_per_token(run) -> int:
    """K and V of one position over the ATTENTION layers alone."""
    sz = run.sizes
    width = 1 if run.server_env.get("MODEL_KV_DTYPE") == "f8" else 2
    return 2 * sz["attn_layers"] * sz["kv_heads"] * sz["head_dim"] * width


def scan_flops(sz: dict, tokens: float) -> float:
    """The recurrence alone over ``tokens`` real tokens, every state-space layer."""
    return SCAN_FLOPS * sz["d_state"] * sz["d_inner"] * sz["ssm_layers"] * tokens


def step_work(run) -> tuple[float, float, float]:
    """(flops, bytes of weights and K/V, bytes of state and tail) of ONE
    step at the window's mean live rows and live tokens."""
    sz = run.sizes
    rows = spec.load_module("kernels", "retention_decode_step").live_rows(run)
    live = spec.load_module("kernels", "decode_step").mean_live_kv_tokens(run)
    body, head = matmul_params(sz)
    state = rows * sz["ssm_layers"] * 2 * sum(state_row_bytes(run))
    flops = (2.0 * rows * (body + head) + scan_flops(sz, rows)
             + 4.0 * sz["head_dim"] * sz["heads"] * sz["attn_layers"] * live)
    return flops, weight_bytes(sz) + kv_bytes_per_token(run) * live, state


def work(run, runs: int) -> tuple[float, float]:
    """(flops, bytes) the traced ``runs`` of the program had to do."""
    steps = runs * int(run.server_env.get("DECODE_CHUNK", "8"))
    flops, moved, state = step_work(run)
    return steps * flops, steps * (moved + state)
