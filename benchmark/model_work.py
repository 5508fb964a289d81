"""Operations and bytes of the decoder, from the configuration's sizes.
Shared by the per-program work functions under ``kernels/``."""

from __future__ import annotations


def matmul_params(sz: dict) -> tuple[int, int]:
    """(weights every token multiplies per layer stack, lm_head weights)."""
    d, qd = sz["dim"], sz["heads"] * sz["head_dim"]
    kvd = sz["kv_heads"] * sz["head_dim"]
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * sz["ffn"]
    return sz["layers"] * per_layer, d * sz["vocab"]


def weight_bytes(sz: dict) -> float:
    """Bytes one pass over the served matmul weights must read: int8 is one
    byte a weight plus a float32 scale per output channel, bf16 two bytes.
    The embedding is gathered by row, not streamed, and is left out."""
    body, head = matmul_params(sz)
    if sz["quant"] == "int8":
        d, qd = sz["dim"], sz["heads"] * sz["head_dim"]
        kvd = sz["kv_heads"] * sz["head_dim"]
        channels = sz["layers"] * (qd + 2 * kvd + d + 2 * sz["ffn"] + d) + sz["vocab"]
        return float(body + head + 4 * channels)
    return 2.0 * (body + head)


def kv_bytes_per_token(sz: dict, kv_bytes: int = 2) -> int:
    return 2 * sz["layers"] * sz["kv_heads"] * sz["head_dim"] * kv_bytes


def forward_flops(sz: dict, tokens: float, head_rows: float) -> float:
    """2 x weights x tokens for the layer stack, plus the head on the rows
    that need logits (one per sequence in prefill, every row in decode)."""
    body, head = matmul_params(sz)
    return 2.0 * body * tokens + 2.0 * head * head_rows


def causal_attention_flops(sz: dict, n: float, before: float = 0.0) -> float:
    """QK^T and PV for ``n`` new positions after ``before`` cached ones:
    4 x head_dim x heads x layers x (n x before + n^2 / 2)."""
    return 4.0 * sz["head_dim"] * sz["heads"] * sz["layers"] * (n * before + n * n / 2.0)
