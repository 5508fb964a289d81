"""FLOPs accounting for training MFU (model-FLOPs-utilization).

``train_mfu`` uses the standard 6·N·tokens approximation (2·N forward,
4·N backward; attention FLOPs and norms are ignored, which slightly
*under*-counts — the reported MFU is a floor, never inflated).

``device_peak_flops`` maps PJRT device kinds to published per-chip bf16
peaks (unknown TPU kind: error; non-TPU platform: no peak, so every
utilization reads 0.0). A serving utilization is not computed in the
program: the benchmark reads it from device time in a trace against its
own work sheets and peaks (``benchmark/kernels/``, ``benchmark/peaks.json``).
"""

from __future__ import annotations

from typing import Any

# Published per-chip dense bf16 peak FLOP/s by PJRT device_kind substring.
# (v5e: 197 TFLOP/s; v4: 275; v5p: 459; v6e/Trillium: 918.)
_PEAKS: tuple[tuple[str, float], ...] = (
    ("v6 lite", 918e12),
    ("v6e", 918e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def device_peak_flops(device_kind: str, platform: str) -> float:
    """Per-chip bf16 peak for the device kind: ordered substring match.
    A TPU kind missing from the table is an error, never a default — a
    utilization against the wrong peak is a wrong number under a trusted
    name. Off-TPU there is no peak (0.0), so ``mfu`` returns 0.0 and no
    CPU run reports a utilization."""
    kind = (device_kind or "").lower()
    if platform != "tpu" and "tpu" not in kind:
        return 0.0
    for needle, value in _PEAKS:
        if needle in kind:
            return value
    raise ValueError(
        f"unknown TPU device_kind {device_kind!r}: add its published "
        "per-chip peak to gofr_tpu/tpu/flops.py"
    )


def transformer_param_count(cfg: Any) -> int:
    """Analytic parameter count for models/transformer.py's weight layout
    (init_transformer): embed + lm_head + final norm + per-layer
    {wq, wk, wv, wo, w_gate, w_up, w_down, 2 norms}. Computed from the
    config so no materialized tree is needed (int8 trees store packed
    {"q","scale"} leaves; the logical count is what MFU wants)."""
    d, f = cfg.dim, cfg.hidden_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    per_layer = (
        d * d  # wq
        + 2 * d * kv_dim  # wk, wv
        + d * d  # wo
        + 3 * d * f  # w_gate, w_up, w_down
        + 2 * d  # attn_norm, mlp_norm
    )
    return cfg.vocab_size * d + d * cfg.vocab_size + d + cfg.n_layers * per_layer


def bert_param_count(cfg: Any) -> int:
    """Analytic parameter count for models/bert.py's layout (init_bert):
    tok/pos embeds + final norm + per-layer {wqkv, wo, w_in/b_in,
    w_out/b_out, 2 norms with biases}."""
    d, f = cfg.dim, cfg.hidden_dim
    per_layer = (
        d * 3 * d  # wqkv
        + d * d  # wo
        + d * f + f  # w_in, b_in
        + f * d + d  # w_out, b_out
        + 4 * d  # two layer norms (weight + bias each)
    )
    return cfg.vocab_size * d + cfg.max_seq * d + 2 * d + cfg.n_layers * per_layer


def mfu(n_params: int, tokens: float, seconds: float, peak: float) -> float:
    """Fraction of peak achieved processing ``tokens`` in ``seconds``:
    2·N·tokens / seconds / peak."""
    if seconds <= 0 or peak <= 0:
        return 0.0
    return (2.0 * n_params * tokens) / seconds / peak


def train_mfu(n_params: int, tokens: float, seconds: float, peak: float) -> float:
    """Training MFU: 6·N·tokens (forward 2N + backward 4N) / seconds /
    aggregate peak. Rematerialized forwards are NOT counted (standard MFU
    convention: model FLOPs, not hardware FLOPs)."""
    return 3.0 * mfu(n_params, tokens, seconds, peak)
