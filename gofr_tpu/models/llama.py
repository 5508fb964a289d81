"""Named Llama-family configurations (BASELINE.json configs 3-4) plus tiny
test/dev shapes."""

from __future__ import annotations

import jax.numpy as jnp

from gofr_tpu.models.transformer import TransformerConfig

# Llama-3-8B (serving target: int8 on v5e-4, p50 TTFT < 200ms)
LLAMA3_8B = TransformerConfig(
    vocab_size=128256,
    dim=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    hidden_dim=14336,
    max_seq=8192,
    rope_theta=500000.0,
)

# Llama-3-70B (DP-sharded decode on v5e-16)
LLAMA3_70B = TransformerConfig(
    vocab_size=128256,
    dim=8192,
    n_layers=80,
    n_heads=64,
    n_kv_heads=8,
    hidden_dim=28672,
    max_seq=8192,
    rope_theta=500000.0,
)

# Tiny config: fast CPU tests and the virtual-mesh dryrun
TINY = TransformerConfig(
    vocab_size=256,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    hidden_dim=128,
    max_seq=128,
    rope_theta=10000.0,
    dtype=jnp.float32,
    attn_impl="xla",
)

# TINY with power-retention layers (a state per row, no K/V): CPU tests
TINY_RETENTION = TransformerConfig(
    vocab_size=256,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    hidden_dim=128,
    max_seq=128,
    rope_theta=10000.0,
    dtype=jnp.float32,
    attn_impl="xla",
    attn_kind="retention",
)

# Small-but-realistic single-chip bench model (fits v5e-1 in bf16 and
# exercises the same kernels/shapes class as 8B)
SMALL = TransformerConfig(
    vocab_size=32000,
    dim=1024,
    n_layers=8,
    n_heads=8,
    n_kv_heads=4,
    hidden_dim=4096,
    max_seq=2048,
    rope_theta=500000.0,
)

CONFIGS: dict[str, TransformerConfig] = {
    "tiny": TINY,
    "tiny-retention": TINY_RETENTION,
    "small": SMALL,
    "llama3-8b": LLAMA3_8B,
    "llama3-70b": LLAMA3_70B,
}
