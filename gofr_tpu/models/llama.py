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

# ZAYA1-8B's block at test size: attention in a latent narrower than the
# hidden size ("cca": 4 q / 2 kv heads of 16 in a hidden of 64, two causal
# convolutions, a value shift, half-rotary) and a top-1 routed expert layer
# behind an MLP router; tied head. CPU tests.
TINY_ZAYA = TransformerConfig(
    vocab_size=256,
    dim=64,
    n_layers=3,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    hidden_dim=32,
    max_seq=128,
    rope_theta=10000.0,
    rope_fraction=0.5,
    dtype=jnp.float32,
    attn_impl="xla",
    attn_kind="cca",
    ffn_kind="moe",
    n_experts=4,
    router_dim=16,
    tie_embeddings=True,
)

# ZAYA1-8B (huggingface.co/Zyphra/ZAYA1-8B config.json): 40 layers of CCA
# attention (8 q / 2 kv heads of 128 in a hidden of 2048) and 16 experts of
# 2048, top-1, router MLP of 256, tied 262,272-row table; bf16
ZAYA1_8B = TransformerConfig(
    vocab_size=262272,
    dim=2048,
    n_layers=40,
    n_heads=8,
    n_kv_heads=2,
    head_dim=128,
    hidden_dim=2048,
    max_seq=131072,
    rope_theta=5000000.0,
    rope_fraction=0.5,
    attn_kind="cca",
    ffn_kind="moe",
    n_experts=16,
    router_dim=256,
    tie_embeddings=True,
)


def _jamba_kinds(n_layers: int, period: int, offset: int) -> tuple:
    """Layer i attends where ``i % period == offset``; every other layer is
    a state-space mixer (the family's attn_layer_period / attn_layer_offset)."""
    return tuple("softmax" if i % period == offset else "ssm" for i in range(n_layers))


# Jamba's stack at test size: state-space (Mamba-1) layers with a softmax
# layer mid-stack (ssm, ssm, softmax, ssm, ssm: three runs), 4 q heads on
# one kv head, no rotary, tied head. CPU tests.
TINY_JAMBA = TransformerConfig(
    vocab_size=256,
    dim=64,
    n_layers=5,
    n_heads=4,
    n_kv_heads=1,
    hidden_dim=128,
    max_seq=128,
    rope_fraction=0.0,
    norm_eps=1e-6,
    dtype=jnp.float32,
    attn_impl="xla",
    layer_kinds=_jamba_kinds(5, 5, 2),
    ssm_state=16,
    ssm_conv=4,
    ssm_dt_rank=4,
    tie_embeddings=True,
)

# AI21-Jamba2-3B (huggingface.co/ai21labs/AI21-Jamba2-3B config.json): 28
# layers, attention (20 q heads of 128 on 1 kv head, no positional
# embedding) where i % 14 == 7 and a Mamba-1 mixer (5120 channels, state
# 16, convolution 4, dt rank 160) everywhere else; dense SwiGLU of 8192
# (num_experts 1), tied 65,536-row table; bf16
JAMBA2_3B = TransformerConfig(
    vocab_size=65536,
    dim=2560,
    n_layers=28,
    n_heads=20,
    n_kv_heads=1,
    hidden_dim=8192,
    max_seq=262144,
    rope_fraction=0.0,
    norm_eps=1e-6,
    layer_kinds=_jamba_kinds(28, 14, 7),
    ssm_state=16,
    ssm_conv=4,
    ssm_dt_rank=160,
    tie_embeddings=True,
)

# LongCat-Flash's block at test size: latent attention (MLA: 4 heads, a q
# bottleneck of 32, a cached latent of 16 and one shared rotated key of 8)
# in the shortcut-connected double layer (two (attention, dense SwiGLU)
# sublayers and one expert product added two sublayers later); a linear
# router over 8 routed and 4 identity experts, top-3, of which this chip
# holds experts 0-1 (rank 0 of ep = 4); untied head. CPU tests.
TINY_LONGCAT = TransformerConfig(
    vocab_size=256,
    dim=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=1,
    hidden_dim=128,
    max_seq=128,
    rope_theta=10000.0,
    dtype=jnp.float32,
    attn_impl="xla",
    attn_kind="mla",
    q_lora_rank=32,
    kv_lora_rank=16,
    qk_nope_dim=16,
    qk_rope_dim=8,
    v_head_dim=16,
    ffn_kind="scmoe",
    router_kind="linear",
    n_experts=2,
    n_routed_experts=8,
    n_identity_experts=4,
    top_k=3,
    routed_scale=6.0,
    expert_dim=32,
)

# LongCat-Flash-Chat (huggingface.co/meituan-longcat/LongCat-Flash-Chat
# config.json): 28 double layers of MLA (64 heads, q rank 1536, latent 512,
# rope 64, nope 128, v 128) and dense SwiGLUs of 12288 around one product of
# 512 experts of 2048 and 256 identity experts, top-12 by a linear gate,
# scores x 6; untied 131,072-row head; bf16. As rank 0 of an ep = 32
# deployment holds it: 16 of each layer's experts (a layer's 512 are 38.7 GB).
# All 28 layers of that share are some 72 GB: no one chip boots this entry (as
# none boots `zaya1-8b` or `llama3-70b`); it holds the published widths, which
# the benchmark cuts to 4 layers (`longcat-flash-ep32-bf16`) and the tests read
LONGCAT_FLASH = TransformerConfig(
    vocab_size=131072,
    dim=6144,
    n_layers=28,
    n_heads=64,
    n_kv_heads=1,
    hidden_dim=12288,
    max_seq=131072,
    rope_theta=10000000.0,
    attn_kind="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    ffn_kind="scmoe",
    router_kind="linear",
    n_experts=16,
    n_routed_experts=512,
    n_identity_experts=256,
    top_k=12,
    routed_scale=6.0,
    expert_dim=2048,
)

# Moonlight's block at test size (the DeepSeek-V3 family): latent attention
# with no query bottleneck and no scale factors (4 heads, a cached latent of
# 16 and one shared rotated key of 8), a leading dense layer of 96 before two
# expert layers: a sigmoid gate over 8 routed experts that this chip holds
# whole, the 3 best by score + bias weighted by their scores normalised over
# the three, times 2.5, beside two shared experts every token takes (one
# SwiGLU of 2 x 32); untied head. CPU tests.
TINY_MOONLIGHT = TransformerConfig(
    vocab_size=256,
    dim=64,
    n_layers=3,
    n_heads=4,
    n_kv_heads=1,
    hidden_dim=96,
    max_seq=128,
    rope_theta=50000.0,
    dtype=jnp.float32,
    attn_impl="xla",
    attn_kind="mla",
    kv_lora_rank=16,
    qk_nope_dim=16,
    qk_rope_dim=8,
    v_head_dim=16,
    mla_scale=False,
    ffn_kinds=("dense", "moe", "moe"),
    router_kind="linear",
    gate_scoring="sigmoid",
    norm_topk=True,
    n_experts=8,
    n_routed_experts=8,
    n_shared_experts=2,
    top_k=3,
    routed_scale=2.5,
    expert_dim=32,
)

# Moonlight-16B-A3B (huggingface.co/moonshotai/Moonlight-16B-A3B config.json,
# model_type deepseek_v3): MLA (16 heads, q projected directly, latent 512,
# rope 64, nope 128, v 128), one dense SwiGLU layer of 11264, then expert
# layers: 64 routed experts of 1408, 6 a token by a sigmoid gate (score +
# bias chooses, the scores normalised over the six weigh, x 2.446), 2 shared
# experts; untied 163,840-row head; bf16. The published model has 27 layers
# (16 B parameters, 32 GB) and no one chip holds them; this entry is what ONE
# v5e holds and boots, every expert and the whole vocabulary with it: the
# dense layer and the first 8 expert layers, 5.43 B parameters, 10.87 GB (the
# benchmark's `moonlight-16b-a3b-bf16`). The other 18 are further stages of
# a pipeline.
MOONLIGHT_16B_9L = TransformerConfig(
    vocab_size=163840,
    dim=2048,
    n_layers=9,
    n_heads=16,
    n_kv_heads=1,
    hidden_dim=11264,
    max_seq=8192,
    rope_theta=50000.0,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    mla_scale=False,
    ffn_kinds=("dense",) + ("moe",) * 8,
    router_kind="linear",
    gate_scoring="sigmoid",
    norm_topk=True,
    n_experts=64,
    n_routed_experts=64,
    n_shared_experts=2,
    top_k=6,
    routed_scale=2.446,
    expert_dim=1408,
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
    "tiny-zaya": TINY_ZAYA,
    "zaya1-8b": ZAYA1_8B,
    "tiny-jamba": TINY_JAMBA,
    "jamba2-3b": JAMBA2_3B,
    "tiny-longcat": TINY_LONGCAT,
    "longcat-flash-ep32": LONGCAT_FLASH,
    "tiny-moonlight": TINY_MOONLIGHT,
    "moonlight-16b-a3b-9l": MOONLIGHT_16B_9L,
    "small": SMALL,
    "llama3-8b": LLAMA3_8B,
    "llama3-70b": LLAMA3_70B,
}
