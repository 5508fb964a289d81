"""Rotary position embeddings (split-half convention, Llama-style).

Frequencies are precomputed once per model config and closed over by the
jitted forward — no per-step trig on the hot path beyond the gather.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0) -> jnp.ndarray:
    """Returns [max_seq, head_dim//2] complex-free (cos, sin) stacked as
    [max_seq, head_dim//2, 2] float32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_seq, head_dim//2]
    return jnp.stack([jnp.cos(freqs), jnp.sin(freqs)], axis=-1)


def apply_rope(x: jnp.ndarray, freqs: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [..., seq, n_heads, head_dim] by position.

    ``positions``: [seq] or [batch, seq] absolute positions (decode passes
    the cache offset). Split-half convention: (x1, x2) -> (x1*cos - x2*sin,
    x2*cos + x1*sin). A table narrower than the head (partial rotary:
    ``freqs`` built for the first ``r`` dims) turns dims 0..r-1, split-half
    within them, and passes the rest.
    """
    rot = 2 * freqs.shape[-2]
    if rot < x.shape[-1]:
        turned = apply_rope(x[..., :rot], freqs, positions)
        return jnp.concatenate([turned, x[..., rot:]], axis=-1)
    dtype = x.dtype
    cos_sin = freqs[positions]  # [..., seq, head_dim//2, 2]
    cos = cos_sin[..., 0][..., None, :]  # broadcast over heads: [..., seq, 1, hd/2]
    sin = cos_sin[..., 1][..., None, :]
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)
