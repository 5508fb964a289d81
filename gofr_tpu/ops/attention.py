"""Multi-head / grouped-query attention with selectable implementation.

- ``impl="xla"``: pure-jnp reference (softmax in f32, grouped einsum so GQA
  never materializes repeated KV heads).
- ``impl="pallas"``: Pallas TPU flash-attention kernel (gofr_tpu.ops.flash);
  runs in interpret mode on non-TPU backends so tests cover the kernel.
- ``impl="auto"``: pallas on TPU when shapes are tile-friendly, else XLA.

Layouts: q [B, Sq, Hq, D]; k, v [B, Skv, Hkv, D]; Hq % Hkv == 0. With
``layer`` (an int32 scalar) k and v are the whole stacked KV cache in the
order it is stored, [L, B, Hkv, Skv, D], and attention reads that layer of
it: the Pallas kernel indexes the stack itself, the XLA path slices it and
contracts over the stored order. Nothing transposes the cache.
``q_offset`` positions the query block absolutely (decode: cache length).
``kv_lens`` [B] bounds the valid key prefix (padded/unwritten cache tail)
— the structured form of a padding mask, supported by both paths.
``mask`` is an arbitrary boolean mask ([B, Skv] or [B, Sq, Skv]); only the
XLA path supports it.
``mesh`` names the serving mesh the surrounding jit is partitioned over:
GSPMD cannot partition a Mosaic kernel, so the Pallas path then runs under
``shard_map`` (heads over ``tp``, batch rows over ``dp``/``fsdp``).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

_NEG_INF = float(-1e30)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    mask: Optional[jnp.ndarray] = None,
    kv_lens: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    mesh: Optional[Any] = None,
    layer: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    stacked = layer is not None
    skv = k.shape[3] if stacked else k.shape[1]
    if impl == "auto":
        # arbitrary masks stay on the XLA path (kv_lens is fine: the flash
        # kernel bounds its KV loop with it)
        impl = "pallas" if (mask is None and _pallas_ok(q, skv)) else "xla"
    if stacked and (impl != "pallas" or k.dtype != q.dtype):
        # the XLA path, and a low-precision cache on either path, read
        # their layer by a slice the compiler is free to fuse: a stack of
        # one, still in the stored order
        k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=True)
        v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=True)
        layer = jnp.zeros((), jnp.int32)
    if k.dtype != q.dtype:
        # low-precision KV cache (float8_e4m3fn via cfg.kv_dtype): upcast
        # at the attention boundary, one layer at a time — capacity is the
        # win (2x tokens per HBM byte); a fused low-precision cache read
        # in the kernel is the follow-on traffic optimization
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    if impl == "pallas":
        if mask is not None:
            raise NotImplementedError(
                "pallas flash attention supports kv_lens=, not arbitrary mask="
            )
        from gofr_tpu.ops.flash import flash_attention

        if mesh is not None:
            return _sharded_flash(
                q, k, v, causal, q_offset, kv_lens, scale, mesh, layer
            )
        return flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, kv_lens=kv_lens,
            scale=scale, layer=layer,
        )
    if stacked:
        k, v = k[0], v[0]  # [B, Hkv, Skv, D]
    if kv_lens is not None:
        len_mask = jnp.arange(skv)[None, :] < kv_lens[:, None]  # [B, Skv]
        if mask is None:
            mask = len_mask
        elif mask.ndim == 2:
            mask = jnp.logical_and(mask, len_mask)
        else:
            mask = jnp.logical_and(mask, len_mask[:, None, :])
    return _xla_attention(q, k, v, causal, q_offset, mask, scale, stacked)


def _sharded_flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool,
    q_offset: int | jnp.ndarray,
    kv_lens: Optional[jnp.ndarray],
    scale: Optional[float],
    mesh: Any,
    layer: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """The flash kernel under a serving mesh. Mosaic lowering refuses a
    kernel inside a GSPMD-partitioned jit ("cannot be automatically
    partitioned"), so the kernel runs once per shard: batch rows over
    (dp, fsdp), heads over tp. Query and KV heads split alike, so every
    shard keeps whole GQA groups, and attention mixes neither axis — no
    collective is needed. Stacked k/v (``layer`` given) are the cache as
    ``parallel/sharding.py::cache_specs`` places it: the layer axis in
    front, never sharded, heads before positions; the index itself is
    replicated."""
    from jax.sharding import PartitionSpec as P

    from gofr_tpu.ops.flash import _normalize_scalars, flash_attention

    skv = k.shape[1] if layer is None else k.shape[3]
    offsets, lens = _normalize_scalars(q, skv, q_offset, kv_lens)
    rows = P(("dp", "fsdp"))
    heads = P(("dp", "fsdp"), None, "tp", None)
    kv_heads, index = (heads, None) if layer is None else (
        P(None, ("dp", "fsdp"), "tp", None, None), P())

    def per_shard(q_, k_, v_, offsets_, lens_, layer_):
        return flash_attention(
            q_, k_, v_, causal=causal, q_offset=offsets_, kv_lens=lens_,
            scale=scale, layer=layer_,
        )

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(heads, kv_heads, kv_heads, rows, rows, index),
        out_specs=heads, check_vma=False,
    )(q, k, v, offsets, lens, layer)


def _pallas_ok(q: jnp.ndarray, skv: int) -> bool:
    """Whether ``q`` [B, Sq, Hq, D] against ``skv`` key positions takes
    the Pallas kernel under ``impl="auto"``."""
    if jax.default_backend() not in ("tpu",):
        return False
    b, sq, hq, d = q.shape
    if sq == 1 and skv < 2048:
        # short-cache decode: per-layer kernel launch overhead outweighs
        # the bounded-KV-loop win (measured on llama3-8b int8, 512-slot
        # cache, v5e: 18.0ms/step XLA vs 22.7ms/step pallas). The ragged
        # kernel pays off once the cache is long enough that XLA's
        # O(max_seq) masked softmax dominates.
        return False
    return d % 128 == 0 and skv % 128 == 0


def _xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool,
    q_offset: int | jnp.ndarray,
    mask: Optional[jnp.ndarray],
    scale: Optional[float],
    heads_first: bool = False,
) -> jnp.ndarray:
    """``k``, ``v`` [B, Skv, Hkv, D], or with ``heads_first`` a layer of
    the cache as it is stored, [B, Hkv, Skv, D]."""
    b, sq, hq, d = q.shape
    if heads_first:
        kv, (hkv, skv) = "bhkd", k.shape[1:3]
    else:
        kv, (skv, hkv) = "bkhd", k.shape[1:3]
    groups = hq // hkv
    if scale is None:
        scale = d ** -0.5

    qg = q.reshape(b, sq, hkv, groups, d)
    # [b, hkv, groups, sq, skv]; accumulate in f32 for softmax stability
    logits = jnp.einsum(
        f"bqhgd,{kv}->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale

    if causal:
        k_pos = jnp.arange(skv)
        offset = jnp.asarray(q_offset)
        if offset.ndim == 0:
            q_pos = offset + jnp.arange(sq)  # [sq]
            causal_mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None]
        else:
            # per-batch offsets [b]: ragged decode positions
            q_pos = offset.reshape(-1, 1) + jnp.arange(sq)[None, :]  # [b, sq]
            causal_mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, None]
        logits = jnp.where(causal_mask, logits, _NEG_INF)
    if mask is not None:
        # mask: [b, skv] key-validity (padding) or [b, sq, skv]
        if mask.ndim == 2:
            m = mask[:, None, None, None, :]
        else:
            m = mask[:, None, None, :, :]
        logits = jnp.where(m, logits, _NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if causal or mask is not None:
        # fully-masked rows (e.g. kv_lens == 0 padding slots) emit zeros —
        # same convention as the flash kernel's l == 0 guard — instead of
        # the uniform-softmax mean(v) that finite -inf masking would give
        all_masked = jnp.all(logits <= _NEG_INF / 2, axis=-1, keepdims=True)
        probs = jnp.where(all_masked, 0.0, probs.astype(jnp.float32)).astype(q.dtype)
    out = jnp.einsum(f"bhgqk,{kv}->bqhgd", probs, v)
    return out.reshape(b, sq, hq, d)
