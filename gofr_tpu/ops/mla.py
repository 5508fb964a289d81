"""Latent attention (MLA) over a cache of latents: what a token leaves behind
is ONE vector, ``c`` [rank] (normed and scaled) and the rotated ``k_rope``
[rope], shared by every head, and the keys and values of the heads are made
from ``c`` through ``w_kv_b`` [rank, heads * (nope + v)] when they are needed.

``latent_attention(q_nope, q_rope, latent, k_rope, w_kv_b, ..., place)``:
``q_nope`` [B, Sq, H, nope] and ``q_rope`` [B, Sq, H, rope] (rotated) against
place ``place`` of the stacks ``latent`` [P, B, S, rank] and ``k_rope`` [P, B,
rope, S] (positions in the minor place: a minor axis of 64 would be padded to
128 by the chip's tiling) -> [B, Sq, H, v]. Query j of row b stands at ``starts[b] + j`` and sees the
positions at or before it and below ``kv_lens[b]`` (0: a row without a
request reads nothing and gets zeros). Two forms of the same arithmetic,
chosen by the call's shape alone, as ``ops/flash.py`` chooses its two:

- **absorbed**, for a call whose ``Sq`` x ``H`` query rows fit one q block
  (the pooled and the solo step, a verify chunk of two): ``q~ = q_nope
  W_uk^T`` into the latent, scores against the latent itself, ``W_uv`` after
  the weighted sum. H heads on one 576-wide key and 512-wide value: the
  cache is read once and never expanded. On the TPU the middle of it is a
  Pallas kernel (``mla_absorbed_decode``) in the manner of the flash decode
  form: grid (rows,), the latent and the rotated key left in HBM and copied
  in by block of positions up to the row's length, each block read ONCE for
  score and weighted sum alike; a row of length 0 copies nothing and gets
  zeros. Elsewhere plain jax.numpy, which reads the whole window.
- **expanded**, for everything longer (prefill buckets, a chunked-prefill
  slice over the carried latent): K and V of every head made from the
  window's latent, then softmax attention with a query-key width (nope +
  rope) that differs from the value width: on the TPU the Pallas flash
  kernel (``ops/flash.py``, which takes the two widths), elsewhere plain
  jax.numpy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from gofr_tpu.ops.flash import (
    _FETCH_BUFFERS,
    DEFAULT_BLOCK_KV,
    DEFAULT_BLOCK_Q,
    _pad_axis,
    _softmax_init,
)

_NEG_INF = float(-1e30)


def _seen(sq: int, skv: int, starts: jnp.ndarray, kv_lens: jnp.ndarray) -> jnp.ndarray:
    """[B, 1, Sq, S]: which positions each query of each row sees."""
    t = jnp.arange(skv)[None, None, :]
    q_pos = starts[:, None, None] + jnp.arange(sq)[None, :, None]
    return ((t <= q_pos) & (t < kv_lens[:, None, None]))[:, None]


def _softmax(scores: jnp.ndarray, seen: jnp.ndarray, dtype) -> jnp.ndarray:
    scores = jnp.where(seen, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # a row with no live key gives zeros, as the flash kernels do
    return jnp.where(jnp.any(seen, axis=-1, keepdims=True), probs, 0.0).astype(dtype)


def _absorbed_kernel(
    starts_ref,  # [B] int32 scalar-prefetch: absolute position of a row's first query
    lens_ref,  # [B] int32 scalar-prefetch: written positions (0: no request)
    place_ref,  # [1] int32 scalar-prefetch: which place of the stacks
    ql_ref,  # [1, rows, rank]: the queries absorbed into the latent, row = s * heads + h
    qr_ref,  # [1, rows, rope]
    latent_hbm,  # [P, B, S, rank], left where it is (HBM)
    k_rope_hbm,  # [P, B, rope, S]
    out_ref,  # [1, rows, rank]: the weighted sums of latents
    latent_buf,  # [_FETCH_BUFFERS, block, rank] VMEM
    k_rope_buf,  # [_FETCH_BUFFERS, rope, block]
    sem,  # DMA semaphores [2, _FETCH_BUFFERS]
    *, scale: float, sq: int, heads: int, block: int, num_blocks: int,
):
    """One row of the batch: its latent and rotated key come in block by
    block, only up to its last live block, each block multiplied once for
    the scores of all heads and once for their weighted sums."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    start, kv_len, place = starts_ref[b], lens_ref[b], place_ref[0]
    ql, qr = ql_ref[0], qr_ref[0]
    rows, rank = ql.shape
    q_pos = start if sq == 1 else start + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), heads)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    hi = jnp.minimum(jnp.minimum(pl.cdiv(kv_len, block), pl.cdiv(start + sq, block)), num_blocks)

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        return (
            pltpu.make_async_copy(
                latent_hbm.at[place, b, at, :], latent_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                k_rope_hbm.at[place, b, :, at], k_rope_buf.at[slot], sem.at[1, slot]),
        )

    def fetch(j):
        @pl.when(j < hi)
        def _():
            for copy in copies(j, j % _FETCH_BUFFERS):
                copy.start()

    for j in range(_FETCH_BUFFERS - 1):
        fetch(j)

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        slot = j % _FETCH_BUFFERS
        fetch(j + _FETCH_BUFFERS - 1)  # the buffer it refills was last read an iteration ago
        for copy in copies(j, slot):
            copy.wait()
        latent = latent_buf[slot]
        s = jax.lax.dot_general(ql, latent, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = (s + jnp.dot(qr, k_rope_buf[slot], preferred_element_type=jnp.float32)) * scale
        k_pos = j * block + k_ids
        s = jnp.where((k_pos < kv_len) & (k_pos <= q_pos), s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(latent.dtype), latent, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_prev * alpha + pv

    _, l, acc = jax.lax.fori_loop(0, hi, body, _softmax_init(rows, rank))
    # a row with no live key gives zeros, not NaN
    out_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)


def _absorbed_pallas(q_lat, q_rope, latent, k_rope, place, starts, kv_lens, scale, interpret):
    """``q_lat`` [B, Sq, H, rank], ``q_rope`` [B, Sq, H, rope] against place
    ``place`` of the stacks -> the weighted sums of latents [B, Sq, H, rank]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, rank = q_lat.shape
    rope, skv = k_rope.shape[2:]
    rows = sq * h
    rows_pad = -(-rows // 16) * 16  # the bf16 tile's sublanes
    block = min(DEFAULT_BLOCK_KV, skv)
    pad = lambda x: _pad_axis(x.reshape(b, rows, x.shape[-1]), 1, rows_pad)  # noqa: E731
    by_row = lambda width: pl.BlockSpec((1, rows_pad, width), lambda bi, *_: (bi, 0, 0))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_absorbed_kernel, scale=scale, sq=sq, heads=h, block=block,
                          num_blocks=skv // block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[by_row(rank), by_row(rope), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=by_row(rank),
            scratch_shapes=[pltpu.VMEM((_FETCH_BUFFERS, block, rank), latent.dtype),
                            pltpu.VMEM((_FETCH_BUFFERS, rope, block), k_rope.dtype),
                            pltpu.SemaphoreType.DMA((2, _FETCH_BUFFERS))]),
        out_shape=jax.ShapeDtypeStruct((b, rows_pad, rank), q_lat.dtype),
        interpret=interpret,
        # what a call moves hangs on its rows' lengths: the bound, every row full
        cost_estimate=pl.CostEstimate(
            flops=2 * b * rows * skv * (2 * rank + rope), transcendentals=b * rows * skv,
            bytes_accessed=(latent.size + k_rope.size) // latent.shape[0] * latent.dtype.itemsize),
        name="mla_absorbed_decode",
    )(starts.astype(jnp.int32), kv_lens.astype(jnp.int32),
      jnp.reshape(place, (1,)).astype(jnp.int32), pad(q_lat), pad(q_rope), latent, k_rope)
    return out[:, :rows].reshape(b, sq, h, rank)


def _place_of(stack: jnp.ndarray, place: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(stack, place, 0, keepdims=False)


def absorbed(q_nope, q_rope, latent, k_rope, place, w_uk, w_uv, starts, kv_lens, scale, pallas):
    """``w_uk`` [rank, H, nope], ``w_uv`` [rank, H, v]; the stacks and a place."""
    f32 = jnp.float32
    with jax.named_scope("attn.mla.absorb"):
        q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk,
                           preferred_element_type=f32).astype(q_nope.dtype)
        if pallas:
            o_lat = _absorbed_pallas(q_lat, q_rope, latent, k_rope, place, starts, kv_lens,
                                     scale, jax.default_backend() != "tpu")
        else:
            latent, k_rope = _place_of(latent, place), _place_of(k_rope, place)
            scores = (jnp.einsum("bshc,btc->bhst", q_lat, latent, preferred_element_type=f32)
                      + jnp.einsum("bshr,brt->bhst", q_rope, k_rope, preferred_element_type=f32))
            probs = _softmax(scores * scale,
                             _seen(q_nope.shape[1], latent.shape[1], starts, kv_lens), latent.dtype)
            o_lat = jnp.einsum("bhst,btc->bshc", probs, latent,
                               preferred_element_type=f32).astype(q_nope.dtype)
        return jnp.einsum("bshc,chv->bshv", o_lat, w_uv,
                          preferred_element_type=f32).astype(q_nope.dtype)


def expanded(q_nope, q_rope, latent, k_rope, place, w_uk, w_uv, starts, kv_lens, scale, pallas):
    f32 = jnp.float32
    b, sq, h, _ = q_nope.shape
    latent, k_rope = _place_of(latent, place), _place_of(k_rope, place)
    with jax.named_scope("attn.mla.expand"):
        k_nope = jnp.einsum("btc,chn->bhtn", latent, w_uk,
                            preferred_element_type=f32).astype(latent.dtype)
        v = jnp.einsum("btc,chv->bhtv", latent, w_uv,
                       preferred_element_type=f32).astype(latent.dtype)
    if pallas:
        from gofr_tpu.ops.flash import flash_attention

        with jax.named_scope("attn.mla.expand"):
            # one key per head: its own nope part and the shared rotated part
            rope, skv = k_rope.shape[1:]
            shared = jnp.broadcast_to(jnp.swapaxes(k_rope, 1, 2)[:, None], (b, h, skv, rope))
            k = jnp.concatenate([k_nope, shared], axis=-1)[None]
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("attn.flash"):
            return flash_attention(q, k, v[None], causal=True, q_offset=starts,
                                   kv_lens=kv_lens, scale=scale, layer=jnp.int32(0))
    with jax.named_scope("attn.flash"):
        scores = (jnp.einsum("bshn,bhtn->bhst", q_nope, k_nope, preferred_element_type=f32)
                  + jnp.einsum("bshr,brt->bhst", q_rope, k_rope, preferred_element_type=f32))
        probs = _softmax(scores * scale, _seen(sq, latent.shape[1], starts, kv_lens), v.dtype)
        return jnp.einsum("bhst,bhtv->bshv", probs, v,
                          preferred_element_type=f32).astype(q_nope.dtype)


def latent_attention(
    q_nope: jnp.ndarray, q_rope: jnp.ndarray, latent: jnp.ndarray, k_rope: jnp.ndarray,
    w_kv_b: jnp.ndarray, starts: jnp.ndarray, kv_lens: jnp.ndarray, place: jnp.ndarray,
    impl: str = "auto",
) -> jnp.ndarray:
    b, sq, h, nope = q_nope.shape
    rank, skv = latent.shape[-1], latent.shape[2]
    w = w_kv_b.reshape(rank, h, -1)
    w_uk, w_uv = w[..., :nope], w[..., nope:]
    scale = (nope + q_rope.shape[-1]) ** -0.5
    pallas = impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu" and skv % 128 == 0 and rank % 128 == 0)
    return (absorbed if sq * h <= DEFAULT_BLOCK_Q else expanded)(
        q_nope, q_rope, latent, k_rope, place, w_uk, w_uv, starts, kv_lens, scale, pallas)
