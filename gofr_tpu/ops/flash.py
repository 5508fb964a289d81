"""Pallas TPU flash attention (forward kernels + fused backward).

TPU-first design (pallas_guide: grid/block specs, scalar prefetch, online
softmax in VMEM). The forward has two forms, and which one a call takes is
read from its shapes alone:

- **the decode form**, for a call whose ``sq`` x ``groups`` query rows fit
  one q block (the pooled and the solo decode chunk, ``sq`` = 1; the
  speculative verify's few rows; a short training sequence): grid =
  (batch, kv_heads), the ``groups`` q heads that share a kv head side by
  side in one q block, so each K/V block is multiplied once for its
  group. K and V stay in HBM; the kernel copies them in itself, a block
  of ``block_kv`` positions at a time (``_FETCH_BUFFERS`` copies in
  flight while the current block is in the arithmetic), only up to the
  row's last live block. A row of length 0 (a decode slot that holds no
  request) issues no copy, runs no iteration and returns zeros. Work and
  bytes are in proportion to the tokens that are live, whatever
  ``max_seq``.
- **the prefill form**, for everything longer (prefill buckets, chunked
  prefill, training): grid = (batch, q_heads, q_blocks); K/V for one
  (batch, kv-head) live whole in VMEM (max_seq 8192 × 128 in bf16 = 2 MiB
  each, well under the ~16 MiB budget), resident across the q blocks and
  the heads of a group, which is the right trade when every q block reads
  them; Q is tiled ``block_q`` rows at a time. GQA maps query head → kv
  head in the BlockSpec index map, so repeated KV heads are never
  materialized.
- both run the KV loop *inside* the kernel as a ``lax.fori_loop`` with a
  **dynamic trip count** — causal blocks past the diagonal and blocks past
  the written KV length are never visited — and share one online-softmax
  step (``_softmax_step``: running (m, l, acc) in f32; probabilities cast
  back to the value dtype so the p·V matmul hits the MXU in bf16 with f32
  accumulation). Both read K/V out of a stack [L, B, Hkv, Skv, D] at a
  layer index (a third scalar-prefetch value): the serving path hands
  them the whole KV cache, which is stored in that order.
- per-batch scalars (``q_offset`` for ragged decode positions, ``kv_lens``
  bounding the valid cache prefix) ride scalar prefetch
  (``PrefetchScalarGridSpec``) — available before the body for the
  dynamic loop bound and the copies' addresses.
- backward: **fused Pallas kernels** (FlashAttention-2 style). The forward
  additionally emits per-row logsumexp; ``_dq_kernel`` recomputes P from it
  and accumulates dQ over the same bounded KV loop as the forward, and
  ``_dkv_kernel`` accumulates dK/dV per KV block over the (causally
  bounded) query blocks, summing GQA groups by revisiting the output block
  on the innermost grid axis. The O(S²) score matrix never materializes in
  either direction. A checkpointed q-blockwise XLA recompute
  (``_blockwise_reference``) remains as the numeric oracle and the
  ``FUSED_BWD = False`` escape hatch.

Layouts match gofr_tpu.ops.attention: q [B, Sq, Hq, D]; k, v [B, Skv,
Hkv, D], or with ``layer`` the stacked cache [L, B, Hkv, Skv, D];
Hq % Hkv == 0. The forward (both forms, a stacked k and v) takes a value
narrower than its key, ``v`` [..., Dv] beside ``q`` and ``k`` [..., D]:
latent attention's expanded form (``ops/mla.py``: 192 and 128). The output
is as wide as the value; the backward is written for one width. On non-TPU backends the kernel runs in pallas
interpret mode (tests exercise the real kernel logic on the CPU mesh, the
way the reference tests run against in-process fakes, SURVEY.md §4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(-1e30)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _softmax_init(rows: int, d: int) -> tuple:
    """The running (max, sum, accumulator) of the online softmax, float32."""
    return (
        jnp.full((rows, 1), _NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
        jnp.zeros((rows, d), jnp.float32),
    )


def _softmax_step(carry, qb, kb, vb, k_pos, q_pos, kv_len, causal, scale):
    """One KV block of the online softmax, shared by both forward bodies:
    ``qb`` [rows, D] against ``kb``, ``vb`` [block_kv, D] whose keys stand
    at ``k_pos`` [1, block_kv]; ``q_pos`` [rows, 1] (or a scalar) are the
    queries' absolute positions."""
    m_prev, l_prev, acc_prev = carry
    s = jax.lax.dot_general(
        qb,
        kb,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [rows, block_kv]

    mask = k_pos < kv_len
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    s = jnp.where(mask, s, _NEG_INF)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)  # [rows, 1]
    p = jnp.exp(s - m_new)  # [rows, block_kv] f32
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(vb.dtype),
        vb,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_prev * alpha + pv


def _softmax_store(carry, out_ref, lse_ref):
    m, l, acc = carry
    # fully-masked rows (padding, a row with no live key) have l == 0 →
    # emit zeros, not NaN
    out = acc / jnp.where(l == 0.0, 1.0, l)
    out_ref[0, 0, :, :] = out.astype(out_ref.dtype)
    # logsumexp residual for the fused backward; +inf on fully-masked rows
    # makes their recomputed probabilities exp(-1e30 - inf) = 0 there
    lse_ref[0, 0, :, :] = jnp.where(l > 0.0, m + jnp.log(l), jnp.inf)


def _kernel(
    offs_ref,  # [B] int32 scalar-prefetch: absolute position of q row 0
    lens_ref,  # [B] int32 scalar-prefetch: valid KV prefix length
    layer_ref,  # [1] int32 scalar-prefetch: read by the K/V index maps only
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, 1, Skv_pad, D]: one (layer, row, kv head) of the stack
    v_ref,  # [1, 1, 1, Skv_pad, D]
    out_ref,  # [1, 1, block_q, D]
    lse_ref,  # [1, 1, block_q, 1] f32: per-row logsumexp (backward
    # residual). The trailing singleton is a TPU tiling requirement: the
    # block's last two dims must be (divisible by 8, divisible by 128) or
    # equal the array dims — a [1, 1, block_q] block puts a size-1 head
    # axis second-to-last, which real-TPU lowering rejects (interpret
    # mode does not check; the r04 hardware sweep caught it)
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    del layer_ref
    b = pl.program_id(0)
    qi = pl.program_id(2)

    offset = offs_ref[b]
    kv_len = lens_ref[b]

    qb = q_ref[0, 0, :, :]  # [block_q, D]
    d = v_ref.shape[-1]  # the value width: the output's (D, or Dv where it differs)

    # absolute positions of this query block's rows (2D iota: TPU rule)
    q_pos = (
        offset
        + qi * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    )  # [block_q, 1]
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)  # [1, block_kv]

    # dynamic trip count: stop at the KV length bound, and (causal) at the
    # block containing this q block's last row
    hi = pl.cdiv(kv_len, block_kv)
    if causal:
        last_q = offset + (qi + 1) * block_q  # exclusive
        hi = jnp.minimum(hi, pl.cdiv(last_q, block_kv))
    hi = jnp.minimum(hi, num_kv_blocks)

    def body(j, carry):
        kb = k_ref[0, 0, 0, pl.ds(j * block_kv, block_kv), :]  # [block_kv, D]
        vb = v_ref[0, 0, 0, pl.ds(j * block_kv, block_kv), :]
        return _softmax_step(
            carry, qb, kb, vb, j * block_kv + k_ids, q_pos, kv_len, causal, scale
        )

    carry = jax.lax.fori_loop(0, hi, body, _softmax_init(block_q, d))
    _softmax_store(carry, out_ref, lse_ref)


# The decode form copies K and V in itself, a block of ``block_kv``
# positions at a time, this many copies of each in flight: the next ones
# run while the current block is in the arithmetic.
_FETCH_BUFFERS = 4


def _decode_kernel(
    offs_ref,  # [B] int32 scalar-prefetch: absolute position of q row 0
    lens_ref,  # [B] int32 scalar-prefetch: valid KV prefix length (0: a
    # row that holds no request)
    layer_ref,  # [1] int32 scalar-prefetch
    q_ref,  # [1, 1, rows, D]: the ``groups`` q heads of this kv head, each
    # with its ``sq`` queries (row = g * sq + s), padded to the tile
    k_hbm,  # [L, B, Hkv, Skv_pad, D], left where it is (HBM)
    v_hbm,
    out_ref,  # [1, 1, rows, D]
    lse_ref,  # [1, 1, rows, 1] f32
    k_buf,  # [_FETCH_BUFFERS, block_kv, D] VMEM
    v_buf,
    sem,  # DMA semaphores [2, _FETCH_BUFFERS]
    *,
    causal: bool,
    scale: float,
    sq: int,
    block_kv: int,
    num_kv_blocks: int,
):
    """One (row, kv head): K and V come in block by block, only up to the
    row's last live block, each block multiplied once for the whole group.
    A row of length 0 issues no copy and runs no iteration."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    offset = offs_ref[b]
    kv_len = lens_ref[b]
    lay = layer_ref[0]

    qb = q_ref[0, 0, :, :]  # [rows, D]
    rows, d = qb.shape[0], v_buf.shape[-1]
    if sq == 1:
        q_pos = offset
    else:
        q_pos = offset + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), sq
        )  # [rows, 1]
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)

    hi = pl.cdiv(kv_len, block_kv)
    if causal:
        hi = jnp.minimum(hi, pl.cdiv(offset + sq, block_kv))
    hi = jnp.minimum(hi, num_kv_blocks)

    def copies(j, slot):
        at = pl.ds(pl.multiple_of(j * block_kv, block_kv), block_kv)
        return (
            pltpu.make_async_copy(
                k_hbm.at[lay, b, h, at, :], k_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[lay, b, h, at, :], v_buf.at[slot], sem.at[1, slot]),
        )

    def start(j):
        @pl.when(j < hi)
        def _():
            for copy in copies(j, j % _FETCH_BUFFERS):
                copy.start()

    for j in range(_FETCH_BUFFERS - 1):
        start(j)

    def body(j, carry):
        slot = j % _FETCH_BUFFERS
        # the buffer this refills was last read an iteration ago
        start(j + _FETCH_BUFFERS - 1)
        for copy in copies(j, slot):
            copy.wait()
        return _softmax_step(
            carry, qb, k_buf[slot], v_buf[slot], j * block_kv + k_ids,
            q_pos, kv_len, causal, scale,
        )

    carry = jax.lax.fori_loop(0, hi, body, _softmax_init(rows, d))
    _softmax_store(carry, out_ref, lse_ref)


def _lanes(d: int) -> int:
    """``d`` as fast memory holds a minor axis: whole tiles of 128."""
    return -(-d // 128) * 128


def _pad_axis(x: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_kv", "interpret")
)
def _flash_fwd_impl(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    offsets: jnp.ndarray,
    kv_lens: jnp.ndarray,
    layer: jnp.ndarray,
    causal: bool,
    scale: float,
    block_q: int,
    block_kv: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``k``, ``v`` [L, B, Hkv, Skv, D] are the stacked KV cache, read at
    ``layer`` [1] int32 by the kernel itself, in the order it is stored
    (``models/transformer.py::init_cache``): no slice of the layer, no
    transpose and no copy of K or V stands in front of the kernel, inside
    a program's loops or where the cache enters and leaves it. (An
    unstacked k/v comes in as a stack of one, its own tokens transposed:
    ``_flash_fwd``.)

    A call whose ``sq`` x ``groups`` query rows fit one q block
    (``block_q``) takes the decode form (``_decode_form``: grid (batch,
    kv_heads), K/V copied in block by block up to each row's length);
    every longer one the prefill form below (grid (batch, q_heads,
    q_blocks), the (Skv, D) block of (layer, row, kv head) whole in VMEM).
    Nothing else chooses. The prefill form's ``cost_estimate`` counts one
    layer of K and V once; the decode form's the same, as the bound of
    what its rows' lengths let it skip.

    The row-major "free" view [L, B, Skv, Hkv·D] is not free under
    (8, 128) tiling: compiled for the v5e it is a reshape of the whole
    stack in every layer."""
    b, sq, hq, d = q.shape
    n_layers, _, hkv, skv, _ = k.shape
    dv = v.shape[-1]  # == d but for latent attention's expanded form (192 and 128)
    groups = hq // hkv

    block_kv = min(block_kv, skv)
    skv_pad = pl.cdiv(skv, block_kv) * block_kv
    # a no-op for a cache (its length is a multiple of the block): padding
    # a stack would copy it
    kt = _pad_axis(k, 3, skv_pad)
    vt = _pad_axis(v, 3, skv_pad)
    num_kv_blocks = skv_pad // block_kv
    cost = dict(
        flops=2 * b * hq * sq * skv * (d + dv),
        transcendentals=b * hq * sq * skv,
    )

    if sq * groups <= block_q:
        return _decode_form(
            q, kt, vt, offsets, kv_lens, layer, causal, scale, block_kv,
            num_kv_blocks, interpret, cost,
        )

    # q and the output in [B, H, S, D]: the kernel tiles (sublane=seq,
    # lane=head_dim)
    qt = jnp.swapaxes(q, 1, 2)
    block_q = min(block_q, max(sq, 16))
    sq_pad = pl.cdiv(sq, block_q) * block_q
    qt = _pad_axis(qt, 2, sq_pad)
    num_q_blocks = sq_pad // block_q

    def kv_block(bi, h, qi, offs, lens, lay):
        return (lay[0], bi, h // groups, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hq, num_q_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda bi, h, qi, *_: (bi, h, qi, 0)
            ),
            pl.BlockSpec((1, 1, 1, skv_pad, d), kv_block),
            pl.BlockSpec((1, 1, 1, skv_pad, dv), kv_block),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, dv), lambda bi, h, qi, *_: (bi, h, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda bi, h, qi, *_: (bi, h, qi, 0)
            ),
        ],
    )

    kernel = functools.partial(
        _kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=num_kv_blocks,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            bytes_accessed=(
                q.size + (k.size + v.size) // n_layers
            ) * q.dtype.itemsize,
            **cost,
        ),
        # K and V of one (row, kv head) whole, double-buffered: past the
        # default scoped limit where a key is wider than 128
        compiler_params=(None if d == dv else pltpu.CompilerParams(
            vmem_limit_bytes=int(
                4 * skv_pad * (_lanes(d) + _lanes(dv)) * q.dtype.itemsize + (8 << 20)))),
    )(offsets, kv_lens, layer, qt, kt, vt)
    return jnp.swapaxes(out[:, :, :sq, :], 1, 2), lse[:, :, :sq, 0]


def _decode_form(
    q, kt, vt, offsets, kv_lens, layer, causal, scale, block_kv,
    num_kv_blocks, interpret, cost,
):
    """The forward for a call whose ``sq`` x ``groups`` query rows fit one
    q block: grid (row, kv head), the group's heads side by side in the
    block, K and V ``kt``, ``vt`` [L, B, Hkv, Skv_pad, D] left in HBM and
    copied in by ``_decode_kernel`` up to each row's length."""
    b, sq, hq, d = q.shape
    hkv, dv = kt.shape[2], vt.shape[-1]
    groups = hq // hkv
    rows = sq * groups
    # sublane floor 16 covers the bf16 min tile (f32 needs only 8)
    rows_pad = pl.cdiv(rows, 16) * 16
    # [B, Sq, Hkv, G, D] -> [B, Hkv, G x Sq, D]: for sq = 1 a reshape
    qg = q.reshape(b, sq, hkv, groups, d).transpose(0, 2, 3, 1, 4)
    qg = _pad_axis(qg.reshape(b, hkv, rows, d), 2, rows_pad)

    def q_block(bi, h, *_):
        return (bi, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, rows_pad, d), q_block),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows_pad, dv), q_block),
            pl.BlockSpec((1, 1, rows_pad, 1), q_block),
        ],
        scratch_shapes=[
            pltpu.VMEM((_FETCH_BUFFERS, block_kv, d), kt.dtype),
            pltpu.VMEM((_FETCH_BUFFERS, block_kv, dv), vt.dtype),
            pltpu.SemaphoreType.DMA((2, _FETCH_BUFFERS)),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _decode_kernel, causal=causal, scale=scale, sq=sq,
            block_kv=block_kv, num_kv_blocks=num_kv_blocks,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, rows_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        # what a call moves hangs on its rows' lengths, which no shape
        # tells: the bound, every row full
        cost_estimate=pl.CostEstimate(
            bytes_accessed=(
                2 * q.size + (kt.size + vt.size) // kt.shape[0]
            ) * q.dtype.itemsize,
            **cost,
        ),
    )(offsets, kv_lens, layer, qg, kt, vt)
    out = out[:, :, :rows].reshape(b, hkv, groups, sq, dv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dv)
    return out, lse[:, :, :rows, 0].reshape(b, hq, sq)


def _dq_kernel(
    offs_ref,  # [B] int32 scalar-prefetch
    lens_ref,  # [B] int32 scalar-prefetch
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, Skv_pad, D]
    v_ref,  # [1, 1, Skv_pad, D]
    do_ref,  # [1, 1, block_q, D]
    lse_ref,  # [1, 1, block_q, 1] f32 (trailing 1: TPU tiling, see _kernel)
    dvec_ref,  # [1, 1, block_q, 1] f32: D = rowsum(dO ⊙ O)
    dq_ref,  # [1, 1, block_q, D] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    """dQ = scale · Σ_j dS_j K_j with dS = P ⊙ (dP − D), P recomputed from
    the forward's logsumexp — same KV loop bounds as the forward, so the
    O(S²) score matrix never materializes."""
    b = pl.program_id(0)
    qi = pl.program_id(2)
    offset = offs_ref[b]
    kv_len = lens_ref[b]

    qb = q_ref[0, 0, :, :]
    dob = do_ref[0, 0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :, :]  # [block_q, 1]
    dvec = dvec_ref[0, 0, :, :]  # [block_q, 1]

    q_pos = (
        offset + qi * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    )
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (1, block_kv), 1)

    hi = pl.cdiv(kv_len, block_kv)
    if causal:
        hi = jnp.minimum(hi, pl.cdiv(offset + (qi + 1) * block_q, block_kv))
    hi = jnp.minimum(hi, num_kv_blocks)

    def body(j, acc):
        kb = k_ref[0, 0, pl.ds(j * block_kv, block_kv), :]
        vb = v_ref[0, 0, pl.ds(j * block_kv, block_kv), :]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        k_pos = j * block_kv + k_ids
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_kv]; masked/padded → 0
        dp = jax.lax.dot_general(
            dob, vb.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dvec)  # [block_q, block_kv]
        return acc + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc0 = jnp.zeros((block_q, qb.shape[-1]), jnp.float32)
    acc = jax.lax.fori_loop(0, hi, body, acc0)
    dq_ref[0, 0, :, :] = acc * scale


def _dkv_kernel(
    offs_ref,  # [B] int32 scalar-prefetch
    lens_ref,  # [B] int32 scalar-prefetch
    q_ref,  # [1, 1, Sq_pad, D] — one query head's full (padded) sequence
    k_ref,  # [1, 1, block_kv, D]
    v_ref,  # [1, 1, block_kv, D]
    do_ref,  # [1, 1, Sq_pad, D]
    lse_ref,  # [1, 1, Sq_pad, 1] f32 (trailing 1: TPU tiling, see _kernel)
    dvec_ref,  # [1, 1, Sq_pad, 1] f32
    dk_ref,  # [1, 1, block_kv, D] f32 — revisited across the g grid axis
    dv_ref,  # [1, 1, block_kv, D] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_kv: int,
    num_q_blocks: int,
):
    """dK/dV for one KV block, accumulated over the query blocks that can
    see it (dynamic causal lower bound) and, via grid revisiting, over the
    ``groups`` query heads sharing this KV head (GQA). The g axis is the
    innermost grid dimension, so the output block stays resident while the
    group accumulates."""
    b = pl.program_id(0)
    ki = pl.program_id(2)
    g = pl.program_id(3)
    offset = offs_ref[b]
    kv_len = lens_ref[b]

    kb = k_ref[0, 0, :, :]
    vb = v_ref[0, 0, :, :]
    d = kb.shape[-1]

    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_kv), 1
    )  # [1, block_kv]
    kv_mask = k_pos < kv_len

    # causal: only query blocks whose last row reaches this KV block's
    # first position contribute (same arithmetic as the forward's hi bound,
    # seen from the KV side)
    if causal:
        lo = jnp.maximum(0, (ki * block_kv - offset) // block_q)
    else:
        lo = 0

    def body(qi, carry):
        dk_acc, dv_acc = carry
        qb = q_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        dob = do_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        dvec = dvec_ref[0, 0, pl.ds(qi * block_q, block_q), :]

        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_kv]
        q_pos = (
            offset + qi * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        )
        mask = kv_mask
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)
        # zero at masked positions (s = -inf). Zero-PADDED query rows have
        # lse = 0 and s = 0, so p = exp(0) = 1 there — those rows still
        # contribute nothing, but only because dO = 0 and D (dvec) = 0
        # make dv/ds vanish; preserve that invariant when editing.
        p = jnp.exp(s - lse)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, vb.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dvec)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_acc, dv_acc

    zeros = jnp.zeros((block_kv, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, num_q_blocks, body, (zeros, zeros))

    @pl.when(g == 0)
    def _init():
        dk_ref[0, 0, :, :] = dk * scale
        dv_ref[0, 0, :, :] = dv

    @pl.when(g > 0)
    def _accum():
        dk_ref[0, 0, :, :] += dk * scale
        dv_ref[0, 0, :, :] += dv


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_kv", "interpret")
)
def _flash_bwd_impl(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    offsets: jnp.ndarray,
    kv_lens: jnp.ndarray,
    out: jnp.ndarray,
    lse: jnp.ndarray,
    g: jnp.ndarray,
    causal: bool,
    scale: float,
    block_q: int,
    block_kv: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot = jnp.swapaxes(g, 1, 2)

    block_q = min(block_q, max(sq, 16))
    block_kv = min(block_kv, skv)
    sq_pad = pl.cdiv(sq, block_q) * block_q
    skv_pad = pl.cdiv(skv, block_kv) * block_kv
    qt = _pad_axis(qt, 2, sq_pad)
    kt = _pad_axis(kt, 2, skv_pad)
    vt = _pad_axis(vt, 2, skv_pad)
    dot = _pad_axis(dot, 2, sq_pad)  # zero-padded rows contribute nothing
    num_q_blocks = sq_pad // block_q
    num_kv_blocks = skv_pad // block_kv

    # D = rowsum(dO ⊙ O): one cheap fused elementwise+reduce, shared by
    # both kernels (padded rows: dO = 0 → D = 0)
    dvec = jnp.sum(
        dot.astype(jnp.float32)
        * _pad_axis(jnp.swapaxes(out, 1, 2), 2, sq_pad).astype(jnp.float32),
        axis=-1,
    )[..., None]  # [B, Hq, Sq_pad, 1] — trailing 1: TPU tiling (see _kernel)
    lse_pad = _pad_axis(lse, 2, sq_pad)[..., None]

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, *_: (bi, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, skv_pad, d),
                lambda bi, h, qi, *_, g_=groups: (bi, h // g_, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, skv_pad, d),
                lambda bi, h, qi, *_, g_=groups: (bi, h // g_, 0, 0),
            ),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, *_: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, h, qi, *_: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, h, qi, *_: (bi, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, h, qi, *_: (bi, h, qi, 0)
        ),
    )
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, causal=causal, scale=scale, block_q=block_q,
            block_kv=block_kv, num_kv_blocks=num_kv_blocks,
        ),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_pad, d), jnp.float32),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=5 * b * hq * sq * skv * d,
            bytes_accessed=(q.size + k.size + v.size + g.size) * q.dtype.itemsize,
            transcendentals=b * hq * sq * skv,
        ),
    )(offsets, kv_lens, qt, kt, vt, dot, lse_pad, dvec)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # g innermost: consecutive iterations revisit the same dk/dv block
        grid=(b, hkv, num_kv_blocks, groups),
        in_specs=[
            pl.BlockSpec(
                (1, 1, sq_pad, d),
                lambda bi, h, ki, gi, *_, g_=groups: (bi, h * g_ + gi, 0, 0),
            ),
            pl.BlockSpec((1, 1, block_kv, d), lambda bi, h, ki, gi, *_: (bi, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda bi, h, ki, gi, *_: (bi, h, ki, 0)),
            pl.BlockSpec(
                (1, 1, sq_pad, d),
                lambda bi, h, ki, gi, *_, g_=groups: (bi, h * g_ + gi, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, sq_pad, 1),
                lambda bi, h, ki, gi, *_, g_=groups: (bi, h * g_ + gi, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, sq_pad, 1),
                lambda bi, h, ki, gi, *_, g_=groups: (bi, h * g_ + gi, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d), lambda bi, h, ki, gi, *_: (bi, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda bi, h, ki, gi, *_: (bi, h, ki, 0)),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, scale=scale, block_q=block_q,
            block_kv=block_kv, num_q_blocks=num_q_blocks,
        ),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, skv_pad, d), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=5 * b * hq * sq * skv * d,
            bytes_accessed=(q.size + k.size + v.size + g.size) * q.dtype.itemsize,
            transcendentals=b * hq * sq * skv,
        ),
    )(offsets, kv_lens, qt, kt, vt, dot, lse_pad, dvec)

    dq = jnp.swapaxes(dq[:, :, :sq, :], 1, 2).astype(q.dtype)
    dk = jnp.swapaxes(dk[:, :, :skv, :], 1, 2).astype(k.dtype)
    dv = jnp.swapaxes(dv[:, :, :skv, :], 1, 2).astype(v.dtype)
    return dq, dk, dv


def _normalize_scalars(
    q: jnp.ndarray,
    skv: int,
    q_offset: int | jnp.ndarray,
    kv_lens: Optional[jnp.ndarray],
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row ``q_offset`` and ``kv_lens`` [B] int32, the lengths capped
    at the ``skv`` positions k and v hold."""
    b = q.shape[0]
    offsets = jnp.asarray(q_offset, jnp.int32)
    if offsets.ndim == 0:
        offsets = jnp.full((b,), offsets, jnp.int32)
    if kv_lens is None:
        lens = jnp.full((b,), skv, jnp.int32)
    else:
        lens = jnp.minimum(jnp.asarray(kv_lens, jnp.int32), skv)
    return offsets, lens


def _reference(q, k, v, offsets, kv_lens, causal, scale):
    """XLA reference with identical semantics (backward recompute path)."""
    from gofr_tpu.ops.attention import attention

    return attention(
        q, k, v, causal=causal, q_offset=offsets, kv_lens=kv_lens, scale=scale,
        impl="xla",
    )


BWD_BLOCK_Q = 512  # q rows per checkpointed backward block


def _blockwise_reference(q, k, v, offsets, kv_lens, causal, scale,
                         block_q: Optional[int] = None):
    """Semantically identical to ``_reference`` but computed q-block by
    q-block under ``jax.checkpoint``: differentiating THIS never holds more
    than one block's [block_q, Skv] score matrix — O(block_q·S) backward
    memory instead of the O(S²) of a full-sequence recompute. Serves as
    the numeric oracle for the fused Pallas backward kernels and as the
    ``FUSED_BWD = False`` fallback. dk/dv accumulate through the scan's
    carry."""
    if block_q is None:
        block_q = BWD_BLOCK_Q  # module-level lookup: tests can patch it
    b, sq, hq, d = q.shape
    if sq <= block_q:
        return _reference(q, k, v, offsets, kv_lens, causal, scale)
    n_blocks = -(-sq // block_q)
    pad = n_blocks * block_q - sq
    q_padded = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_blocks = q_padded.reshape(b, n_blocks, block_q, hq, d).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(n_blocks, dtype=jnp.int32) * block_q

    @jax.checkpoint
    def block(qb, start):
        # q rows [start, start+block_q) attend the full KV under the same
        # causal/ragged semantics (offsets shift per block)
        return _reference(qb, k, v, offsets + start, kv_lens, causal, scale)

    def body(_, inputs):
        qb, start = inputs
        return None, block(qb, start)

    _, outs = jax.lax.scan(body, None, (q_blocks, starts))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * block_q, hq, d)
    return out[:, :sq]


# Backward implementation switch: True (default) uses the fused Pallas
# kernels; False selects the checkpointed q-blockwise XLA recompute (the
# numeric oracle the fused kernels are tested against, and the escape
# hatch if a backend miscompiles the backward kernels). Read at TRACE
# time: set it before building jitted train steps — already-compiled
# functions keep the backward they were traced with until their jit
# caches are cleared (jax.clear_caches()).
FUSED_BWD = True


_LAYER_0 = np.zeros((1,), np.int32)  # an unstacked k/v is a stack of one


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, offsets, kv_lens, causal, scale, block_q, block_kv, interpret):
    return _flash_fwd(
        q, k, v, offsets, kv_lens, causal, scale, block_q, block_kv, interpret
    )[0]


def _flash_fwd(q, k, v, offsets, kv_lens, causal, scale, block_q, block_kv, interpret):
    out, lse = _flash_fwd_impl(
        q, jnp.swapaxes(k, 1, 2)[None], jnp.swapaxes(v, 1, 2)[None],
        offsets, kv_lens, _LAYER_0,
        causal, scale, block_q, block_kv, interpret,
    )
    return out, (q, k, v, offsets, kv_lens, out, lse)


def _flash_bwd(causal, scale, block_q, block_kv, interpret, residuals, g):
    q, k, v, offsets, kv_lens, out, lse = residuals
    if FUSED_BWD:
        dq, dk, dv = _flash_bwd_impl(
            q, k, v, offsets, kv_lens, out, lse, g,
            causal, scale, block_q, block_kv, interpret,
        )
    else:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _blockwise_reference(
                q_, k_, v_, offsets, kv_lens, causal, scale
            ),
            q,
            k,
            v,
        )
        dq, dk, dv = vjp(g)
    return (
        dq,
        dk,
        dv,
        np.zeros(offsets.shape, jax.dtypes.float0),
        np.zeros(kv_lens.shape, jax.dtypes.float0),
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    kv_lens: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: Optional[bool] = None,
    layer: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flash attention. q [B, Sq, Hq, D]; k, v [B, Skv, Hkv, D].

    ``q_offset``: scalar or [B] absolute position of q row 0 (ragged
    decode). ``kv_lens``: optional [B] count of valid KV positions
    (padded/unwritten cache tail is masked). Differentiable via the fused
    backward kernels (gradients flow to q, k, v; not to the position
    scalars).

    ``layer`` (int32 scalar): k, v are the stacked KV cache [L, B, Hkv,
    Skv, D] and the kernel reads that layer of it (the serving path: no
    gradient is defined through the stack).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    skv = k.shape[1] if layer is None else k.shape[3]
    offsets, lens = _normalize_scalars(q, skv, q_offset, kv_lens)
    if layer is None:
        return _flash(
            q, k, v, offsets, lens, causal, float(scale), block_q, block_kv,
            interpret,
        )
    return _flash_fwd_impl(
        q, k, v, offsets, lens, jnp.asarray(layer, jnp.int32).reshape(1),
        causal, float(scale), block_q, block_kv, interpret,
    )[0]
