"""Power retention (degree 2): attention whose cache is a fixed-size state.

For token t of a sequence, one kv head with the query heads that share it,
head size d, a gate ``log g_t <= 0`` per kv head:

- attention form:  ``y_t = sum_{j<=t} w_tj v_j / (sum_{j<=t} w_tj + eps)``,
  ``w_tj = exp(sum_{s=j+1..t} log g_s) * (q_t . k_j / sqrt(d))^2 >= 0``;
- recurrent form, the same numbers:  ``S_t = g_t S_{t-1} + v_t phi(k_t)^T``,
  ``z_t = g_t z_{t-1} + phi(k_t)``, ``y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)``
  with ``phi(q) . phi(k) = (q . k / sqrt(d))^2``;
- chunked form (prefill): inside a chunk the attention form, from earlier
  chunks ``S_prev phi(q_t)`` and ``z_prev . phi(q_t)`` times the decay from
  the chunk's start to t; numerators and denominators add before the
  division.

``phi`` is laid out for 128 lanes: ``d/2 + 1`` rows of ``d`` entries, row r
holding ``c_r x_j x_{(j+r) mod d} / sqrt(d)`` (a lane roll). Row 0 is the
squares (c = 1), rows 1..d/2-1 each hold d distinct off-diagonal products
once (c = sqrt 2), and row d/2 holds its d/2 products twice (c = 1: twice at
weight 1 is once at weight sqrt 2 in every inner product). That is the
``d (d+1) / 2`` distinct products in ``(d/2 + 1) d`` entries (8,256 in 8,320
for d = 128): the 0.8% a TPU's tiling would pad anyway.

The state of one (layer, row, kv head) is ``S`` [d, phi_dim] (v's entries
by phi's) and ``z`` [d/2 + 1, d] (phi's rows by lanes), float32 unless the cache's type says
otherwise; sums run in float32 either way.

Shapes: q [B, T, H, d]; k, v [B, T, Hkv, d]; log_g [B, T, Hkv] float32;
``valid`` [B, T] bool. A token that is not valid (bucket padding) has g = 1
and phi(k) = 0: it leaves the state as it was.

Each form that serves has two implementations: XLA (any backend, and what
the tests compare everything with) and a Pallas kernel for the TPU that
reads its layer out of the stacked state and writes it back in place: the
one-token step (``retention_step_pallas``) and the chunked form
(``retention_chunk_pallas``). ``retention_cached`` chooses.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

EPS = 1e-6  # added to the sum of weights before the division
SUB_CHUNK = 128  # tokens whose pairwise weights are formed at once (prefill)
_HI = jax.lax.Precision.HIGHEST  # sums over the state keep float32's digits


def phi_dim(head_dim: int) -> int:
    return (head_dim // 2 + 1) * head_dim


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """[..., d] -> [..., phi_dim(d)] float32 (module docstring)."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"power retention needs an even head size, got {d}")
    x = x.astype(jnp.float32) * (d ** -0.25)
    rows = [x * x]
    rows += [(2.0 ** 0.5) * x * jnp.roll(x, -r, axis=-1) for r in range(1, d // 2)]
    rows.append(x * jnp.roll(x, -(d // 2), axis=-1))
    return jnp.concatenate(rows, axis=-1)


def init_state(batch: int, n_kv_heads: int, head_dim: int, dtype: Any = jnp.float32,
               layers: Optional[int] = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Zero (S, z); with ``layers`` stacked on a leading axis."""
    lead = (batch,) if layers is None else (layers, batch)
    return (jnp.zeros(lead + (n_kv_heads, head_dim, phi_dim(head_dim)), dtype),
            jnp.zeros(lead + (n_kv_heads, head_dim // 2 + 1, head_dim), dtype))


def _normalise(num: jnp.ndarray, den: jnp.ndarray) -> jnp.ndarray:
    """Weighted sum over the sum of weights (``den`` broadcasts over d)."""
    return num / (den + EPS)


def _grouped(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    b, t, h, d = q.shape
    return q.reshape(b, t, n_kv, h // n_kv, d)


def _mask_pads(k: jnp.ndarray, log_g: jnp.ndarray, valid: Optional[jnp.ndarray]):
    log_g = log_g.astype(jnp.float32)
    if valid is None:
        return k, log_g
    return (jnp.where(valid[:, :, None, None], k, jnp.zeros_like(k)),
            jnp.where(valid[:, :, None], log_g, 0.0))


def retention_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                        valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The attention form over one whole sequence (no state in or out):
    the no-cache forward, quadratic in T. -> [B, T, H, d] float32."""
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    k, log_g = _mask_pads(k, log_g, valid)
    qg = _grouped(q.astype(jnp.float32), n_kv)
    scores = jnp.einsum("bthrd,bjhd->bhrtj", qg, k.astype(jnp.float32), precision=_HI)
    a = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)  # [B, Hkv, T]
    causal = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.exp(jnp.where(causal, a[:, :, :, None] - a[:, :, None, :], -jnp.inf))
    w = decay[:, :, None] * jnp.square(scores * (d ** -0.5))
    num = jnp.einsum("bhrtj,bjhd->bthrd", w, v.astype(jnp.float32), precision=_HI)
    den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)[..., None]
    return _normalise(num, den).reshape(b, t, h, d)


def _sub_chunk(carry, xs, d: int):
    """One sub-chunk of the chunked form; shapes lead with B."""
    s, z = carry  # [B, Hkv, d, P], [B, Hkv, P] float32
    qg, k, v, log_g = xs  # [B, C, Hkv, R, d], [B, C, Hkv, d] x2, [B, C, Hkv]
    c = k.shape[1]
    a = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)  # [B, Hkv, C], <= 0
    # earlier chunks, through the state, decayed from the chunk's start to t
    pq = phi(qg)  # [B, C, Hkv, R, P]
    grow = jnp.exp(a).transpose(0, 2, 1)[..., None, None]  # [B, C, Hkv, 1, 1]
    num = jnp.einsum("bchrp,bhdp->bchrd", pq, s, precision=_HI) * grow
    den = jnp.einsum("bchrp,bhp->bchr", pq, z, precision=_HI)[..., None] * grow
    # inside the chunk, the attention form
    scores = jnp.einsum("bchrd,bjhd->bhrcj", qg, k, precision=_HI) * (d ** -0.5)
    causal = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(causal, a[:, :, :, None] - a[:, :, None, :], -jnp.inf))
    w = decay[:, :, None] * jnp.square(scores)  # [B, Hkv, R, C, C]
    num = num + jnp.einsum("bhrcj,bjhd->bchrd", w, v, precision=_HI)
    den = den + jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)[..., None]
    # the state at the chunk's end
    keep = jnp.exp(a[:, :, -1:] - a).transpose(0, 2, 1)  # [B, C, Hkv]: decay from j to the end
    pk = phi(k) * keep[..., None]  # [B, C, Hkv, P]
    total = jnp.exp(a[:, :, -1])  # [B, Hkv]
    s = s * total[..., None, None] + jnp.einsum("bchd,bchp->bhdp", v, pk, precision=_HI)
    z = z * total[..., None] + jnp.sum(pk, axis=1)
    return (s, z), _normalise(num, den)


def retention_chunk(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                    s: jnp.ndarray, z: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
                    sub_chunk: int = SUB_CHUNK) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The chunked form over T tokens from the state (s, z) -> (y [B, T, H, d]
    float32, s, z in the types they came in). T is cut into sub-chunks of
    ``sub_chunk`` (a last shorter one is padded with tokens that are not
    valid), so pairwise weights and phi(q) exist for one sub-chunk at a time."""
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    k, log_g = _mask_pads(k, log_g, valid)
    c = min(sub_chunk, t)
    pad = -t % c
    qg = _grouped(q.astype(jnp.float32), n_kv)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    if pad:  # zero keys and log g = 0 leave the state alone
        qg, k, v, log_g = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                           for x in (qg, k, v, log_g))
    n = (t + pad) // c
    xs = tuple(x.reshape((b, n, c) + x.shape[2:]).swapaxes(0, 1) for x in (qg, k, v, log_g))
    flat = z.astype(jnp.float32).reshape(b, n_kv, -1)  # z is kept as phi's rows by lanes
    (s_new, z_new), y = jax.lax.scan(
        functools.partial(_sub_chunk, d=d), (s.astype(jnp.float32), flat), xs)
    y = y.swapaxes(0, 1).reshape(b, t + pad, h, d)[:, :t]
    return y, s_new.astype(s.dtype), z_new.reshape(z.shape).astype(z.dtype)


def retention_step(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                   s: jnp.ndarray, z: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The recurrent form, one token (T = 1), in XLA -> (y [B, 1, H, d]
    float32, s, z)."""
    b, _, h, d = q.shape
    n_kv = k.shape[2]
    g = jnp.exp(log_g.astype(jnp.float32))[:, 0]  # [B, Hkv]
    pk = phi(k[:, 0])  # [B, Hkv, P]
    s_new = s.astype(jnp.float32) * g[..., None, None] + \
        v[:, 0].astype(jnp.float32)[..., None] * pk[:, :, None, :]
    z_new = z.astype(jnp.float32).reshape(b, n_kv, -1) * g[..., None] + pk
    s_new, z_new = s_new.astype(s.dtype), z_new.astype(z.dtype)
    pq = phi(_grouped(q, n_kv)[:, 0])  # [B, Hkv, R, P]
    num = jnp.einsum("bhrp,bhdp->bhrd", pq, s_new.astype(jnp.float32), precision=_HI)
    den = jnp.einsum("bhrp,bhp->bhr", pq, z_new.astype(jnp.float32), precision=_HI)
    y = _normalise(num, den[..., None])
    return y.reshape(b, 1, h, d), s_new, z_new.reshape(z.shape)


# -- the stacked state: [L, B, Hkv, d, P] and [L, B, Hkv, d/2+1, d] -------------------------

def _use_pallas(impl: str, head_dim: int) -> bool:
    if impl == "auto":
        return jax.default_backend() == "tpu" and head_dim % 128 == 0
    return impl == "pallas"


def retention_cached(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                     s_stack: jnp.ndarray, z_stack: jnp.ndarray, layer: jnp.ndarray,
                     valid: Optional[jnp.ndarray] = None, impl: str = "auto",
                     live: Optional[jnp.ndarray] = None,
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """This call's T tokens through layer ``layer`` of the stacked state ->
    (y, s_stack, z_stack): the buffers that came in, that layer advanced.
    T = 1 is the recurrent step (on the TPU the Pallas kernel, which touches
    nothing but its layer), T > 1 the chunked form. ``live`` [B] (the decode
    pool's slots that hold a request): a row that is not live keeps its
    state, its output is not defined, and the kernel moves nothing for it."""
    t = q.shape[1]
    if t == 1 and valid is None and _use_pallas(impl, q.shape[-1]):
        with jax.named_scope("attn.retention.step"):
            return retention_step_pallas(q, k, v, log_g, s_stack, z_stack, layer, live,
                                         interpret=jax.default_backend() != "tpu")
    if t % SUB_CHUNK == 0 and q.shape[-1] == SUB_CHUNK and _use_pallas(impl, q.shape[-1]):
        with jax.named_scope("attn.retention.chunk"):
            return retention_chunk_pallas(q, k, v, log_g, s_stack, z_stack, layer, valid,
                                          interpret=jax.default_backend() != "tpu")
    s = jax.lax.dynamic_index_in_dim(s_stack, layer, 0, keepdims=False)
    z = jax.lax.dynamic_index_in_dim(z_stack, layer, 0, keepdims=False)
    if t == 1 and valid is None:
        with jax.named_scope("attn.retention.step"):
            y, s_new, z_new = retention_step(q, k, v, log_g, s, z)
            if live is not None:
                keep = (live > 0)[:, None, None, None]
                s_new, z_new = jnp.where(keep, s_new, s), jnp.where(keep, z_new, z)
            s, z = s_new, z_new
    else:
        with jax.named_scope("attn.retention.chunk"):
            y, s, z = retention_chunk(q, k, v, log_g, s, z, valid)
    s_stack = jax.lax.dynamic_update_index_in_dim(s_stack, s, layer, 0)
    z_stack = jax.lax.dynamic_update_index_in_dim(z_stack, z, layer, 0)
    return y, s_stack, z_stack


# -- the one-token step on the TPU ---------------------------------------------------

def _for_each_group(rows: int, body) -> None:
    """``body(start, n)`` over phi's rows in groups of 8 (one tile of
    sublanes): the whole groups under a ``fori_loop`` with ``start`` traced
    (a multiple of 8) and n = 8, so that a kernel holds 8 copies of its row
    body and not d/2 + 1; then the rows past the last whole group, ``start``
    static. A dynamic slice of sublanes has to start on a tile."""
    from jax.experimental import pallas as pl

    whole = rows // 8

    def group(g, carry):
        body(pl.multiple_of(g * 8, 8), 8)
        return carry

    jax.lax.fori_loop(0, whole, group, 0)
    if rows > whole * 8:
        body(whole * 8, rows - whole * 8)


def _lanes_of(start, i: int, lanes: int):
    """The lanes of phi's row ``start + i``."""
    from jax.experimental import pallas as pl

    if isinstance(start, int):
        return slice((start + i) * lanes, (start + i + 1) * lanes)
    return pl.ds(pl.multiple_of((start + i) * lanes, lanes), lanes)


def _step_kernel(layer_ref, live_ref, g_ref, pq_ref, pk_ref, vb_ref, s_ref, z_ref,
                 num_ref, den_ref, s_out, z_out, *, lanes: int, rows: int):
    """Grid (row, kv head): one step holds one head's whole S [d, rows*lanes]
    and walks phi's rows, each [d, lanes] of S and [1, lanes] of z. A row
    that is not live runs nothing, and the index maps hand its steps the
    state block of a neighbouring live row, which the pipeline then neither
    fetches nor writes again (``_state_block``)."""
    del layer_ref  # used by the index maps
    from jax.experimental import pallas as pl

    row = pl.program_id(0)
    num_ref[0, 0] = jnp.zeros(num_ref.shape[2:], jnp.float32)  # [R8, d]
    den_ref[0, 0] = jnp.zeros(den_ref.shape[2:], jnp.float32)

    # live_ref: [0..B) the rows' flags, [B..2B) the row whose block a step
    # is handed, [2B] how many rows are live
    n_rows = pl.num_programs(0)

    @pl.when((live_ref[row] == 0) & (live_ref[2 * n_rows] == 0))
    def _():  # nothing is live: every step holds block (0, 0), written back once, as it was
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    g = g_ref[row, pl.program_id(1)]  # read out here: a branch has no program_id
    pl.when(live_ref[row] != 0)(functools.partial(
        _step_row, g, pq_ref, pk_ref, vb_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out,
        lanes=lanes, rows=rows))


def _step_row(g, pq_ref, pk_ref, vb_ref, s_ref, z_ref, num_ref, den_ref, s_out, z_out, *,
              lanes: int, rows: int):
    from jax.experimental import pallas as pl

    vb = vb_ref[0, 0]  # [d, lanes]: v's entries down the sublanes, repeated along the lanes

    def group(start, n):
        tile = pl.ds(start, n)
        pk = pk_ref[0, 0, tile, :]  # [n, lanes]
        z_new = (z_ref[0, 0, 0, tile, :].astype(jnp.float32) * g + pk).astype(z_out.dtype)
        z_out[0, 0, 0, tile, :] = z_new
        for i in range(n):
            at = _lanes_of(start, i, lanes)
            pq = pq_ref[0, 0, :, at]  # [R8, lanes] float32
            s_new = (s_ref[0, 0, 0, :, at].astype(jnp.float32) * g
                     + vb * pk[i:i + 1]).astype(s_out.dtype)
            s_out[0, 0, 0, :, at] = s_new
            num_ref[0, 0] += jax.lax.dot_general(
                pq, s_new.astype(jnp.float32), (((1,), (1,)), ((), ())),
                precision=_HI, preferred_element_type=jnp.float32)
            den_ref[0, 0] += jnp.broadcast_to(
                jnp.sum(pq * z_new[i:i + 1].astype(jnp.float32), axis=-1, keepdims=True),
                den_ref.shape[2:])

    _for_each_group(rows, group)


def _state_block(live: jnp.ndarray, n_kv: int):
    """-> (the scalars the step kernel is handed, the index map of a state
    block). A live row's step (r, n) holds block (r, n). A row that is not
    live holds, for all its steps, ONE block the pipeline already has: the
    last head of the nearest live row before it, or the first head of the
    first live row if none is before it (block (0, 0) if nothing is live).
    Consecutive steps on one block index fetch and write nothing."""
    b = live.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    on = live > 0
    before = jax.lax.cummax(jnp.where(on, idx, -1))  # the nearest live row at or before r
    first = jnp.argmax(on).astype(jnp.int32)  # 0 if none
    src = jnp.where(before >= 0, before, first)
    scalars = jnp.concatenate(
        [on.astype(jnp.int32), src, jnp.sum(on, dtype=jnp.int32)[None]])

    def block(r, n, lyr, flags, g):
        held = flags[b + r]
        head = jnp.where(flags[r] != 0, n, jnp.where(held < r, n_kv - 1, 0))
        return (lyr[0], held, head, 0, 0)

    return scalars, block


def retention_step_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                          s_stack: jnp.ndarray, z_stack: jnp.ndarray, layer: jnp.ndarray,
                          live: Optional[jnp.ndarray] = None, interpret: bool = False,
                          ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``retention_step`` on layer ``layer`` of the stacked state, in place:
    the state is aliased in to out and the kernel's blocks are that layer's
    alone, so a step reads and writes one layer's state once and nothing
    else of the stack. phi(q), phi(k) and v's broadcast are made outside
    (2% of the state's bytes)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, d = q.shape
    n_kv = k.shape[2]
    rep = h // n_kv
    rep8 = -(-rep // 8) * 8
    rows, width = d // 2 + 1, phi_dim(d)
    g = jnp.exp(log_g.astype(jnp.float32))[:, 0]  # [B, Hkv]
    pq = phi(_grouped(q, n_kv)[:, 0])  # [B, Hkv, R, P]
    pq = jnp.pad(pq, ((0, 0), (0, 0), (0, rep8 - rep), (0, 0)))
    pk = phi(k[:, 0]).reshape(b, n_kv, rows, d)
    vb = jnp.broadcast_to(v[:, 0].astype(jnp.float32)[..., None], (b, n_kv, d, d))
    kernel = functools.partial(_step_kernel, lanes=d, rows=rows)
    flags, state_block = _state_block(
        jnp.ones((b,), jnp.int32) if live is None else live, n_kv)
    own = lambda r, n, lyr, flags, g: (r, n, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, rep8, width), own),
            pl.BlockSpec((1, 1, rows, d), own),
            pl.BlockSpec((1, 1, d, d), own),
            pl.BlockSpec((1, 1, 1, d, width), state_block),
            pl.BlockSpec((1, 1, 1, rows, d), state_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep8, d), own),
            pl.BlockSpec((1, 1, rep8, d), own),
            pl.BlockSpec((1, 1, 1, d, width), state_block),
            pl.BlockSpec((1, 1, 1, rows, d), state_block),
        ],
    )
    block_bytes = d * width * s_stack.dtype.itemsize
    num, den, s_stack, z_stack = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, rep8, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, rep8, d), jnp.float32),
            jax.ShapeDtypeStruct(s_stack.shape, s_stack.dtype),
            jax.ShapeDtypeStruct(z_stack.shape, z_stack.dtype),
        ],
        # operands count the scalar prefetches: layer, flags, g, pq, pk, vb, S, z
        input_output_aliases={6: 2, 7: 3},
        compiler_params=pltpu.CompilerParams(
            # in order: a row that is not live leans on the block before it
            dimension_semantics=("arbitrary", "arbitrary"),
            # S in and out, each double-buffered, and room for the rest
            vmem_limit_bytes=int(4 * block_bytes + (24 << 20))),
        interpret=interpret,
        name="retention_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), flags, g, pq, pk, vb, s_stack, z_stack)
    y = _normalise(num[:, :, :rep], den[:, :, :rep, :1])
    return y.reshape(b, 1, h, d), s_stack, z_stack


# -- the chunked form on the TPU -------------------------------------------------------

def _chunk_kernel(layer_ref, total_ref, q2_ref, q_ref, k2_ref, k_ref, v_ref, acol_ref, arow_ref,
                  s_ref, z_ref, y_ref, s_out, z_out, den_ref, *, lanes: int, rows: int, rep: int):
    """Grid (row, kv head, sub-chunk), the sub-chunks in order: the head's
    state stays in the output block while its C-token sub-chunks pass.
    q2 and k2 carry the decays that are safe to fold in (module text)."""
    del layer_ref
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    total = total_ref[pl.program_id(0), pl.program_id(1), step]  # exp(a_C)
    q2, k2 = q2_ref[0, 0, 0], k2_ref[0, 0, 0]  # [R*C, d], [C, d]
    v = v_ref[0, 0, 0]  # [C, d]
    vt = v.T
    c = v.shape[0]
    scale = lanes ** -0.5  # phi's two factors of d^-1/4
    y_ref[0, 0, 0] = jnp.zeros(q2.shape, jnp.float32)  # the numerator, until the division
    den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)

    def group(start, n):
        tile = pl.ds(start, n)
        z_old = z_out[0, 0, 0, tile, :].astype(jnp.float32)  # [n, lanes]
        z_add = []
        for i in range(n):
            j = start + i
            # row 0 is the squares and the last row holds its products twice
            ends = (j in (0, rows - 1)) if isinstance(j, int) else (j == 0) | (j == rows - 1)
            weight = scale * jnp.where(ends, 1.0, 2.0 ** 0.5)
            shift = (lanes - j) % lanes
            pq = q2 * pltpu.roll(q2, shift, 1) * weight
            pk = k2 * pltpu.roll(k2, shift, 1) * weight
            at = _lanes_of(start, i, lanes)
            s_j = s_out[0, 0, 0, :, at].astype(jnp.float32)  # [d, lanes]
            y_ref[0, 0, 0] += jax.lax.dot_general(
                pq, s_j, (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)
            den_ref[...] += jnp.sum(pq * z_old[i:i + 1], axis=-1, keepdims=True)
            s_out[0, 0, 0, :, at] = (s_j * total + jnp.dot(
                vt, pk, precision=_HI, preferred_element_type=jnp.float32)).astype(s_out.dtype)
            z_add.append(jnp.sum(pk, axis=0, keepdims=True))
        z_out[0, 0, 0, tile, :] = (
            z_old * total + jnp.concatenate(z_add, axis=0)).astype(z_out.dtype)

    _for_each_group(rows, group)
    # inside the sub-chunk: the attention form
    scores = jax.lax.dot_general(q_ref[0, 0, 0], k_ref[0, 0, 0], (((1,), (1,)), ((), ())),
                                 precision=_HI, preferred_element_type=jnp.float32) * scale
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    log_decay = acol_ref[0, 0, 0][:, :c] - arow_ref[0, 0, 0]  # [C, C]: a_t - a_j
    decay = jnp.where(j_idx <= t_idx, jnp.exp(jnp.minimum(log_decay, 0.0)), 0.0)
    w = jnp.concatenate([decay] * rep, axis=0) * scores * scores  # [R*C, C]
    num = y_ref[0, 0, 0] + jnp.dot(w, v, precision=_HI, preferred_element_type=jnp.float32)
    den = den_ref[...] + jnp.sum(w, axis=-1, keepdims=True)
    y_ref[0, 0, 0] = num / (den + EPS)


def retention_chunk_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
                           s_stack: jnp.ndarray, z_stack: jnp.ndarray, layer: jnp.ndarray,
                           valid: Optional[jnp.ndarray] = None, sub_chunk: int = SUB_CHUNK,
                           interpret: bool = False,
                           ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``retention_chunk`` on layer ``layer`` of the stacked state, the state
    aliased in to out. T must be a multiple of ``sub_chunk`` (a bucket is),
    and ``sub_chunk`` the head size (one tile of lanes). Decays whose
    exponent is <= 0 are folded into the operands, since phi is quadratic:
    ``phi(q e^{a_t/2}) = e^{a_t} phi(q)`` (from the chunk's start to t) and
    ``phi(k e^{(a_C-a_j)/2}) = e^{a_C-a_j} phi(k)`` (from j to the chunk's
    end); ``a_t - a_j`` inside the chunk is formed in the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    n_kv = k.shape[2]
    rep = h // n_kv
    c = sub_chunk
    if t % c or c != d:
        raise ValueError(f"the chunk kernel takes T % {c} == 0 and head size {c}, got {t}, {d}")
    n = t // c
    rows, width = d // 2 + 1, phi_dim(d)
    k, log_g = _mask_pads(k, log_g, valid)
    a = jnp.cumsum(log_g.reshape(b, n, c, n_kv), axis=2)  # [B, n, C, Hkv], <= 0
    a = a.transpose(0, 3, 1, 2)  # [B, Hkv, n, C]
    total = jnp.exp(a[..., -1])  # [B, Hkv, n]
    kf = k.astype(jnp.float32).reshape(b, n, c, n_kv, d).transpose(0, 3, 1, 2, 4)  # [B,Hkv,n,C,d]
    vf = v.astype(jnp.float32).reshape(b, n, c, n_kv, d).transpose(0, 3, 1, 2, 4)
    k2 = kf * jnp.exp(0.5 * (a[..., -1:] - a))[..., None]
    # queries: the R heads of a kv head stacked along the rows, (r, t)
    qf = q.astype(jnp.float32).reshape(b, n, c, n_kv, rep, d).transpose(0, 3, 1, 4, 2, 5)
    q2 = (qf * jnp.exp(0.5 * a)[:, :, :, None, :, None]).reshape(b, n_kv, n, rep * c, d)
    qf = qf.reshape(b, n_kv, n, rep * c, d)
    acol = jnp.broadcast_to(a[..., None], (b, n_kv, n, c, d))  # a_t down the sublanes
    arow = a[:, :, :, None, :]  # [B, Hkv, n, 1, C]: a_j along the lanes
    kernel = functools.partial(_chunk_kernel, lanes=d, rows=rows, rep=rep)
    per_sub = lambda shape: pl.BlockSpec(  # noqa: E731
        (1, 1, 1) + shape, lambda r, m, s, lyr, tot: (r, m, s, 0, 0))
    state = lambda shape: pl.BlockSpec(  # noqa: E731
        (1, 1, 1) + shape, lambda r, m, s, lyr, tot: (lyr[0], r, m, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_kv, n),
        in_specs=[per_sub((rep * c, d)), per_sub((rep * c, d)), per_sub((c, d)), per_sub((c, d)),
                  per_sub((c, d)), per_sub((c, d)), per_sub((1, c)),
                  state((d, width)), state((rows, d))],
        out_specs=[per_sub((rep * c, d)), state((d, width)), state((rows, d))],
        scratch_shapes=[pltpu.VMEM((rep * c, 1), jnp.float32)],  # the sum of weights
    )
    block_bytes = d * width * s_stack.dtype.itemsize
    y, s_stack, z_stack = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, n, rep * c, d), jnp.float32),
            jax.ShapeDtypeStruct(s_stack.shape, s_stack.dtype),
            jax.ShapeDtypeStruct(z_stack.shape, z_stack.dtype),
        ],
        # operands count the scalar prefetches: layer, total, then the seven blocks, S, z
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(4 * block_bytes + (40 << 20))),
        interpret=interpret,
        name="retention_chunk",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), total, q2, qf, k2, kf, vf, acol, arow,
      s_stack, z_stack)
    y = y.reshape(b, n_kv, n, rep, c, d).transpose(0, 2, 4, 1, 3, 5).reshape(b, t, h, d)
    return y, s_stack, z_stack
