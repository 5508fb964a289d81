"""The selective scan of a state-space (Mamba-1) layer: a diagonal state per
channel, updated elementwise token by token.

For token t of a sequence, channel i of ``d_inner`` and state entry n of
``d_state``, with ``delta_t[i] > 0``, ``A[n, i] < 0``, and ``B_t``, ``C_t`` of
``d_state`` entries shared by every channel::

    s_t[n, i] = exp(delta_t[i] A[n, i]) s_{t-1}[n, i] + delta_t[i] u_t[i] B_t[n]
    y_t[i]    = sum_n s_t[n, i] C_t[n]

(the skip ``D * u``, the gate and the projections around it are the
caller's: models/transformer.py). Nothing is a matrix product: a token is
``d_state * d_inner`` multiply-adds and as many ``exp``, all on the vector
and transcendental units.

The state is kept ``[d_state, d_inner]`` a row and layer, the channels along
the lanes: ``d_state`` is 16, and a TPU's tiling would pad an axis of 16 in
the minor place to 128, eight times the bytes in HBM and in every read.
Stacked it is ``[L, B, d_state, d_inner]``, float32 unless the model's entry
says otherwise; sums run in float32 either way.

Shapes: ``u``, ``delta`` [B, T, Di] float32; ``a`` [N, Di] float32 (``A``
as above, already ``-exp(A_log)``); ``b``, ``c`` [B, T, N] float32; state
[B, N, Di]. A token that is not valid (bucket padding) is given ``delta``
0 by the caller: its decay is 1 and it adds nothing, so it leaves the state
as it was.

Each form has an XLA implementation (any backend; what the tests compare
everything with is ``scan_tokens``, one token a step) and on the TPU a
Pallas kernel that reads its layer out of the stacked state and writes it
back in place: the one-token step (``step_pallas``: one grid step a row,
nothing moved for a row that is not live) and the chunked scan
(``scan_pallas``: a row's block of channels stays in registers while its
tokens pass). ``scan_cached`` chooses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

UNROLL = 8  # tokens a step of the chunked forms: one tile of sublanes
LANE_BLOCK = 1024  # channels a grid step of the chunked kernel holds: 16 x 1024 float32, 16 vregs


def _advance(s: jnp.ndarray, a: jnp.ndarray, u: jnp.ndarray, delta: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token: s [B, N, Di]; u, delta [B, Di]; b, c [B, N] -> (s, y [B, Di])."""
    s = jnp.exp(delta[:, None, :] * a) * s + (delta * u)[:, None, :] * b[:, :, None]
    return s, jnp.sum(s * c[:, :, None], axis=1)


def scan_tokens(u: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, state: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence as written, one token a ``lax.scan`` step -> (y
    [B, T, Di] float32, the state in the type it came in)."""
    xs = tuple(jnp.swapaxes(x.astype(jnp.float32), 0, 1) for x in (u, delta, b, c))
    s, y = jax.lax.scan(lambda s, x: _advance(s, a, *x), state.astype(jnp.float32), xs)
    return jnp.swapaxes(y, 0, 1), s.astype(state.dtype)


def scan_chunked(u: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                 c: jnp.ndarray, state: jnp.ndarray, unroll: int = UNROLL,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``scan_tokens`` with ``unroll`` tokens a loop step (the same
    arithmetic in the same order, so the same numbers): the state passes
    from token to token inside one fused body and the loop runs T / unroll
    steps. A T that is no multiple is padded with tokens of ``delta`` 0."""
    bsz, t, _ = u.shape
    pad = -t % unroll
    xs = tuple(x.astype(jnp.float32) for x in (u, delta, b, c))
    if pad:
        xs = tuple(jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in xs)
    n = (t + pad) // unroll
    xs = tuple(x.reshape((bsz, n, unroll) + x.shape[2:]).swapaxes(0, 1) for x in xs)

    def body(s, chunk):
        ys = []
        for i in range(unroll):
            s, y = _advance(s, a, *(x[:, i] for x in chunk))
            ys.append(y)
        return s, jnp.stack(ys, axis=1)

    s, y = jax.lax.scan(body, state.astype(jnp.float32), xs)
    y = y.swapaxes(0, 1).reshape(bsz, t + pad, -1)[:, :t]
    return y, s.astype(state.dtype)


# -- the stacked state [L, B, N, Di] ---------------------------------------------------------

def _use_pallas(impl: str, d_inner: int) -> bool:
    if impl == "auto":
        return jax.default_backend() == "tpu" and d_inner % LANE_BLOCK == 0
    return impl == "pallas"


def scan_cached(u: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, stack: jnp.ndarray, layer: jnp.ndarray, impl: str = "auto",
                live: Optional[jnp.ndarray] = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """This call's T tokens through layer ``layer`` of the stacked state ->
    (y [B, T, Di] float32, the stack that came in, that layer advanced).
    T = 1 is the one-token step, T > 1 the chunked scan; on the TPU each is
    a Pallas kernel that touches nothing but its layer. ``live`` [B] (the
    decode pool's slots that hold a request): a row that is not live keeps
    its state, its output is not defined, and the step kernel moves nothing
    for it."""
    t = u.shape[1]
    pallas = _use_pallas(impl, u.shape[-1])
    interpret = jax.default_backend() != "tpu"
    if t == 1 and pallas:
        return step_pallas(u, delta, a, b, c, stack, layer, live, interpret=interpret)
    if live is not None:
        # a step size of 0 is a decay of 1 and nothing added: the state of a
        # row that is not live comes out as it went in
        delta = jnp.where((live > 0)[:, None, None], delta, 0.0)
    if t % UNROLL == 0 and pallas:
        return scan_pallas(u, delta, a, b, c, stack, layer, interpret=interpret)
    s = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    if t == 1:
        s_new, y = _advance(s.astype(jnp.float32), a, u[:, 0], delta[:, 0], b[:, 0], c[:, 0])
        y, s_new = y[:, None], s_new.astype(s.dtype)
    else:
        y, s_new = scan_chunked(u, delta, a, b, c, s)
    return y, jax.lax.dynamic_update_index_in_dim(stack, s_new, layer, 0)


# -- the one-token step on the TPU -------------------------------------------------------------

def _step_kernel(layer_ref, flags_ref, delta_ref, du_ref, b_ref, c_ref, a_ref, s_ref,
                 y_ref, s_out):
    """Grid (row): one step holds one row's whole state [N, Di]. A row that
    is not live computes nothing, and the index map hands its step the
    state block of a neighbouring live row, which the pipeline then neither
    fetches nor writes again (``_state_block``)."""
    del layer_ref  # used by the index maps
    from jax.experimental import pallas as pl

    row = pl.program_id(0)
    n_rows = pl.num_programs(0)
    # flags_ref: [0..B) the rows' flags, [B..2B) the row whose block a step
    # is handed, [2B] how many rows are live

    @pl.when((flags_ref[row] == 0) & (flags_ref[2 * n_rows] == 0))
    def _():  # nothing is live: every step holds block 0, written back once, as it was
        s_out[...] = s_ref[...]

    @pl.when(flags_ref[row] == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(flags_ref[row] != 0)
    def _():
        s = (jnp.exp(delta_ref[0] * a_ref[...]) * s_ref[0, 0].astype(jnp.float32)
             + du_ref[0] * b_ref[0])  # [1, Di] x [N, Di], [1, Di] x [N, 1]
        s_out[0, 0] = s.astype(s_out.dtype)
        y_ref[0] = jnp.sum(s * c_ref[0], axis=0, keepdims=True)


def _state_block(live: jnp.ndarray):
    """-> (the scalars the step kernel is handed, the index map of a state
    block). A live row's step holds its own block. A row that is not live
    holds a block the pipeline already has: that of the nearest live row
    before it, or of the first live row if none is before it (block 0 if
    nothing is live). Consecutive steps on one block index fetch and write
    nothing."""
    n = live.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    on = live > 0
    before = jax.lax.cummax(jnp.where(on, idx, -1))  # the nearest live row at or before r
    first = jnp.argmax(on).astype(jnp.int32)  # 0 if none
    held = jnp.where(before >= 0, before, first)
    scalars = jnp.concatenate([on.astype(jnp.int32), held, jnp.sum(on, dtype=jnp.int32)[None]])
    return scalars, lambda r, lyr, flags: (lyr[0], flags[n + r], 0, 0)


def step_pallas(u: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, stack: jnp.ndarray, layer: jnp.ndarray,
                live: Optional[jnp.ndarray] = None, interpret: bool = False,
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The one-token step on layer ``layer`` of the stacked state, in place:
    the state is aliased in to out and the kernel's blocks are that layer's
    alone, so a step reads and writes each live row's state of one layer
    once and nothing else of the stack."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, _, di = u.shape
    n = a.shape[0]
    f32 = jnp.float32
    delta = delta.astype(f32)
    du = delta * u.astype(f32)  # [B, 1, Di]
    col = lambda x: jnp.swapaxes(x.astype(f32), 1, 2)  # noqa: E731  [B, 1, N] -> [B, N, 1]
    flags, state_block = _state_block(
        jnp.ones((bsz,), jnp.int32) if live is None else live)
    own = lambda r, lyr, flags: (r, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, 1, di), own),
            pl.BlockSpec((1, 1, di), own),
            pl.BlockSpec((1, n, 1), own),
            pl.BlockSpec((1, n, 1), own),
            pl.BlockSpec((n, di), lambda r, lyr, flags: (0, 0)),
            pl.BlockSpec((1, 1, n, di), state_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, di), own),
            pl.BlockSpec((1, 1, n, di), state_block),
        ],
    )
    y, stack = pl.pallas_call(
        _step_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1, di), f32),
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        ],
        # operands count the scalar prefetches: layer, flags, delta, du, b, c, a, state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a row that is not live leans on the block before it
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), flags, delta, du, col(b), col(c),
      a.astype(f32), stack)
    return y, stack


# -- the chunked scan on the TPU ---------------------------------------------------------------

def _scan_kernel(layer_ref, delta_ref, du_ref, b_ref, c_ref, a_ref, s_ref, y_ref, s_out, *,
                 tokens: int):
    """Grid (row, block of channels): the block's state [N, lanes] is the
    token loop's carry (registers), ``UNROLL`` tokens a loop step so that
    delta, delta * u and y move as whole tiles of sublanes."""
    del layer_ref
    from jax.experimental import pallas as pl

    a = a_ref[...]

    def body(g, s):
        at = pl.ds(pl.multiple_of(g * UNROLL, UNROLL), UNROLL)
        delta, du = delta_ref[0, at, :], du_ref[0, at, :]  # [UNROLL, lanes]
        bs, cs = b_ref[0, at], c_ref[0, at]  # [UNROLL, N, 1]
        ys = []
        for i in range(UNROLL):
            s = jnp.exp(delta[i:i + 1] * a) * s + du[i:i + 1] * bs[i]
            ys.append(jnp.sum(s * cs[i], axis=0, keepdims=True))
        y_ref[0, at, :] = jnp.concatenate(ys, axis=0)
        return s

    s = jax.lax.fori_loop(0, tokens // UNROLL, body, s_ref[0, 0].astype(jnp.float32))
    s_out[0, 0] = s.astype(s_out.dtype)


def scan_pallas(u: jnp.ndarray, delta: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, stack: jnp.ndarray, layer: jnp.ndarray,
                interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``scan_chunked`` on layer ``layer`` of the stacked state, the state
    aliased in to out. T must be a multiple of ``UNROLL`` (a bucket is) and
    ``d_inner`` of ``LANE_BLOCK``. B and C come as columns [B, T, N, 1]: a
    token's column then lies down the sublanes, as the state's entries do."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, di = u.shape
    n = a.shape[0]
    lanes = min(LANE_BLOCK, di)
    if t % UNROLL or di % lanes:
        raise ValueError(f"the scan kernel takes T % {UNROLL} == 0 and d_inner % {lanes} == 0, "
                         f"got {t}, {di}")
    f32 = jnp.float32
    delta = delta.astype(f32)
    du = delta * u.astype(f32)
    by_token = lambda r, j, lyr: (r, 0, j)  # noqa: E731
    columns = lambda r, j, lyr: (r, 0, 0, 0)  # noqa: E731
    state = lambda r, j, lyr: (lyr[0], r, 0, j)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, di // lanes),
        in_specs=[
            pl.BlockSpec((1, t, lanes), by_token),
            pl.BlockSpec((1, t, lanes), by_token),
            pl.BlockSpec((1, t, n, 1), columns),
            pl.BlockSpec((1, t, n, 1), columns),
            pl.BlockSpec((n, lanes), lambda r, j, lyr: (0, j)),
            pl.BlockSpec((1, 1, n, lanes), state),
        ],
        out_specs=[
            pl.BlockSpec((1, t, lanes), by_token),
            pl.BlockSpec((1, 1, n, lanes), state),
        ],
    )
    # a column [N, 1] takes a whole tile of lanes in VMEM: 128 x 4 B an entry
    column_bytes = t * (-(-n // 8) * 8) * 128 * 4
    y, stack = pl.pallas_call(
        functools.partial(_scan_kernel, tokens=t),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, di), f32),
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        ],
        # operands count the scalar prefetch: layer, delta, du, b, c, a, state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # delta, du and y by token, B and C as columns, each double-buffered
            vmem_limit_bytes=int(2 * (3 * t * lanes * 4 + 2 * column_bytes) + (16 << 20))),
        interpret=interpret,
        name="ssm_scan",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), delta, du, b.astype(f32)[..., None],
      c.astype(f32)[..., None], a.astype(f32), stack)
    return y, stack
