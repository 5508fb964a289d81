"""The routed expert product: every token through the SwiGLU expert (one, or
its top-k) it was sent to, reading only the experts that got a token.

``routed_experts(x, expert, w_gate, w_up, w_down, layer)``: ``x`` [T, D]
tokens, ``expert`` [T] int32 the expert of each (``n_experts`` for a token
that goes to none: bucket padding, the row of a slot without a request; or
``expert`` [T, k] with ``weight`` [T, k], k (token, expert) pairs a token and
their weighted sum on the way back: ``_routed_pairs``, whose rows a pass
follow the share of the gate's ``gate_outputs`` that is held here),
the weights as the model holds them, stacked over layers
([L, E, D, F], [L, E, D, F], [L, E, F, D]) with ``layer`` an int32 scalar,
or one layer's ([E, ...], ``layer`` None). Returns ``y`` [T, D] (zeros for
a token of no expert) and ``counts`` [E], the tokens each expert got.

Shapes are static in T; the group sizes are data:

- dispatch: tokens sorted by expert (a stable argsort; tokens of no expert
  sort last and belong to no group), and the schedule of (row tile, expert)
  visits made from the group sizes;
- experts: two Pallas calls on the TPU, ``moe_experts_gated``
  (silu(x Wg) * (x Wu)) and ``moe_experts_down``. The grid is (column
  tiles, visits); a visit multiplies one row tile by one expert's column
  tile and keeps the rows that are that expert's. An expert with no token
  is never visited, so its weights are never read from HBM; the visits
  past the last real one repeat its block indices, which moves nothing.
  The kernels index [layer, expert] of the stacks themselves: a layer's
  slice handed in by a scan would be copied whole first. Off the TPU (and
  under ``impl="xla"``) ``jax.lax.ragged_dot`` over the same sorted tokens;
- combine: the sorted result back in token order, zeros where no expert.

The all-expert form this is tested against is ``models/moe.py::
_moe_mlp_dense``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

ROW_TILE = 128  # rows of sorted tokens a visit multiplies (fewer when T is)
COL_TILE = 512  # columns of an expert's weight a visit reads: [K, 512] bf16 = 2 MB


def _row_tile(tokens: int) -> int:
    return ROW_TILE if tokens >= ROW_TILE else -(-tokens // 16) * 16


def visit_schedule(counts: jnp.ndarray, tiles: int, tile: int) -> tuple:
    """The (row tile, expert) pairs a grouped product has to visit, experts
    in order, for sorted tokens with ``counts`` [E] a group over ``tiles``
    row tiles of ``tile`` rows -> (offsets [E + 1], expert [V], row tile [V],
    visits [1]) with V = tiles + E - 1, the most there can be. Entries past
    ``visits`` repeat the last real one."""
    n = counts.shape[0]
    ends = jnp.cumsum(counts)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32)
    first = offsets[:-1] // tile
    last = jnp.maximum(ends - 1, 0) // tile
    per_expert = jnp.where(counts > 0, last - first + 1, 0)
    visit_ends = jnp.cumsum(per_expert)
    total = visit_ends[-1]
    at = jnp.minimum(jnp.arange(tiles + n - 1), jnp.maximum(total - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(visit_ends, at, side="right"), n - 1)
    row_tile = first[expert] + at - (visit_ends - per_expert)[expert]
    return (offsets, expert.astype(jnp.int32),
            jnp.clip(row_tile, 0, tiles - 1).astype(jnp.int32),
            jnp.reshape(total, (1,)).astype(jnp.int32))


def _visit_kernel(layer_ref, offsets_ref, expert_ref, tile_ref, visits_ref,
                  x_ref, *refs, tile: int, gated: bool):
    from jax.experimental import pallas as pl

    del layer_ref
    out_ref = refs[-1]
    i = pl.program_id(1)

    @pl.when(i < visits_ref[0])
    def _():
        e, t = expert_ref[i], tile_ref[i]
        x = x_ref[...]
        y = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
        if gated:
            y = jax.nn.silu(y) * jnp.dot(x, refs[1][...], preferred_element_type=jnp.float32)
        row = t * tile + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        # a row tile's visits follow one another: the first of them starts
        # the tile's output from zeros, the others add their expert's rows
        fresh = jnp.logical_or(i == 0, tile_ref[jnp.maximum(i - 1, 0)] != t)
        kept = jnp.where(fresh, 0.0, out_ref[...].astype(jnp.float32))
        out_ref[...] = jnp.where(mine, y, kept).astype(out_ref.dtype)


def _grouped(x: jnp.ndarray, weights: tuple, layer: jnp.ndarray, schedule: tuple,
             tile: int, interpret: bool) -> jnp.ndarray:
    """One Pallas call over the visit schedule: ``x`` [M, K] sorted tokens
    (M a multiple of ``tile``), ``weights`` one stack [L, E, K, N] (the
    product) or two (the gated pair) -> [M, N]. Rows of no expert are not
    written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = weights[0].shape[-1]
    cols = COL_TILE if n % COL_TILE == 0 else n
    offsets, expert, row_tile, visits = schedule
    rows_of = lambda j, i, lyr, off, ex, rt, nv: (rt[i], 0)  # noqa: E731
    weight_of = lambda j, i, lyr, off, ex, rt, nv: (lyr[0], ex[i], 0, j)  # noqa: E731
    out_of = lambda j, i, lyr, off, ex, rt, nv: (rt[i], j)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // cols, expert.shape[0]),
        in_specs=[pl.BlockSpec((tile, k), rows_of)]
        + [pl.BlockSpec((None, None, k, cols), weight_of) for _ in weights],
        out_specs=pl.BlockSpec((tile, cols), out_of),
    )
    block_bytes = k * cols * weights[0].dtype.itemsize
    return pl.pallas_call(
        functools.partial(_visit_kernel, tile=tile, gated=len(weights) == 2),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # in order: a tile's later visits build on its earlier ones
            dimension_semantics=("arbitrary", "arbitrary"),
            # each weight block double-buffered, and room for the rest
            vmem_limit_bytes=int(2 * len(weights) * block_bytes + (24 << 20))),
        interpret=interpret,
        name="moe_experts_gated" if len(weights) == 2 else "moe_experts_down",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, expert, row_tile, visits,
      x, *weights)


def _use_pallas(impl: str, x: jnp.ndarray, w: jnp.ndarray) -> bool:
    if impl == "auto":
        return (jax.default_backend() == "tpu" and x.shape[-1] % 128 == 0
                and w.shape[-1] % 128 == 0)
    return impl == "pallas"


def _sorted_product(xs: jnp.ndarray, counts: jnp.ndarray, w_gate: jnp.ndarray,
                    w_up: jnp.ndarray, w_down: jnp.ndarray, layer: jnp.ndarray,
                    impl: str) -> jnp.ndarray:
    """``xs`` [M, D] rows sorted by expert, ``counts`` [E] rows a group (the
    rows past the last group belong to none and come back as whatever the
    kernel's buffer held) -> each row through its group's expert, [M, D]."""
    t = xs.shape[0]
    if _use_pallas(impl, xs, w_gate):
        tile = _row_tile(t)
        padded = -(-t // tile) * tile
        xs = jnp.pad(xs, ((0, padded - t), (0, 0)))
        schedule = visit_schedule(counts, padded // tile, tile)
        interpret = jax.default_backend() != "tpu"
        hidden = _grouped(xs, (w_gate, w_up), layer, schedule, tile, interpret)
        return _grouped(hidden, (w_down,), layer, schedule, tile, interpret)[:t]
    wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
                  for w in (w_gate, w_up, w_down))
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=counts,
                            preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(dot(xs, wg)) * dot(xs, wu)).astype(xs.dtype)
    return dot(hidden, wd).astype(xs.dtype)


def pair_capacity(tokens: int, k: int = 1, held: int = 0, outputs: int = 1) -> int:
    """Rows of sorted pairs one pass of the pair form multiplies. It follows
    the share of the gate's ``outputs`` that are experts ``held`` here
    (``tokens * k * held / outputs`` pairs are expected to land): twice the
    expectation, and never under every token's worth for a step's few
    rows, half of it for a prefill's many. A chip of a wide deployment
    holds a small share, so the pairs that land are a fraction of a pair a
    token and one pass of that floor takes them; a chip that holds every
    expert gets all ``tokens * k`` pairs, and one pass takes those."""
    floor = tokens if tokens <= ROW_TILE else tokens // 2
    return min(tokens * k, max(floor, 2 * tokens * k * held // outputs))


def _routed_pairs(x, expert, weight, w_gate, w_up, w_down, layer, impl, gate_outputs=0):
    """The top-k form: ``expert`` [T, k] (``n`` for a pair that goes to no
    expert HERE: an identity expert, another chip's, a pad's) and ``weight``
    [T, k] -> (sum over a token's pairs of weight x expert(x), float32
    [T, D]; counts [E] of PAIRS). The cost follows the pairs that landed on
    an expert held here, not the T x k drawn: the pairs are sorted (ints
    alone), and only those with an expert are gathered, ``pair_capacity``
    rows a pass, as many passes as they need (one, but for a skewed batch
    on a chip that holds a small share of the experts)."""
    t, k = expert.shape
    n = w_gate.shape[1]
    f32 = jnp.float32
    cap = pair_capacity(t, k, n if gate_outputs else 0, gate_outputs or 1)
    with jax.named_scope("moe.dispatch"):
        pairs = expert.reshape(-1)
        counts = jnp.sum(pairs[:, None] == jnp.arange(n)[None, :], axis=0).astype(jnp.int32)
        passes = -(-t * k // cap)
        order = jnp.pad(jnp.argsort(pairs, stable=True).astype(jnp.int32),
                        (0, passes * cap - t * k))
        ends = jnp.cumsum(counts)
        landed = ends[-1]
        flat_weight = weight.reshape(-1).astype(f32)

    def one_pass(i, y):
        lo = i * cap
        with jax.named_scope("moe.dispatch"):
            idx = jax.lax.dynamic_slice_in_dim(order, lo, cap)
            token = idx // k
            xs = x[token]
            here = jnp.clip(ends, lo, lo + cap) - jnp.clip(ends - counts, lo, lo + cap)
        with jax.named_scope("moe.experts"):
            ys = _sorted_product(xs, here, w_gate, w_up, w_down, layer, impl)
        with jax.named_scope("moe.combine"):
            real = lo + jnp.arange(cap) < landed
            scaled = jnp.where(real[:, None], ys.astype(f32) * flat_weight[idx][:, None], 0.0)
            # back to the tokens, a token's pairs summed: a 0/1 product
            to_token = (token[None, :] == jnp.arange(t)[:, None]) & real[None, :]
            return y + jnp.einsum("tc,cd->td", to_token.astype(x.dtype), scaled.astype(x.dtype),
                                  preferred_element_type=f32)

    y = jax.lax.fori_loop(0, -(-landed // cap), one_pass, jnp.zeros(x.shape, f32))
    return y, counts


def routed_experts(
    x: jnp.ndarray, expert: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
    w_down: jnp.ndarray, layer: Optional[jnp.ndarray] = None, impl: str = "auto",
    weight: Optional[jnp.ndarray] = None, gate_outputs: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = jnp.int32(0)
    if expert.ndim == 2:
        return _routed_pairs(x, expert, weight, w_gate, w_up, w_down, layer, impl, gate_outputs)
    t, n = x.shape[0], w_gate.shape[1]
    with jax.named_scope("moe.dispatch"):
        counts = jnp.sum(expert[:, None] == jnp.arange(n)[None, :], axis=0).astype(jnp.int32)
        order = jnp.argsort(expert, stable=True)
        xs = x[order]
    with jax.named_scope("moe.experts"):
        ys = _sorted_product(xs, counts, w_gate, w_up, w_down, layer, impl)
    with jax.named_scope("moe.combine"):
        # rows of no expert hold whatever the kernel's buffer held
        back = jnp.zeros((t,), jnp.int32).at[order].set(jnp.arange(t, dtype=jnp.int32))
        y = jnp.where((expert < n)[:, None], ys[back], jnp.zeros((), ys.dtype))
    return y, counts
