"""The decode form of the flash forward (ops/flash.py), in interpret mode:
a call whose ``sq`` x ``groups`` query rows fit one q block runs on a grid
of (row, kv head), copies K and V in by blocks up to each row's length and
skips a row of length 0. K and V are a stacked cache in the order it is
stored, [L, B, Hkv, Skv, D]; the reference is ``_xla_attention`` on the
sliced layer, transposed here into the order fresh projections have.

Not slow-marked (tests/test_flash.py is): small head width, three layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import flash
from gofr_tpu.ops.attention import _xla_attention, attention

LAYERS, SKV, HKV, D = 3, 2048, 2, 64
LENGTHS = {
    1: [1450],
    6: [0, 129, 1450, 2048, 1, 128],
    12: [0, 1, 127, 128, 129, 1450, 2048, 255, 256, 257, 1024, 2047],
}
# (groups, rows of the batch): 4 is Mistral's grouping, 2 InternLM2's
SHAPES = [(4, 6), (2, 12), (1, 1)]


def _largest_decode_sq(groups: int) -> int:
    return flash.DEFAULT_BLOCK_Q // groups


@pytest.fixture(scope="module")
def stacks():
    made = {}
    for batch in LENGTHS:
        kk, kv = jax.random.split(jax.random.key(batch))
        shape = (LAYERS, batch, HKV, SKV, D)
        made[batch] = (jax.random.normal(kk, shape), jax.random.normal(kv, shape))
    return made


def _query(batch, sq, groups):
    return jax.random.normal(jax.random.key(17 * sq + groups), (batch, sq, HKV * groups, D))


def _reference(q, k, v, layer, offsets, lens):
    mask = jnp.arange(SKV)[None, :] < lens[:, None]
    return _xla_attention(
        q, jnp.swapaxes(k[layer], 1, 2), jnp.swapaxes(v[layer], 1, 2),
        True, offsets, mask, None)


def _spans(lens, sq):
    """A call's ``sq`` queries end each row: offsets and lengths as
    ``_run_cached`` hands them over (a row of length 0 stays empty)."""
    lens = jnp.asarray(lens, jnp.int32)
    return jnp.maximum(lens - sq, 0), lens


def _grids(fn, *args):
    """The grid of every Pallas call in ``fn``'s trace: (row, kv head) for
    the decode form, (row, q head, q block) for the other."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("which", ["one", "largest", "past"])
@pytest.mark.parametrize("groups,batch", SHAPES)
def test_decode_form_matches_the_reference(stacks, groups, batch, which, layer):
    sq = {"one": 1, "largest": _largest_decode_sq(groups),
          "past": _largest_decode_sq(groups) + 1}[which]
    k, v = stacks[batch]
    q = _query(batch, sq, groups)
    offsets, lens = _spans(LENGTHS[batch], sq)

    def call(q, k, v):
        return flash.flash_attention(
            q, k, v, causal=True, q_offset=offsets, kv_lens=lens, layer=jnp.int32(layer))

    # the first sq past one q block keeps the grid over q heads and q blocks
    q_blocks = -(-sq // flash.DEFAULT_BLOCK_Q)
    grid = (batch, HKV * groups, q_blocks) if which == "past" else (batch, HKV)
    assert _grids(call, q, k, v) == [grid]
    out = call(q, k, v)
    want = _reference(q, k, v, layer, offsets, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    empty = np.asarray(lens) == 0
    assert not np.asarray(out)[empty].any()  # no key: zeros, not NaN


@pytest.mark.parametrize("sq", [1, 5])
def test_a_row_that_is_not_live_returns_zeros_and_is_never_read(stacks, sq):
    """Length 0 is what ``_run_cached`` hands the kernel for a slot without
    a request. With every block wholly past a row's length, and every block
    of such a slot, filled with NaN, the live rows come out finite and
    equal to the reference: nothing past the last live block enters the
    arithmetic."""
    batch, groups, layer = 6, 4, 1
    k, v = stacks[batch]
    live = np.asarray([1, 0, 1, 1, 0, 1], bool)
    offsets, lens = _spans(np.where(live, [300, 2048, 129, 1450, 700, 128], 0), sq)
    q = _query(batch, sq, groups)
    want = _reference(q, k, v, layer, offsets, lens)

    block = flash.DEFAULT_BLOCK_KV
    first_dead = -(-np.asarray(lens) // block) * block  # [B]
    dead = np.arange(SKV)[None, :] >= first_dead[:, None]  # [B, Skv]
    poison = jnp.asarray(dead)[None, :, None, :, None]
    out = flash.flash_attention(
        q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v),
        causal=True, q_offset=offsets, kv_lens=lens, layer=jnp.int32(layer))
    out = np.asarray(out)
    assert np.isfinite(out).all()
    assert not out[~live].any()
    np.testing.assert_allclose(out[live], np.asarray(want)[live], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("cache_type", [jnp.float32, jnp.float8_e4m3fn], ids=["same", "f8"])
@pytest.mark.parametrize("sq", [1, 200], ids=["decode_form", "prefill_form"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_both_paths_read_their_layer_of_the_stack_as_it_is_stored(stacks, impl, sq, cache_type):
    """``attention(layer=...)`` on the whole cache: the XLA path slices its
    layer and contracts over the stored order, the kernel indexes the stack;
    a cache of another type than q (``MODEL_KV_DTYPE=f8``) is sliced and
    upcast a layer at a time on either path."""
    batch, groups, layer = 6, 4, 2
    k, v = (x.astype(cache_type) for x in stacks[batch])
    q = _query(batch, sq, groups)
    offsets, lens = _spans(LENGTHS[batch], sq)
    out = attention(q, k, v, causal=True, q_offset=offsets, kv_lens=lens,
                    impl=impl, layer=jnp.int32(layer))
    want = _reference(q, k.astype(q.dtype), v.astype(q.dtype), layer, offsets, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_cache_shorter_than_the_copies_in_flight():
    """Three blocks of keys, four buffers: no copy past the last block."""
    batch, groups, skv = 2, 2, 3 * flash.DEFAULT_BLOCK_KV
    kk, kv, kq = jax.random.split(jax.random.key(5), 3)
    k = jax.random.normal(kk, (batch, skv, HKV, D))
    v = jax.random.normal(kv, (batch, skv, HKV, D))
    q = jax.random.normal(kq, (batch, 1, HKV * groups, D))
    lens = jnp.asarray([skv, 130], jnp.int32)

    def call(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, q_offset=lens - 1, kv_lens=lens)

    assert _grids(call, q, k, v) == [(batch, HKV)]
    out = call(q, k, v)
    want = attention(q, k, v, causal=True, q_offset=lens - 1, kv_lens=lens, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_through_the_decode_form(causal):
    """A short training sequence takes the decode form too (the form hangs
    on shapes alone): its logsumexp feeds the fused backward."""
    batch, sq, groups = 2, 24, 2
    kq, kk, kv = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(kq, (batch, sq, HKV * groups, D))
    k = jax.random.normal(kk, (batch, sq, HKV, D))
    v = jax.random.normal(kv, (batch, sq, HKV, D))

    def loss(impl):
        return lambda q, k, v: jnp.sum(
            jnp.sin(attention(q, k, v, causal=causal, impl=impl)))

    assert _grids(loss("pallas"), q, k, v) == [(batch, HKV)]
    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=2e-4)
