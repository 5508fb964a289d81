"""The cached forward carries its KV cache: one buffer that the step loop
and the layer loop hand on, written only where a token lands, and read a
layer at a time out of the stack (ISSUE 26), in the order the attention
kernel reads, [L, B, Hkv, S, D] (ISSUE 35).

Structure (jaxpr and compiled CPU HLO), the write, and every caller of
``_run_cached`` against ``transformer_forward`` on the same tokens."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.llama import CONFIGS, TINY
from gofr_tpu.models.lora import add_lora, build_lora_stack
from gofr_tpu.models.transformer import (
    _write_kv,
    decode_chunk,
    decode_chunk_pool,
    decode_chunk_pool_lora,
    decode_chunk_pool_penalized,
    init_cache,
    init_transformer,
    prefill,
    transformer_forward,
    unpack_expert_counts,
    verify_chunk,
)

CFG = dataclasses.replace(TINY, max_seq=64)  # f32, XLA attention
SLOTS, STEPS, WIDTH = 4, 4, 16
LENS = (5, 0, 9, 0)  # ragged rows, idle slots between them
# what a pool's cache says of such slots: not live, and a length left over
# from the request before (the pool never resets one)
LIVE, STALE = (1, 0, 1, 0), (0, 40, 0, 57)


@pytest.fixture(scope="module")
def params():
    return init_transformer(jax.random.key(0), CFG)


def _pool_args(slots=SLOTS):
    return (
        jax.random.key(3), jnp.zeros((slots,), jnp.float32),  # greedy
        jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), jnp.float32),
        jnp.zeros((slots,), jnp.float32),
    )


# -- structure ----------------------------------------------------------------

def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


def _programs(params):
    cache = init_cache(CFG, SLOTS)
    tok = jnp.zeros((SLOTS, 1), jnp.int32)
    return {
        "decode_chunk_pool": (
            lambda p, t, c, *a: decode_chunk_pool(p, t, c, CFG, STEPS, *a),
            (params, tok, cache, *_pool_args()),
        ),
        "prefill": (
            lambda p, t, c, l: prefill(p, t, c, CFG, l),
            (params, jnp.zeros((SLOTS, WIDTH), jnp.int32), cache,
             jnp.asarray(LENS, jnp.int32)),
        ),
    }


@pytest.mark.parametrize("program", ["decode_chunk_pool", "prefill"])
def test_every_scan_carries_the_cache(params, program):
    fn, args = _programs(params)[program]
    shape = init_cache(CFG, SLOTS)["k"].shape
    carried = 0
    for eqn in _scans(jax.make_jaxpr(fn)(*args).jaxpr):
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = eqn.invars[n_consts + n_carry:]
        ys = eqn.outvars[n_carry:]
        assert not [v for v in (*xs, *ys) if v.aval.shape == shape], (
            f"{program}: a scan takes or returns the cache as xs/ys"
        )
        carry = eqn.invars[n_consts:n_consts + n_carry]
        carried += sum(v.aval.shape == shape for v in carry)
    # k and v, in the layer loop (and the step loop of the chunk)
    assert carried >= (4 if program == "decode_chunk_pool" else 2)


def test_pooled_chunk_compiles_without_a_copy_of_the_cache(params):
    fn, args = _programs(params)["decode_chunk_pool"]
    hlo = jax.jit(fn, donate_argnums=(2, 3)).lower(*args).compile().as_text()
    dims = ",".join(str(n) for n in init_cache(CFG, SLOTS)["k"].shape)
    assert f"f32[{dims}]" in hlo  # the stack is there to be copied
    copies = re.findall(rf"= f32\[{dims}\]\S* copy\(", hlo)
    assert not copies, f"{len(copies)} whole-cache copies in the pooled chunk"


# -- the write ----------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 8])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_write_touches_only_where_the_tokens_land(layer, s):
    """[layer, row, :, start:start + s, :] and nothing else of the stack;
    a start past ``max_seq - s`` (a full row) clamps to it."""
    n_layers, b, max_seq, h, d = 3, 4, 32, 2, 8
    stack = jax.random.normal(jax.random.key(1), (n_layers, b, h, max_seq, d))
    new = jax.random.normal(jax.random.key(2), (b, s, h, d))
    starts = jnp.asarray([0, 7, max_seq - s, max_seq], jnp.int32)
    got = np.asarray(jax.jit(_write_kv)(stack, new, jnp.int32(layer), starts))
    want = np.asarray(stack).copy()
    for row, start in enumerate(np.minimum(np.asarray(starts), max_seq - s)):
        want[layer, row, :, start:start + s] = np.asarray(new)[row].transpose(1, 0, 2)
    np.testing.assert_array_equal(got, want)


# -- every caller against the plain forward -----------------------------------

def _prompts(cfg, lens, width=WIDTH):
    toks = jax.random.randint(jax.random.key(7), (len(lens), width), 1, cfg.vocab_size)
    mask = jnp.arange(width)[None, :] < jnp.asarray(lens)[:, None]
    return jnp.where(mask, toks, 0)


def _teacher(params, cfg, prompts, lens, first, toks):
    """Log-softmax of the plain forward over prompt + first + toks, at the
    positions that predict ``toks``: [B, steps, V]. Causal attention makes
    the zero tail of a shorter row harmless."""
    b, steps = toks.shape
    seq = np.zeros((b, WIDTH + 1 + steps), np.int32)
    for row, n in enumerate(lens):
        seq[row, :n] = np.asarray(prompts)[row, :n]
        seq[row, n] = int(first[row])
        seq[row, n + 1:n + 1 + steps] = np.asarray(toks)[row]
    logits = jax.jit(lambda p, t: transformer_forward(p, t, cfg))(params, jnp.asarray(seq))
    lps = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    return np.stack([lps[row, n:n + steps] for row, n in enumerate(lens)])


def _check_rows(want, toks, lps, rows, atol, exact=True):
    toks, lps = np.asarray(toks), np.asarray(lps)
    for row in rows:
        if exact:
            assert toks[row].tolist() == want[row].argmax(-1).tolist()
        chosen = want[row][np.arange(toks.shape[1]), toks[row]]
        np.testing.assert_allclose(lps[row], chosen, atol=atol, rtol=0)


def _prefilled(params, cfg, prompts, lens):
    cache = init_cache(cfg, len(lens))
    logits, cache = jax.jit(lambda p, t, c, l: prefill(p, t, c, cfg, l))(
        params, prompts, cache, jnp.asarray(lens, jnp.int32)
    )
    return jnp.argmax(logits, -1).astype(jnp.int32), cache


def _as_in_a_pool(cache):
    """``live`` for the active slots only, stale lengths on the idle ones."""
    live = jnp.asarray(LIVE, jnp.int32)
    lengths = jnp.where(live > 0, cache["lengths"], jnp.asarray(STALE, jnp.int32))
    return {**cache, "live": live, "lengths": lengths}


def _pool_case(params, cfg=CFG, atol=2e-4, exact=True):
    prompts = _prompts(cfg, LENS)
    first, cache = _prefilled(params, cfg, prompts, LENS)
    toks, lps, *_, cache = jax.jit(
        lambda p, t, c, *a: decode_chunk_pool(p, t, c, cfg, STEPS, *a)
    )(params, first[:, None], _as_in_a_pool(cache), *_pool_args())
    want = _teacher(params, cfg, prompts, LENS, first, toks)
    _check_rows(want, toks, lps, [0, 2], atol, exact)
    # the chunk hands the mask on as it came, and every row's length moves
    assert np.asarray(cache["live"]).tolist() == list(LIVE)
    assert np.asarray(cache["lengths"]).tolist() == [
        (n if on else old) + STEPS for n, on, old in zip(LENS, LIVE, STALE)]


def _penalized_case(params):
    prompts = _prompts(CFG, LENS)
    first, cache = _prefilled(params, CFG, prompts, LENS)
    key, temp, top_k, top_p, min_p = _pool_args()
    zeros_v = jnp.zeros((SLOTS, CFG.vocab_size), jnp.float32)
    zeros, ones = jnp.zeros((SLOTS,), jnp.float32), jnp.ones((SLOTS,), jnp.float32)
    toks, lps, *_ = jax.jit(
        lambda p, t, c, *a: decode_chunk_pool_penalized(p, t, c, CFG, STEPS, *a)
    )(params, first[:, None], cache, key, temp, top_k, top_p, min_p,
      zeros_v.astype(bool), ones, zeros_v, zeros, zeros, zeros_v)
    want = _teacher(params, CFG, prompts, LENS, first, toks)
    _check_rows(want, toks, lps, [0, 2], 2e-4)


def _lora_case(params):
    wrapped = add_lora(params, jax.random.key(11), rank=4)
    for name in ("wq", "w_up"):  # a fresh adapter is the identity: move it
        leaf = wrapped["layers"][name]
        leaf["lora_b"] = 0.05 * jax.random.normal(
            jax.random.key(12), leaf["lora_b"].shape, leaf["lora_b"].dtype
        )
    prompts = _prompts(CFG, LENS)
    first_a, cache_a = _prefilled(wrapped, CFG, prompts, LENS)
    first_b, cache_b = _prefilled(params, CFG, prompts, LENS)
    # row 0 rides the adapter, row 2 the base
    ids = jnp.asarray([1, 0, 0, 0], jnp.int32)
    on = ids.astype(bool)
    first = jnp.where(on, first_a, first_b)
    cache = {
        "k": jnp.where(on[None, :, None, None, None], cache_a["k"], cache_b["k"]),
        "v": jnp.where(on[None, :, None, None, None], cache_a["v"], cache_b["v"]),
        "lengths": cache_b["lengths"],
    }
    stacked = build_lora_stack(params, {"a": wrapped})
    toks, lps, *_ = jax.jit(
        lambda s, i, t, c, *a: decode_chunk_pool_lora(s, i, t, c, CFG, STEPS, *a)
    )(stacked, ids, first[:, None], cache, *_pool_args())
    _check_rows(_teacher(wrapped, CFG, prompts, LENS, first, toks), toks, lps, [0], 5e-4)
    _check_rows(_teacher(params, CFG, prompts, LENS, first, toks), toks, lps, [2], 5e-4)


def _solo_case(params):
    lens = (11,)
    prompts = _prompts(CFG, lens)
    first, cache = _prefilled(params, CFG, prompts, lens)
    toks, _, lps, *_ = jax.jit(
        lambda p, t, c, k: decode_chunk(p, t, c, CFG, STEPS, k, with_logprobs=True)
    )(params, first[:, None], cache, jax.random.key(3))
    _check_rows(_teacher(params, CFG, prompts, lens, first, toks), toks, lps, [0], 2e-4)


def _verify_case(params):
    lens = (6, 10)
    prompts = _prompts(CFG, lens)
    first, cache = _prefilled(params, CFG, prompts, lens)
    drafts = jax.random.randint(jax.random.key(13), (2, STEPS - 1), 1, CFG.vocab_size)
    fed = jnp.concatenate([first[:, None], drafts], axis=1)  # pending + drafts
    next_ids, cache = jax.jit(lambda p, t, c: verify_chunk(p, t, c, CFG))(params, fed, cache)
    assert np.asarray(cache["lengths"]).tolist() == [n + STEPS for n in lens]
    # position i's argmax follows fed[:, :i+1]: teacher-force the drafts
    want = _teacher(params, CFG, prompts, lens, first, jnp.pad(drafts, ((0, 0), (0, 1))))
    assert np.asarray(next_ids).tolist() == want.argmax(-1).tolist()


def _slices_case(params):
    lens = (13, 10)
    prompts = _prompts(CFG, lens)
    step = jax.jit(lambda p, t, c, l: prefill(p, t, c, CFG, l))  # one executable, two slices
    cache = init_cache(CFG, 2)
    _, cache = step(params, prompts[:, :8], cache, jnp.asarray([8, 8], jnp.int32))
    logits, cache = step(params, prompts[:, 8:], cache, jnp.asarray([5, 2], jnp.int32))
    assert np.asarray(cache["lengths"]).tolist() == list(lens)
    full = jax.jit(lambda p, t: transformer_forward(p, t, CFG))(params, prompts)
    want = np.stack([np.asarray(full)[row, n - 1] for row, n in enumerate(lens)])
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-4, atol=2e-4)


CASES = {
    "pool_ragged_idle": _pool_case,
    "penalized": _penalized_case,
    "lora": _lora_case,
    "solo_chunk": _solo_case,
    "verify_chunk": _verify_case,
    "prompt_in_slices": _slices_case,
    # float8 KV: the layer is upcast as it is read, within float8's error
    # of the forward (tests/test_ops.py's tolerance); tokens may differ
    "fp8_kv": lambda p: _pool_case(
        p, dataclasses.replace(CFG, kv_dtype=jnp.float8_e4m3fn), atol=0.2, exact=False
    ),
    # the Pallas kernel (interpret mode here) reads its layer from the stack
    "pallas_stacked_read": lambda p: _pool_case(
        p, dataclasses.replace(CFG, attn_impl="pallas")
    ),
}


@pytest.mark.parametrize("case", list(CASES))
def test_cached_paths_match_the_plain_forward(params, case):
    CASES[case](params)


KINDS = {
    "dense_gqa": CFG,
    # ZAYA1's block at test size: K and V beside a tail per row, routed experts
    "cca": dataclasses.replace(CONFIGS["tiny-zaya"], max_seq=64),
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_ragged_rows_a_dead_row_and_a_full_row_beside_each_other(kind, impl):
    """A pool's cache as a chunk meets it: rows 0 and 2 hold prompts of 5
    and 9 tokens, slot 1 holds no request (not live, a stale length, NaN
    where its K and V were) and slot 3 is full (live, ``max_seq`` long,
    another request's K and V). Rows 0 and 2 decode what the plain forward,
    which has no cache, gives them; the full row's writes clamp to its last
    position and touch nothing before it."""
    cfg = dataclasses.replace(KINDS[kind], attn_impl=impl)
    params = init_transformer(jax.random.key(0), cfg)
    prompts = _prompts(cfg, LENS)
    first, cache = _prefilled(params, cfg, prompts, LENS)
    row = jnp.arange(SLOTS)[None, :, None, None, None]
    for i, name in enumerate(("k", "v")):
        junk = jax.random.normal(jax.random.key(20 + i), cache[name].shape, cache[name].dtype)
        cache[name] = jnp.where(row == 1, jnp.nan, jnp.where(row == 3, junk, cache[name]))
    cache["live"] = jnp.asarray([1, 0, 1, 1], jnp.int32)
    cache["lengths"] = jnp.asarray([LENS[0], 40, LENS[2], cfg.max_seq], jnp.int32)
    before = jax.tree.map(np.asarray, cache)
    toks, lps, *_, after = jax.jit(
        lambda p, t, c, *a: decode_chunk_pool(p, t, c, cfg, STEPS, *a)
    )(params, first[:, None], cache, *_pool_args())
    toks, _ = unpack_expert_counts(np.asarray(toks), SLOTS, cfg.n_experts)
    want = _teacher(params, cfg, prompts, LENS, first, toks)
    _check_rows(want, toks, lps, [0, 2], 2e-4)
    last = cfg.max_seq - 1
    for name in ("k", "v"):
        got = np.asarray(after[name])
        np.testing.assert_array_equal(got[:, 3, :, :last], before[name][:, 3, :, :last])
        assert not np.array_equal(got[:, 3, :, last], before[name][:, 3, :, last])
        for r, n in ((0, LENS[0]), (2, LENS[2])):  # a live row: its new tokens alone
            np.testing.assert_array_equal(got[:, r, :, :n], before[name][:, r, :, :n])
            np.testing.assert_array_equal(
                got[:, r, :, n + STEPS:], before[name][:, r, :, n + STEPS:])
    assert np.asarray(after["lengths"]).tolist() == [
        LENS[0] + STEPS, 40 + STEPS, LENS[2] + STEPS, cfg.max_seq + STEPS]
    if "tail" in before:  # the slot without a request keeps its tail
        np.testing.assert_array_equal(np.asarray(after["tail"])[:, 1], before["tail"][:, 1])


def test_every_row_of_a_prefill_and_of_a_solo_cache_is_live(params):
    """Only a pool marks rows off: the cache ``init_cache`` makes, what a
    prefill returns and what a solo chunk returns say every row is live."""
    lens = (11, 7)
    assert np.asarray(init_cache(CFG, len(lens))["live"]).tolist() == [1, 1]
    first, cache = _prefilled(params, CFG, _prompts(CFG, lens), lens)
    assert np.asarray(cache["live"]).tolist() == [1, 1]
    _, cache = jax.jit(lambda p, t, c, k: decode_chunk(p, t, c, CFG, STEPS, k))(
        params, first[:, None], cache, jax.random.key(3))
    assert np.asarray(cache["live"]).tolist() == [1, 1]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_row_that_is_not_live_attends_nothing(params, impl):
    """Poison the idle slots' K and V: a step still gives the live rows the
    tokens it gives them over a clean cache, on either attention path."""
    cfg = dataclasses.replace(CFG, attn_impl=impl)
    first, cache = _prefilled(params, cfg, _prompts(cfg, LENS), LENS)
    cache = _as_in_a_pool(cache)
    idle = (jnp.asarray(LIVE) == 0)[None, :, None, None, None]
    poisoned = {**cache, "k": jnp.where(idle, jnp.nan, cache["k"]),
                "v": jnp.where(idle, jnp.nan, cache["v"])}
    run = jax.jit(lambda p, t, c, *a: decode_chunk_pool(p, t, c, cfg, 1, *a))
    clean = run(params, first[:, None], cache, *_pool_args())
    dirty = run(params, first[:, None], poisoned, *_pool_args())
    for row in (0, 2):
        assert np.asarray(dirty[0])[row].tolist() == np.asarray(clean[0])[row].tolist()
        np.testing.assert_allclose(np.asarray(dirty[1])[row], np.asarray(clean[1])[row], atol=1e-6)
