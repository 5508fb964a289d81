"""ZAYA1's block (attention kind ``cca``, feed-forward kind ``moe``) on the
serving path: prefill and decode through the cache against the benchmark's
plain reference, slices and padding against the carried tail, the decode
pool's rows, the counters, and the settings this cache cannot serve. CPU,
tiny widths (hidden 64, 4 / 2 heads of 16, 4 experts of 32, 3 layers)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from gofr_tpu.config import EnvConfig
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.models import transformer as T
from gofr_tpu.models.llama import CONFIGS
from gofr_tpu.ops.rope import apply_rope
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import new_device

ARCH = spec.load_module("architectures", "cca_moe")
REF_CFG = {
    "_name": "tiny-cca-moe", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 1,
    "router_hidden_size": 16, "vocab_size": 256, "max_position_embeddings": 128,
    "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 10000.0}}, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "serving": {"quant": "", "dtype": "float32"},
}
SEED, PROMPT, STEPS = 11, 23, 16
# float32 on both sides, the sums in another order (the cache's tail and a
# grouped product against whole-sequence convolutions and products by
# index): measured 4e-6 on logits of size 3. A token whose two best experts
# lie closer than that would part the two sides at that layer by far more:
# none does at this seed, and the test would say so
TOLERANCE = 1e-4


def _model(**over):
    sz = ARCH.sizes_of(REF_CFG)
    cfg = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"], hidden_dim=sz["ffn"], max_seq=128,
        rope_theta=10000.0, rope_fraction=0.5, norm_eps=1e-5, dtype=jnp.float32,
        attn_impl="xla", attn_kind="cca", ffn_kind="moe", n_experts=sz["experts"],
        router_dim=sz["router"], tie_embeddings=True, **over)
    return cfg, ARCH.make_params(SEED, sz)


def _tokens():
    return np.asarray(jax.random.randint(jax.random.key(5), (1, PROMPT + STEPS), 3, 256))


def _reference_logits():
    toks = _tokens()
    cols = np.arange(PROMPT - 1, PROMPT + STEPS - 1)
    (logits,) = list(ARCH.logits_at(SEED, REF_CFG, [(toks, np.zeros_like(cols), cols)]))
    return np.asarray(logits)  # [STEPS, V]: after the prompt, then after each fed token


def _served_logits(cfg, params):
    """Prefill in a right-padded bucket of 32, then decode steps that feed the
    reference's tokens: the logits the program computes at the same positions."""
    toks = jnp.asarray(_tokens())
    padded = jnp.zeros((1, 32), jnp.int32).at[:, :PROMPT].set(toks[:, :PROMPT])
    logits, cache = T.prefill(params, padded, T.init_cache(cfg, 1), cfg, jnp.array([PROMPT]))
    out = [logits[0]]
    for i in range(STEPS - 1):
        logits, cache = T.decode_step(params, toks[:, PROMPT + i:PROMPT + i + 1], cache, cfg)
        out.append(logits[0])
    return np.asarray(jnp.stack(out))


# -- (a) the model against the plain reference ----------------------------------------

def test_prefill_then_decode_through_the_cache_gives_the_references_logits():
    got, want = _served_logits(*_model()), _reference_logits()
    assert np.abs(want).max() > 1.0
    assert np.max(np.abs(got - want)) < TOLERANCE


def test_the_whole_sequence_forward_gives_the_references_logits():
    cfg, params = _model()
    got = T.transformer_forward(params, jnp.asarray(_tokens()), cfg)[0, PROMPT - 1:-1]
    assert np.max(np.abs(np.asarray(got) - _reference_logits())) < TOLERANCE


@pytest.mark.parametrize("part", ["value_shift", "second_convolution", "qk_mean", "router_carry"])
def test_dropping_a_term_of_the_mathematics_fails_the_tolerance(part, monkeypatch):
    cfg, params = _model()
    want = _reference_logits()  # before any patch: the reference calls jax.numpy too
    layers = dict(params["layers"])
    if part == "value_shift":
        # the token before's half of v replaced by this token's
        real = T._shift
        monkeypatch.setattr(T, "_shift", lambda x, before: x if x.shape[-1] == 16 else real(x, before))
    elif part == "second_convolution":
        layers["cca_w1"] = layers["cca_w1"].at[:, 0].set(0.0)
    elif part == "qk_mean":
        monkeypatch.setattr(jnp, "repeat", lambda a, *args, **kw: jnp.zeros(
            np.repeat(np.empty(a.shape, bool), *args, **kw).shape, a.dtype))
    else:
        layers["router_gamma"] = jnp.zeros_like(layers["router_gamma"])
    got = _served_logits(cfg, {**params, "layers": layers})
    assert np.max(np.abs(got - want)) > 10 * TOLERANCE


def test_the_references_indexed_experts_are_its_masked_experts():
    """The form ``logits_at`` runs (each expert over its tokens by index)
    against the plainest one (every expert over every token, masked)."""
    sz = ARCH.sizes_of(REF_CFG)
    w = {k: v.astype(jnp.float32) for k, v in ARCH.layer_values(
        jnp.uint32(SEED), jnp.int32(1), sz).items()}
    m = jax.random.normal(jax.random.key(2), (37, sz["dim"]))
    e = jax.random.randint(jax.random.key(3), (37,), 0, 3)  # expert 3 gets none
    count = int(np.bincount(np.asarray(e)).max())
    np.testing.assert_allclose(ARCH.experts_indexed(m, e, w, count),
                               ARCH.experts_masked(m, e, w), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ARCH.experts_indexed(m, e, w, count + 9),
                               ARCH.experts_masked(m, e, w), rtol=1e-5, atol=1e-6)


# -- (b) slices, padding and the tail ---------------------------------------------------

def test_a_prompt_prefilled_in_two_slices_is_the_prompt_prefilled_in_one():
    cfg, params = _model()
    toks = jnp.asarray(_tokens())[:, :32]
    full_logits, full = T.prefill(params, toks, T.init_cache(cfg, 1), cfg, jnp.array([32]))
    cache = T.init_cache(cfg, 1)
    for at in (0, 16):
        logits, cache = T.prefill(params, toks[:, at:at + 16], cache, cfg, jnp.array([16]))
    np.testing.assert_allclose(logits, full_logits, rtol=1e-4, atol=1e-5)
    assert np.asarray(cache["tail"]).any()
    for name in ("k", "v", "tail"):
        np.testing.assert_allclose(cache[name], full[name], rtol=1e-4, atol=1e-5)
    assert int(cache["lengths"][0]) == 32


def test_a_second_slice_without_the_first_ones_tail_is_another_answer():
    cfg, params = _model()
    toks = jnp.asarray(_tokens())[:, :32]
    full_logits, _ = T.prefill(params, toks, T.init_cache(cfg, 1), cfg, jnp.array([32]))
    _, cache = T.prefill(params, toks[:, :16], T.init_cache(cfg, 1), cfg, jnp.array([16]))
    cache = {**cache, "tail": jnp.zeros_like(cache["tail"])}
    logits, _ = T.prefill(params, toks[:, 16:], cache, cfg, jnp.array([16]))
    assert np.max(np.abs(np.asarray(logits - full_logits))) > 10 * TOLERANCE


def test_a_buckets_padding_does_not_enter_the_tail_nor_any_expert():
    cfg, params = _model()
    toks = jnp.asarray(_tokens())
    padded = jnp.full((1, 32), 7, jnp.int32).at[:, :PROMPT].set(toks[:, :PROMPT])  # pads are real ids
    logits, cache, aux = T.prefill(params, padded, T.init_cache(cfg, 1), cfg,
                                   jnp.array([PROMPT]), with_aux=True)
    bare_logits, bare, bare_aux = T.prefill(params, toks[:, :PROMPT], T.init_cache(cfg, 1), cfg,
                                            jnp.array([PROMPT]), with_aux=True)
    np.testing.assert_allclose(logits, bare_logits, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cache["tail"], bare["tail"], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(aux["expert_counts"], bare_aux["expert_counts"])
    assert aux["expert_counts"].shape == (3, 4)
    assert np.asarray(aux["expert_counts"]).sum(axis=1).tolist() == [PROMPT] * 3
    # and the next token decodes from it as from the bare prompt's
    step = toks[:, PROMPT:PROMPT + 1]
    a, _ = T.decode_step(params, step, cache, cfg)
    b, _ = T.decode_step(params, step, bare, cfg)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_a_row_that_is_not_live_keeps_its_tail_and_goes_to_no_expert():
    cfg, params = _model()
    toks = jnp.asarray(_tokens())
    two = jnp.concatenate([toks[:, :16], toks[:, 16:32]])
    _, cache = T.prefill(params, two, T.init_cache(cfg, 2), cfg, jnp.array([16, 16]))
    cache = {**cache, "live": jnp.array([1, 0], jnp.int32)}
    _, after, aux = T.decode_step(params, two[:, :1], cache, cfg, with_aux=True)
    np.testing.assert_array_equal(after["tail"][:, 1], cache["tail"][:, 1])
    assert not np.array_equal(np.asarray(after["tail"][:, 0]), np.asarray(cache["tail"][:, 0]))
    assert np.asarray(aux["expert_counts"]).sum(axis=1).tolist() == [1, 1, 1]


def test_the_cache_names_its_leaves_and_the_tail_has_the_row_axis_second():
    cfg = CONFIGS["tiny-zaya"]
    cache = T.init_cache(cfg, 3)
    assert T.cache_leaves(cache) == ("k", "tail", "v")
    assert cfg.tail_dim == 2 * (4 + 2) * 16 + 16
    assert cache["tail"].shape == (3, 3, cfg.tail_dim) and cache["k"].shape == (3, 3, 2, 128, 16)
    big = CONFIGS["zaya1-8b"]
    assert (big.q_dim, big.tail_dim, big.rope_dim) == (1024, 2688, 64)
    f8 = dataclasses.replace(cfg, kv_dtype=jnp.float8_e4m3fn)
    cache = T.init_cache(f8, 1)
    assert cache["k"].dtype == jnp.float8_e4m3fn and cache["tail"].dtype == jnp.float32


# -- (c) the configuration's new fields ---------------------------------------------------

def test_partial_rotary_leaves_the_second_half_of_a_head_unrotated():
    cfg = CONFIGS["tiny-zaya"]
    freqs = jnp.asarray(T._cached_freqs(cfg.rope_dim, 64, cfg.rope_theta))
    assert freqs.shape == (64, 4, 2)  # 8 of a head's 16 dims turn, in 4 pairs
    x = jax.random.normal(jax.random.key(0), (1, 9, 4, 16))
    y = apply_rope(x, freqs, jnp.arange(9) + 5)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    whole = apply_rope(x[..., :8], freqs, jnp.arange(9) + 5)
    np.testing.assert_array_equal(y[..., :8], whole)
    assert not np.allclose(np.asarray(y[..., :8]), np.asarray(x[..., :8]))


@pytest.mark.parametrize("name", ["tiny", "tiny-retention", "small", "llama3-8b"])
def test_the_default_head_size_builds_the_dense_models_shapes_unchanged(name):
    cfg = CONFIGS[name]
    assert cfg.head_dim == cfg.dim // cfg.n_heads and cfg.q_dim == cfg.dim
    assert cfg.rope_dim == cfg.head_dim and cfg.ffn_kind == "dense" and not cfg.tie_embeddings
    assert dataclasses.replace(cfg, max_seq=64).head_dim == cfg.head_dim
    if cfg.dim > 64:
        return
    shapes = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
    kv = cfg.n_kv_heads * cfg.head_dim
    layers = {k: v.shape[1:] for k, v in shapes["layers"].items()}
    assert layers["wq"] == layers["wo"] == (cfg.dim, cfg.dim)
    assert layers["wk"] == layers["wv"] == (cfg.dim, kv)
    assert layers["w_gate"] == (cfg.dim, cfg.hidden_dim)
    assert shapes["lm_head"].shape == (cfg.dim, cfg.vocab_size)


def test_a_stated_head_size_shapes_the_projections_and_a_tied_tree_has_no_head():
    cfg = CONFIGS["tiny-zaya"]
    shapes = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
    layers = {k: v.shape[1:] for k, v in shapes["layers"].items()}
    assert "lm_head" not in shapes
    assert layers["wq"] == (64, 64) and layers["wo"] == (64, 64) and layers["wk"] == (64, 32)
    assert layers["w_gate"] == (4, 64, 32) and layers["w_down"] == (4, 32, 64)
    narrow = dataclasses.replace(cfg, n_heads=2)  # a latent half the hidden size
    shapes = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), narrow))
    assert shapes["layers"]["wq"].shape[1:] == (64, 32)
    assert shapes["layers"]["wo"].shape[1:] == (32, 64)


# -- (d) the normal serving path ------------------------------------------------------------

def _device(**env):
    defaults = {"MODEL_NAME": "tiny-zaya", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
                "MODEL_BUCKETS": "16,32", "DECODE_SLOTS": "3", "DECODE_CHUNK": "4"}
    defaults.update(env)
    old = {k: os.environ.get(k) for k in defaults}
    os.environ.update(defaults)
    try:
        return new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


@pytest.fixture(scope="module")
def device():
    dev = _device()
    yield dev
    dev.close()


def _prompts():
    rng = np.random.default_rng(4)
    return [rng.integers(3, 256, n).tolist() for n in (9, 50, 21)]


def _greedy_by_the_model(prompt, n):
    """What the whole-sequence forward, which has no cache, tail or pool,
    puts first after the prompt and after each of its own tokens."""
    cfg = CONFIGS["tiny-zaya"]
    params = T.init_transformer(jax.random.key(0), cfg)
    seq = list(prompt)
    for _ in range(n):
        logits = T.transformer_forward(params, jnp.asarray([seq]), cfg)
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def test_the_served_tokens_are_the_whole_sequence_forwards(device):
    """Length 9 takes a batched prefill, 50 a chunked one (above the top
    bucket of 32: two slices, the second from the first's tail)."""
    short, long_, _ = _prompts()
    assert device.generate(short, max_new_tokens=6) == _greedy_by_the_model(short, 6)
    assert device.generate(long_, max_new_tokens=6) == _greedy_by_the_model(long_, 6)


def test_two_requests_decoded_together_give_what_each_gives_alone(device):
    import concurrent.futures as cf

    short, long_, _ = _prompts()
    alone = [device.generate(p, max_new_tokens=12) for p in (short, long_)]
    with cf.ThreadPoolExecutor(2) as pool:
        together = list(pool.map(lambda p: device.generate(p, max_new_tokens=12), (short, long_)))
    assert together == alone


def test_a_slot_reused_after_a_longer_request_carries_nothing_over(device):
    short, long_, other = _prompts()
    fresh = device.generate(other, max_new_tokens=10)
    for _ in range(3):  # run every slot through the long request
        device.generate(long_, max_new_tokens=20)
        device.generate(short, max_new_tokens=3)
    assert device.generate(other, max_new_tokens=10) == fresh


def test_a_row_moved_between_pool_slots_keeps_its_tail(device):
    pool = device.decode_pool
    assert pool is not None and not pool._active
    assert T.cache_leaves(pool.cache) == ("k", "tail", "v")
    before = jax.tree.map(np.asarray, pool.cache)
    row = {name: jax.random.normal(jax.random.key(i), (1,) + leaf.shape[1:]).astype(leaf.dtype)
           if leaf.ndim == 1 else
           jax.random.normal(jax.random.key(i), leaf.shape[:1] + (1,) + leaf.shape[2:]).astype(leaf.dtype)
           for i, (name, leaf) in enumerate(sorted(pool.cache.items()))}
    pool.cache = pool._write_slot(pool.cache, row, 1)
    moved = pool._read_slot(pool.cache, 1)
    pool.cache = pool._write_slot(pool.cache, moved, 2)
    back = pool._read_slot(pool.cache, 2)
    assert set(back) == set(row) == set(before)
    for name in row:
        np.testing.assert_array_equal(np.asarray(back[name]), np.asarray(row[name]))
        other = np.asarray(pool.cache[name])
        keep = other[0] if other.ndim == 1 else other[:, 0]
        was = before[name][0] if other.ndim == 1 else before[name][:, 0]
        np.testing.assert_array_equal(keep, was)  # slot 0 untouched
    pool.cache = jax.tree.map(jnp.asarray, before)


def _finished_records(device):
    """The timeline once the chunk still in flight behind a finished
    request has been fetched."""
    import time

    for _ in range(200):
        records = device.timeline.records(limit=1000)
        if all(r["status"] != "running" for r in records):
            return records
        time.sleep(0.05)
    raise AssertionError("a dispatch stayed running")


def test_dispatch_records_count_what_routing_did(device):
    """A request of 9 + 9: one prefill (9 real tokens of a bucket of 16, a
    row of the batch unused) and pooled chunks with one live row of three."""
    device.timeline._ring.clear()
    short, _, _ = _prompts()
    device.generate(short, max_new_tokens=9)
    records = _finished_records(device)
    layers, experts = 3, 4
    (prefill,) = [r for r in records if r["kind"] == "prefill"]
    assert prefill["expert_tokens"] == 9 * layers
    assert layers <= prefill["experts_read"] <= layers * experts
    assert prefill["expert_tokens"] / experts <= prefill["expert_tokens_max"] <= 9 * layers
    chunks = [r for r in records if r["kind"] == "decode_chunk" and r["batch_size"]]
    assert chunks
    for r in chunks:  # one live row: each layer-step routes one token to one expert
        steps = r["expert_tokens"] // layers
        assert 1 <= steps <= 4 and r["expert_tokens"] == steps * layers
        assert r["experts_read"] == r["expert_tokens_max"] == r["expert_tokens"]
    dense = _device(MODEL_NAME="tiny")
    try:
        dense.generate(short, max_new_tokens=5)
        assert all(r["expert_tokens"] is None and r["experts_read"] is None
                   for r in dense.timeline.records(limit=100))
    finally:
        dense.close()


def test_a_chunked_prefills_slices_each_count_their_own_tokens(device):
    device.timeline._ring.clear()
    _, long_, _ = _prompts()
    device.generate(long_, max_new_tokens=2)
    slices = sorted((r for r in _finished_records(device) if r["kind"] == "prefill_chunk"),
                    key=lambda r: r["dispatch_id"])
    assert [r["tokens"] for r in slices] == [32, 18]
    assert [r["expert_tokens"] for r in slices] == [32 * 3, 18 * 3]
    assert [r["carried"] for r in slices] == [False, True]


# -- (e) what this cache cannot serve is refused at boot, by name ------------------------------

@pytest.mark.parametrize("setting,value", [
    ("PREFIX_CACHE", "4"), ("KV_BLOCKS", "64"), ("KV_HBM_BUDGET_MB", "8"),
    ("DRAFT_MODEL_NAME", "tiny"), ("SPEC_POOLED", "on"), ("KV_TRANSFER", "on"),
    ("KV_TRANSFER_TRUST_HINT", "on"), ("FLEET_ROLE", "prefill"), ("TPU_MESH", "tp=2"),
])
def test_a_setting_that_rests_on_kv_rows_alone_is_refused_for_a_cache_with_a_tail(setting, value):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*tail per row"):
        _device(**{setting: value})


def test_the_quantiser_is_refused_for_expert_stacked_leaves():
    with pytest.raises(ValueError, match="MODEL_QUANT is not supported .*expert-stacked"):
        _device(MODEL_QUANT="int8")


def test_float8_is_a_type_for_its_k_and_v_and_not_for_the_tail():
    dev = _device(MODEL_KV_DTYPE="f8")
    try:
        cache = dev.decode_pool.cache
        assert cache["k"].dtype == cache["v"].dtype == jnp.float8_e4m3fn
        assert cache["tail"].dtype == jnp.float32
        assert len(dev.generate(_prompts()[0], max_new_tokens=4)) == 4
    finally:
        dev.close()
