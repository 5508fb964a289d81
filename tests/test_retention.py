"""Power retention (attention kind ``retention``): the three forms of the
operation against each other, the model's prefill and decode through the
state against the benchmark's plain reference, bucket slices and padding,
the decode pool's rows, and the settings a state cannot serve. CPU, tiny
sizes (head size 16: phi has 144 entries)."""

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
from gofr_tpu.ops import retention as R
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import new_device

B, H, HKV, D = 2, 4, 2, 16


def _qkvg(t, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, t, H, D))
    k = jax.random.normal(ks[1], (B, t, HKV, D))
    v = jax.random.normal(ks[2], (B, t, HKV, D))
    log_g = jax.nn.log_sigmoid(2.0 + 2.0 * jax.random.normal(ks[3], (B, t, HKV)))
    return q, k, v, log_g


# -- (a) the operation ------------------------------------------------------------

def test_phi_of_q_dot_phi_of_k_is_the_squared_scaled_product():
    q, k, _, _ = _qkvg(9)
    q = q[:, :, :HKV]
    assert R.phi(q).shape[-1] == R.phi_dim(D) == (D // 2 + 1) * D
    got = jnp.sum(R.phi(q) * R.phi(k), axis=-1)
    want = jnp.square(jnp.sum(q * k, axis=-1) / D ** 0.5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 32), (37, 8), (37, 5), (37, 128), (1, 8)])
def test_chunked_form_is_the_attention_form(t, chunk):
    """Chunk sizes that do and do not divide the length."""
    q, k, v, log_g = _qkvg(t)
    want = R.retention_attention(q, k, v, log_g)
    got, _, _ = R.retention_chunk(q, k, v, log_g, *R.init_state(B, HKV, D), sub_chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_recurrent_form_is_the_attention_form_and_leaves_the_chunked_forms_state():
    t = 37
    q, k, v, log_g = _qkvg(t)
    s, z = R.init_state(B, HKV, D)
    ys = []
    for i in range(t):
        y, s, z = R.retention_step(q[:, i:i + 1], k[:, i:i + 1], v[:, i:i + 1],
                                   log_g[:, i:i + 1], s, z)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), R.retention_attention(q, k, v, log_g),
                               rtol=1e-4, atol=2e-5)
    _, s_c, z_c = R.retention_chunk(q, k, v, log_g, *R.init_state(B, HKV, D), sub_chunk=8)
    np.testing.assert_allclose(s, s_c, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(z, z_c, rtol=1e-4, atol=1e-5)


def test_a_token_that_is_not_valid_leaves_state_and_earlier_outputs_alone():
    t, real = 24, 13
    q, k, v, log_g = _qkvg(t)
    valid = jnp.arange(t)[None, :] < jnp.array([real, t])[:, None]
    y, s, z = R.retention_chunk(q, k, v, log_g, *R.init_state(B, HKV, D), valid, sub_chunk=8)
    y1, s1, z1 = R.retention_chunk(q[:1, :real], k[:1, :real], v[:1, :real], log_g[:1, :real],
                                   *R.init_state(1, HKV, D), sub_chunk=8)
    np.testing.assert_allclose(y[0, :real], y1[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s[0], s1[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(z[0], z1[0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_pallas_step_updates_its_layer_of_the_stack_and_nothing_else(dtype):
    """The TPU's kernel in interpret mode against the XLA step."""
    q, k, v, log_g = _qkvg(1, seed=3)
    s, z = (x.astype(dtype) for x in jax.tree.map(
        lambda x: jax.random.normal(jax.random.key(7), x.shape), R.init_state(B, HKV, D)))
    s_stack, z_stack = R.init_state(B, HKV, D, dtype, layers=3)
    s_stack, z_stack = s_stack.at[1].set(s), z_stack.at[1].set(z)
    y, s_new, z_new = R.retention_step(q, k, v, log_g, s, z)
    y_k, s_k, z_k = jax.jit(
        lambda *a: R.retention_step_pallas(*a, interpret=True)
    )(q, k, v, log_g, s_stack, z_stack, jnp.int32(1))
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(y_k, y, **tol)
    np.testing.assert_allclose(s_k[1].astype(jnp.float32), s_new.astype(jnp.float32), **tol)
    np.testing.assert_allclose(z_k[1].astype(jnp.float32), z_new.astype(jnp.float32), **tol)
    assert not np.asarray(s_k[0]).any() and not np.asarray(s_k[2]).any()
    assert not np.asarray(z_k[0]).any() and not np.asarray(z_k[2]).any()


def test_pallas_chunk_carries_its_layer_of_the_stack_from_a_state(monkeypatch):
    """The TPU's chunked-form kernel in interpret mode against the XLA form:
    three sub-chunks from a carried state, one row right-padded."""
    t = 48
    q, k, v, log_g = _qkvg(t, seed=5)
    _, s, z = R.retention_chunk(*_qkvg(t, seed=6), *R.init_state(B, HKV, D), sub_chunk=16)
    valid = jnp.arange(t)[None, :] < jnp.array([29, t])[:, None]
    y, s_new, z_new = R.retention_chunk(q, k, v, log_g, s, z, valid, sub_chunk=16)
    s_stack, z_stack = R.init_state(B, HKV, D, layers=3)
    s_stack, z_stack = s_stack.at[2].set(s), z_stack.at[2].set(z)
    y_k, s_k, z_k = jax.jit(lambda *a: R.retention_chunk_pallas(
        *a, sub_chunk=16, interpret=True))(q, k, v, log_g, s_stack, z_stack, jnp.int32(2), valid)
    real = np.asarray(valid)[:, :, None, None]
    np.testing.assert_allclose(np.where(real, y_k, 0), np.where(real, y, 0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_k[2], s_new, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z_k[2], z_new, rtol=1e-5, atol=1e-5)
    assert not np.asarray(s_k[:2]).any() and not np.asarray(z_k[:2]).any()


# -- (b) the model against the plain reference ----------------------------------------

ARCH = spec.load_module("architectures", "power_retention")
REF_CFG = {
    "_name": "tiny-power-retention", "hidden_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 160, "vocab_size": 256, "max_position_embeddings": 128,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "serving": {"quant": "", "dtype": "float32"},
}
SEED, PROMPT, STEPS = 11, 23, 16
# float32 on both sides, the sums in another order (chunked and recurrent
# against the reference's attention form, blocked): measured 3e-5 on
# logits of size 3; a bfloat16 state reads 3e-3 and more
TOLERANCE = 3e-4


def _model(**over):
    sz = ARCH.sizes_of(REF_CFG)
    cfg = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], hidden_dim=sz["ffn"], max_seq=128, rope_theta=10000.0,
        norm_eps=1e-6, dtype=jnp.float32, attn_impl="xla", attn_kind="retention", **over)
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


def test_prefill_then_decode_through_the_state_gives_the_references_logits():
    got = _served_logits(*_model())
    assert np.max(np.abs(got - _reference_logits())) < TOLERANCE


def test_a_bfloat16_state_fails_the_tolerance():
    cfg, params = _model(kv_dtype=jnp.bfloat16)
    assert T.init_cache(cfg, 1)["s"].dtype == jnp.bfloat16
    assert np.max(np.abs(_served_logits(cfg, params) - _reference_logits())) > TOLERANCE


def test_dropping_the_gate_fails_the_tolerance(monkeypatch):
    real = R.retention_cached
    monkeypatch.setattr(R, "retention_cached", lambda q, k, v, log_g, *a, **kw: real(
        q, k, v, jnp.zeros_like(log_g), *a, **kw))
    assert np.max(np.abs(_served_logits(*_model()) - _reference_logits())) > TOLERANCE


def test_dropping_the_normaliser_fails_the_tolerance(monkeypatch):
    monkeypatch.setattr(R, "_normalise", lambda num, den: num)
    assert np.max(np.abs(_served_logits(*_model()) - _reference_logits())) > TOLERANCE


# -- (c) slices and padding --------------------------------------------------------------

def test_bucket_slices_give_the_state_and_logits_of_one_full_width_call():
    cfg, params = _model()
    toks = jnp.asarray(_tokens())[:, :32]
    full_logits, full = T.prefill(params, toks, T.init_cache(cfg, 1), cfg, jnp.array([32]))
    cache = T.init_cache(cfg, 1)
    for at in (0, 16):
        logits, cache = T.prefill(params, toks[:, at:at + 16], cache, cfg, jnp.array([16]))
    np.testing.assert_allclose(logits, full_logits, rtol=1e-4, atol=1e-4)
    for name in ("s", "z"):
        np.testing.assert_allclose(cache[name], full[name], rtol=1e-4, atol=1e-5)
    assert int(cache["lengths"][0]) == 32


def test_a_right_padded_bucket_gives_the_state_of_the_unpadded_prompt():
    cfg, params = _model()
    toks = jnp.asarray(_tokens())
    padded = jnp.full((1, 32), 7, jnp.int32).at[:, :PROMPT].set(toks[:, :PROMPT])  # pads are real ids
    logits, cache = T.prefill(params, padded, T.init_cache(cfg, 1), cfg, jnp.array([PROMPT]))
    bare_logits, bare = T.prefill(params, toks[:, :PROMPT], T.init_cache(cfg, 1), cfg,
                                  jnp.array([PROMPT]))
    np.testing.assert_allclose(logits, bare_logits, rtol=1e-4, atol=1e-4)
    for name in ("s", "z"):
        np.testing.assert_allclose(cache[name], bare[name], rtol=1e-4, atol=1e-5)


def test_the_cache_of_each_kind_names_its_leaves():
    assert T.cache_leaves(T.init_cache(CONFIGS["tiny"], 2)) == ("k", "v")
    cache = T.init_cache(CONFIGS["tiny-retention"], 2)
    assert T.cache_leaves(cache) == ("s", "z")
    assert cache["s"].shape == (2, 2, 2, 16, 144) and cache["s"].dtype == jnp.float32
    assert cache["z"].shape == (2, 2, 2, 9, 16)


# -- (d) the decode pool -------------------------------------------------------------------

def _device(**env):
    defaults = {"MODEL_NAME": "tiny-retention", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
                "MODEL_BUCKETS": "16,32", "DECODE_SLOTS": "2", "DECODE_CHUNK": "4"}
    defaults.update(env)
    old = {k: os.environ.get(k) for k in defaults}
    os.environ.update(defaults)
    try:
        return new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


@pytest.fixture(scope="module", params=["tiny", "tiny-retention"])
def device(request):
    dev = _device(MODEL_NAME=request.param)
    yield dev
    dev.close()


def _prompts():
    rng = np.random.default_rng(4)
    return [rng.integers(3, 256, n).tolist() for n in (9, 50, 21)]


def test_two_requests_decoded_together_give_what_each_gives_alone(device):
    """Lengths 9 and 50: one batched prefill, one chunked (above the top
    bucket), then one pooled chunk after another over both."""
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


def test_write_slot_and_read_slot_round_trip_every_leaf(device):
    pool = device.decode_pool
    assert pool is not None and not pool._active
    before = jax.tree.map(np.asarray, pool.cache)
    row = {name: jax.random.normal(jax.random.key(i), (1,) + leaf.shape[1:]).astype(leaf.dtype)
           if leaf.ndim == 1 else
           jax.random.normal(jax.random.key(i), leaf.shape[:1] + (1,) + leaf.shape[2:]).astype(leaf.dtype)
           for i, (name, leaf) in enumerate(sorted(pool.cache.items()))}
    pool.cache = pool._write_slot(pool.cache, row, 1)
    back = pool._read_slot(pool.cache, 1)
    assert set(back) == set(row) == set(before)
    for name in row:
        np.testing.assert_array_equal(np.asarray(back[name]), np.asarray(row[name]))
        other = np.asarray(pool.cache[name])
        keep = other[0] if other.ndim == 1 else other[:, 0]
        was = before[name][0] if other.ndim == 1 else before[name][:, 0]
        np.testing.assert_array_equal(keep, was)  # slot 0 untouched
    pool.cache = jax.tree.map(jnp.asarray, before)


# -- (e) what a state cannot serve is refused at boot, by name --------------------------------

@pytest.mark.parametrize("setting,value", [
    ("PREFIX_CACHE", "4"), ("KV_BLOCKS", "64"), ("KV_HBM_BUDGET_MB", "8"),
    ("DRAFT_MODEL_NAME", "tiny"), ("SPEC_POOLED", "on"), ("MODEL_KV_DTYPE", "f8"),
    ("KV_TRANSFER", "on"), ("KV_TRANSFER_TRUST_HINT", "on"), ("FLEET_ROLE", "prefill"),
    ("TPU_MESH", "tp=2"),
])
def test_a_setting_that_rests_on_kv_rows_is_refused_for_a_retention_model(setting, value):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*retention state"):
        _device(**{setting: value})


def test_the_same_settings_still_boot_a_dense_model():
    dev = _device(MODEL_NAME="tiny", PREFIX_CACHE="2", MODEL_KV_DTYPE="bf16")
    try:
        assert T.cache_leaves(dev.decode_pool.cache) == ("k", "v")
        assert dev.decode_pool.cache["k"].dtype == jnp.float32  # bf16 = "the model's type"
    finally:
        dev.close()


def test_bf16_is_a_stated_type_for_a_state_and_float32_the_default():
    assert dataclasses.replace(CONFIGS["tiny-retention"]).cache_dtype == jnp.float32
    dev = _device(MODEL_KV_DTYPE="bf16")
    try:
        assert dev.decode_pool.cache["s"].dtype == jnp.bfloat16
    finally:
        dev.close()


@pytest.mark.parametrize("live", [(1, 0, 1, 0), (0, 0, 1, 1), (0, 1, 0, 0), (0, 0, 0, 0), (1, 1, 1, 1)],
                         ids=lambda v: "".join(map(str, v)))
@pytest.mark.parametrize("form", ["pallas", "xla"])
def test_a_row_that_is_not_live_keeps_its_state_and_the_live_rows_step(live, form):
    """The decode pool's slots that hold no request: every pattern of which
    rows lean on which neighbour's block."""
    b = len(live)
    ks = jax.random.split(jax.random.key(9), 6)
    q = jax.random.normal(ks[0], (b, 1, H, D))
    k, v = (jax.random.normal(kk, (b, 1, HKV, D)) for kk in ks[1:3])
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, 1, HKV)))
    s_stack, z_stack = (jax.random.normal(kk, x.shape) for kk, x in
                        zip(ks[4:], R.init_state(b, HKV, D, layers=2)))
    flags = jnp.asarray(live, jnp.int32)
    y, s_new, z_new = jax.jit(lambda *a: R.retention_cached(
        *a, impl=form, live=flags))(q, k, v, log_g, s_stack, z_stack, jnp.int32(1))
    y_all, s_all, z_all = R.retention_step(q, k, v, log_g, s_stack[1], z_stack[1])
    on = np.asarray(live, bool)
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(y_all)[on], rtol=1e-5, atol=1e-5)
    for got, stepped, was in ((s_new, s_all, s_stack), (z_new, z_all, z_stack)):
        np.testing.assert_allclose(np.asarray(got[1])[on], np.asarray(stepped)[on], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[1])[~on], np.asarray(was[1])[~on])
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(was[0]))
