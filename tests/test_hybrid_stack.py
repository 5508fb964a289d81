"""Layers of two kinds in one stack (``tiny-jamba``: state-space mixers with
a softmax layer mid-stack, no rotary) on the serving path: prefill and decode
through the cache, chunked prefill and the decode pool's row moves against
the benchmark's plain reference (logits, not tokens), each term of the
mathematics, the per-kind stacks and cache, the settings this cache cannot
serve, and that a model of one kind keeps its stack, cache and programs.
CPU, tiny widths (hidden 64, 4 heads on 1 kv head, 128 channels x 16, feed-forward 96)."""

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
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import new_device

ARCH = spec.load_module("architectures", "hybrid_ssm")
# two periods of (ssm, ssm, softmax, ssm): the layer loop's outer scan runs,
# and the attention layer sits between state-space layers
REF_CFG = {
    "_name": "tiny-hybrid", "hidden_size": 64, "num_hidden_layers": 8, "attn_layer_period": 4,
    "attn_layer_offset": 2, "num_experts": 1, "num_experts_per_tok": 1,
    "num_attention_heads": 4, "num_key_value_heads": 1, "intermediate_size": 96,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 4, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "vocab_size": 256,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    "serving": {"quant": "", "dtype": "float32"},
}
SEED, PROMPT, STEPS = 11, 23, 16
# float32 on both sides, the sums in another order (a carried state and tail
# and a chunked scan against one pass over the whole sequence token by
# token): measured 6e-6 on logits of size 3. bfloat16 anywhere reads 1e-2
# and a dropped term 0.05 to 3 (the tests below)
TOLERANCE = 1e-4


def _model(**over):
    sz = ARCH.sizes_of(REF_CFG)
    cfg = T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], hidden_dim=sz["ffn"], max_seq=128, rope_fraction=0.0,
        norm_eps=1e-6, dtype=jnp.float32, attn_impl="xla", layer_kinds=ARCH.kinds_of(REF_CFG),
        ssm_state=16, ssm_conv=4, ssm_dt_rank=4, tie_embeddings=True, **over)
    return cfg, ARCH.make_params(SEED, sz)


def _tokens():
    return np.asarray(jax.random.randint(jax.random.key(5), (1, PROMPT + STEPS), 3, 256))


def _reference_logits(mode=None):
    toks = _tokens()
    cols = np.arange(PROMPT - 1, PROMPT + STEPS - 1)
    (logits,) = list(ARCH.logits_at(SEED, REF_CFG, [(toks, np.zeros_like(cols), cols)], mode))
    return np.asarray(logits)  # [STEPS, V]: after the prompt, then after each fed token


def _programs(cfg):
    """Prefill and one decode step, each compiled once for this call (a
    fresh trace: a test that patches a term traces the patched term)."""
    return (jax.jit(lambda p, t, c, n: T.prefill(p, t, c, cfg, n)),
            jax.jit(lambda p, t, c: T.decode_step(p, t, c, cfg)))


def _decode_on(step_fn, params, logits, cache, first=0, steps=STEPS - 1, held=lambda c: c):
    """``held``: what becomes of the cache between two calls (nothing)."""
    toks, out = jnp.asarray(_tokens()), [logits[0]]
    for i in range(first, steps):
        logits, cache = step_fn(params, toks[:, PROMPT + i:PROMPT + i + 1], held(cache))
        out.append(logits[0])
    return out


def _served_logits(cfg, params, **kw):
    """Prefill in a right-padded bucket of 32, then decode steps that feed
    the reference's tokens: the logits the program computes at the same
    positions."""
    prefill, step = _programs(cfg)
    toks = jnp.asarray(_tokens())
    padded = jnp.zeros((1, 32), jnp.int32).at[:, :PROMPT].set(toks[:, :PROMPT])
    logits, cache = prefill(params, padded, T.init_cache(cfg, 1), jnp.array([PROMPT]))
    return np.asarray(jnp.stack(_decode_on(step, params, logits, cache, **kw)))


# -- (a) the model against the plain reference ----------------------------------------

def test_prefill_then_decode_through_the_cache_gives_the_references_logits():
    got, want = _served_logits(*_model()), _reference_logits()
    assert np.abs(want).max() > 1.0
    assert np.max(np.abs(got - want)) < TOLERANCE


def test_the_whole_sequence_forward_gives_the_references_logits():
    cfg, params = _model()
    got = T.transformer_forward(params, jnp.asarray(_tokens()), cfg)[0, PROMPT - 1:-1]
    assert np.max(np.abs(np.asarray(got) - _reference_logits())) < TOLERANCE


def test_a_prompt_prefilled_in_slices_from_a_carried_cache_gives_the_references_logits():
    """Three slices of 8 in a bucket of 8 (the last one 7 real tokens and a
    pad): each carries on from the state, the tail and the K/V rows of the
    one before."""
    cfg, params = _model()
    prefill, step = _programs(cfg)
    toks = jnp.asarray(_tokens())
    cache = T.init_cache(cfg, 1)
    for lo in (0, 8, 16):
        n = min(8, PROMPT - lo)
        piece = jnp.zeros((1, 8), jnp.int32).at[:, :n].set(toks[:, lo:lo + n])
        logits, cache = prefill(params, piece, cache, jnp.array([n]))
    assert int(cache["lengths"][0]) == PROMPT
    got = np.asarray(jnp.stack(_decode_on(step, params, logits, cache)))
    assert np.max(np.abs(got - _reference_logits())) < TOLERANCE


def test_a_second_slice_without_the_first_ones_state_is_another_answer():
    cfg, params = _model()
    prefill, _ = _programs(cfg)
    toks = jnp.asarray(_tokens())
    _, cache = prefill(params, toks[:, :16], T.init_cache(cfg, 1), jnp.array([16]))
    want, _ = prefill(params, toks[:, 16:24], cache, jnp.array([8]))
    for leaf in ("ssm", "conv", "k"):
        lost = {**cache, leaf: jnp.zeros_like(cache[leaf])}
        got, _ = prefill(params, toks[:, 16:24], lost, jnp.array([8]))
        assert np.max(np.abs(np.asarray(got - want))) > 100 * TOLERANCE, leaf


def test_decode_through_the_pools_row_moves_gives_the_references_logits():
    """A prefilled row written into slot 2 of a four-slot pool cache (as
    ``decode_pool.write_slot`` writes it: every leaf at its row axis), the
    other slots not live; pooled steps; the row read back out and decoded
    alone."""
    cfg, params = _model()
    prefill, step = _programs(cfg)
    toks = jnp.asarray(_tokens())
    logits, row = prefill(params, toks[:, :PROMPT], T.init_cache(cfg, 1), jnp.array([PROMPT]))
    pool = T.init_cache(cfg, 4)
    noise = {name: jax.random.normal(jax.random.key(9), leaf.shape).astype(leaf.dtype)
             for name, leaf in pool.items() if leaf.ndim > 1}
    pool = {**pool, **noise}  # what earlier requests left in every slot
    write = lambda pool, row, i: {  # noqa: E731
        name: jax.lax.dynamic_update_slice_in_dim(leaf, row[name], i, axis=0 if leaf.ndim == 1 else 1)
        for name, leaf in pool.items()}
    pool = {**write(pool, row, 2), "live": jnp.asarray([0, 0, 1, 0], jnp.int32)}
    out = [logits[0]]
    for i in range(7):
        feed = jnp.zeros((4, 1), jnp.int32).at[2].set(toks[0, PROMPT + i])
        logits, pool = step(params, feed, pool)
        out.append(logits[2])
    for name in ("ssm", "conv"):  # a slot that is not live kept its state and its tail
        np.testing.assert_array_equal(np.asarray(pool[name])[:, 0], np.asarray(noise[name])[:, 0])
    back = {name: leaf[2:3] if leaf.ndim == 1 else leaf[:, 2:3] for name, leaf in pool.items()}
    back["live"] = jnp.ones((1,), jnp.int32)
    steps = _decode_on(step, params, out[-1][None], back, first=7)[1:]
    got = np.asarray(jnp.stack(out + steps))
    assert np.max(np.abs(got - _reference_logits())) < TOLERANCE


@pytest.mark.parametrize("part", ["dt_norm", "b_norm", "c_norm", "skip_d", "conv_bias", "gate_z",
                                  "rotary", "conv_tail"])
def test_dropping_a_term_of_the_mathematics_fails_the_tolerance(part, monkeypatch):
    cfg, params = _model()
    want = _reference_logits()
    ssm = dict(params["layers"]["ssm"])
    if part in ("dt_norm", "b_norm", "c_norm"):
        # an inner norm left out. A mixer norms dt (a width only it has), then
        # B, then C (one width: B's is the odd call of that width, C's the even)
        real, seen = T.rms_norm, {"n": 0}

        def skipping(x, w, eps):
            if part == "dt_norm":
                return x if x.shape[-1] == cfg.ssm_dt_rank else real(x, w, eps)
            if x.shape[-1] != cfg.ssm_state:
                return real(x, w, eps)
            seen["n"] += 1
            return x if seen["n"] % 2 == (part == "b_norm") else real(x, w, eps)

        monkeypatch.setattr(T, "rms_norm", skipping)
    elif part == "skip_d":
        ssm["ssm_d"] = jnp.zeros_like(ssm["ssm_d"])
    elif part == "conv_bias":
        ssm["ssm_conv_b"] = jnp.zeros_like(ssm["ssm_conv_b"])
    elif part == "gate_z":
        # the gate replaced by 1. A mixer calls silu on [.., d_inner] twice:
        # first on the convolution's output, then on z (the feed-forward's
        # is another width)
        real, seen = jax.nn.silu, {"n": 0}

        def ungated(x):
            if x.shape[-1] != cfg.d_inner:
                return real(x)
            seen["n"] += 1
            return jnp.ones_like(x) if seen["n"] % 2 == 0 else real(x)

        monkeypatch.setattr(T.jax.nn, "silu", ungated)
    elif part == "rotary":
        cfg = dataclasses.replace(cfg, rope_fraction=1.0, rope_theta=10000.0)  # wrongly applied
    else:
        # the tail forgotten between calls: every call starts its convolution from zeros
        real = jax.lax.dynamic_index_in_dim
        def forgetful(x, i, axis=0, keepdims=True):
            got = real(x, i, axis, keepdims)
            return jnp.zeros_like(got) if x.ndim == 3 and x.shape[-1] == 3 * cfg.d_inner else got

        monkeypatch.setattr(T.jax.lax, "dynamic_index_in_dim", forgetful)
    got = _served_logits(cfg, {**params, "layers": {**params["layers"], "ssm": ssm}})
    assert np.max(np.abs(got - want)) > 100 * TOLERANCE


@pytest.mark.parametrize("mode,fails", [("bf16", True), (None, False)])
def test_a_lower_precision_fails_the_tolerance(mode, fails):
    """The reference with every matmul weight rounded to bfloat16 is 1e-2
    from the float32 one: a tolerance that a lower precision passes would
    let part of the mathematics go."""
    gap = np.max(np.abs(_served_logits(*_model()) - _reference_logits(mode)))
    assert (gap > 10 * TOLERANCE) == fails


def test_a_state_rounded_to_bfloat16_between_steps_fails_the_tolerance():
    """The state is float32 between steps and no field of the config says
    otherwise; held in the published cache's type between two calls, it is
    10 x the tolerance from the reference."""
    cfg, params = _model()
    assert T.init_cache(cfg, 1)["ssm"].dtype == jnp.float32
    rounded = lambda c: dict(c, ssm=c["ssm"].astype(jnp.bfloat16).astype(jnp.float32))  # noqa: E731
    got = _served_logits(cfg, params, held=rounded)
    assert np.max(np.abs(got - _reference_logits())) > 10 * TOLERANCE


# -- (b) parameters and cache stacked per kind ------------------------------------------------

def test_parameters_and_cache_are_stacked_per_kind_and_the_loop_scans_the_period():
    cfg, params = _model()
    assert cfg.mixed and cfg.kinds_present == ("ssm", "softmax")
    assert cfg.layer_period == (4, (("ssm", 0, 2), ("softmax", 0, 1), ("ssm", 2, 1)))
    assert set(params["layers"]) == {"ssm", "softmax"}
    assert params["layers"]["ssm"]["ssm_in"].shape == (6, 64, 256)
    assert params["layers"]["softmax"]["wq"].shape == (2, 64, 64)
    assert "wq" not in params["layers"]["ssm"] and "lm_head" not in params
    own = T.init_transformer(jax.random.key(0), cfg)  # the program's own init: the same tree
    assert jax.tree.map(lambda x: (x.shape, x.dtype), own) == jax.tree.map(
        lambda x: (x.shape, x.dtype), params)
    # the family's initialisation, not noise: A = -(1..16), steps in [0.001, 0.1]
    np.testing.assert_allclose(np.exp(own["layers"]["ssm"]["ssm_a_log"][0, :, 0]), np.arange(1, 17),
                               rtol=1e-6)
    steps = np.asarray(jax.nn.softplus(own["layers"]["ssm"]["ssm_dt_b"]))
    assert 0.001 * 0.999 <= steps.min() and steps.max() <= 0.1 * 1.001
    cache = T.init_cache(cfg, 3)
    assert T.cache_leaves(cache) == ("conv", "k", "ssm", "v")
    assert cache["k"].shape == cache["v"].shape == (2, 3, 1, 128, 16)
    assert cache["ssm"].shape == (6, 3, 16, 128) and cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (6, 3, 3 * 128)
    assert T.state_row_bytes(cache) == 6 * (16 * 128 * 4 + 3 * 128 * 4)
    assert T.STATE_LEAVES == ("s", "z", "conv", "ssm")


def test_the_compiled_loop_has_one_body_a_run_of_the_period_not_a_block_a_layer():
    cfg, params = _model()
    cache = T.init_cache(cfg, 1)
    hlo = jax.jit(lambda p, t, c: T.decode_step(p, t, c, cfg)).lower(
        params, jnp.zeros((1, 1), jnp.int32), cache).as_text()
    # the period's three runs: a scanned run of 2, the attention layer and a
    # run of 1 inline, inside the scan over the two periods
    assert hlo.count("stablehlo.while") == 2
    assert 0 < hlo.count("stablehlo.exponential") < 4 * 3  # not once a layer (6 ssm layers)


@pytest.mark.parametrize("bad", [
    dict(layer_kinds=("ssm", "softmax")), dict(layer_kinds=("ssm", "linear") * 4),
    dict(ffn_kind="moe"),
])
def test_a_pattern_the_program_cannot_run_is_refused_where_it_is_written(bad):
    cfg, _ = _model()
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, **bad)


@pytest.mark.parametrize("name", ["tiny", "tiny-retention", "tiny-zaya", "llama3-8b"])
def test_a_model_of_one_kind_keeps_its_one_stack_its_cache_and_its_program(name):
    cfg = CONFIGS[name]
    assert not cfg.mixed and cfg.kinds == (cfg.attn_kind,) * cfg.n_layers
    shapes = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
    assert "wq" in shapes["layers"] and shapes["layers"]["wq"].shape[0] == cfg.n_layers
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, min(cfg.max_seq, 256)))
    want = {"softmax": ("k", "v"), "retention": ("s", "z"), "cca": ("k", "tail", "v")}
    assert T.cache_leaves(cache) == want[cfg.attn_kind]
    assert all(cache[leaf].shape[0] == cfg.n_layers for leaf in want[cfg.attn_kind])
    if name == "llama3-8b":
        return
    # the layer loop is the one scan over the one stack: its operands are the
    # stacked leaves themselves
    params = T.init_transformer(jax.random.key(0), cfg)
    text = jax.jit(lambda p, t, c: T.decode_step(p, t, c, cfg)).lower(
        params, jnp.zeros((2, 1), jnp.int32), T.init_cache(cfg, 2)).as_text()
    assert text.count("stablehlo.while") == 1


def test_a_retention_state_takes_the_cache_type_and_a_state_space_one_does_not():
    ret = dataclasses.replace(CONFIGS["tiny-retention"], kv_dtype=jnp.bfloat16)
    assert T.init_cache(ret, 1)["s"].dtype == jnp.bfloat16
    jam = dataclasses.replace(CONFIGS["tiny-jamba"], kv_dtype=jnp.float8_e4m3fn)
    cache = T.init_cache(jam, 1)
    assert cache["k"].dtype == jnp.float8_e4m3fn and cache["ssm"].dtype == jnp.float32
    assert cache["conv"].dtype == jnp.float32  # the model's own type


# -- (c) the normal serving path ------------------------------------------------------------

def _device(**env):
    defaults = {"MODEL_NAME": "tiny-jamba", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
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
    """What the whole-sequence forward, which has no cache and no pool,
    puts first after the prompt and after each of its own tokens."""
    cfg = CONFIGS["tiny-jamba"]
    params = T.init_transformer(jax.random.key(0), cfg)
    forward = jax.jit(lambda p, t: T.transformer_forward(p, t, cfg))
    seq = list(prompt)
    for _ in range(n):
        # right-padded to one width (one compile): every part of a layer is
        # causal or by the token, so padding stays out of earlier positions
        padded = jnp.zeros((1, 64), jnp.int32).at[0, :len(seq)].set(jnp.asarray(seq))
        seq.append(int(jnp.argmax(forward(params, padded)[0, len(seq) - 1])))
    return seq[len(prompt):]


def test_the_served_tokens_are_the_whole_sequence_forwards(device):
    """Length 9 takes a batched prefill, 50 a chunked one (above the top
    bucket of 32: two slices, the second from the first's state, tail and
    K/V rows)."""
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


def test_a_row_moved_between_pool_slots_keeps_every_leaf(device):
    pool = device.decode_pool
    assert pool is not None and not pool._active
    assert T.cache_leaves(pool.cache) == ("conv", "k", "ssm", "v")
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
    import time

    for _ in range(200):
        records = device.timeline.records(limit=1000)
        if all(r["status"] != "running" for r in records):
            return records
        time.sleep(0.05)
    raise AssertionError("a dispatch stayed running")


def test_dispatch_records_count_the_state_a_chunk_moved_and_what_a_slice_carried(device):
    device.timeline._ring.clear()
    short, long_, _ = _prompts()
    device.generate(short, max_new_tokens=9)
    records = _finished_records(device)
    row = 4 * (16 * 128 * 4 + 3 * 128 * 4)  # 4 state-space layers: state and tail, float32
    assert device.decode_pool._state_row_bytes == row
    chunks = [r for r in records if r["kind"] == "decode_chunk" and r["batch_size"]]
    assert chunks and all(r["state_bytes"] == r["batch_size"] * 2 * row * 4 for r in chunks)
    assert all(r["kv_blocks_held"] for r in chunks)  # and K/V rows beside it
    device.timeline._ring.clear()
    device.generate(long_, max_new_tokens=2)
    slices = sorted((r for r in _finished_records(device) if r["kind"] == "prefill_chunk"),
                    key=lambda r: r["dispatch_id"])
    assert [r["tokens"] for r in slices] == [32, 18]
    assert [r["carried"] for r in slices] == [False, True]
    # a state-space row is small: its chunked prefills are not gated (a retention row's are)
    assert device.runner._state_prefill_gate is None


def test_a_large_state_is_what_gates_chunked_prefill_not_a_kind_of_attention():
    import gofr_tpu.tpu.device as D

    old = D._STATE_GATE_BYTES
    D._STATE_GATE_BYTES = 1 << 10
    try:
        dev = _device()
        try:
            assert dev.runner._state_prefill_gate is not None
            assert len(dev.generate(_prompts()[1], max_new_tokens=3)) == 3
        finally:
            dev.close()
    finally:
        D._STATE_GATE_BYTES = old


# -- (d) what this cache cannot serve is refused at boot, by name ------------------------------

@pytest.mark.parametrize("setting,value", [
    ("PREFIX_CACHE", "4"), ("KV_BLOCKS", "64"), ("KV_HBM_BUDGET_MB", "8"),
    ("DRAFT_MODEL_NAME", "tiny"), ("SPEC_POOLED", "on"), ("KV_TRANSFER", "on"),
    ("KV_TRANSFER_TRUST_HINT", "on"), ("FLEET_ROLE", "prefill"), ("TPU_MESH", "tp=2"),
])
def test_a_setting_that_rests_on_kv_rows_is_refused_for_a_state_beside_them(setting, value):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*state per row beside"):
        _device(**{setting: value})


@pytest.mark.parametrize("setting,value,why", [
    ("MODEL_QUANT", "int8", "layers stacked per kind"),
    ("LORA_ADAPTERS", "a=/nowhere", "stacked per kind"),
])
def test_what_takes_one_stack_of_layers_is_refused_for_layers_stacked_per_kind(setting, value, why):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*{why}"):
        _device(**{setting: value})


def test_the_cache_type_is_what_k_and_v_take_and_the_state_stays_float32():
    dev = _device(MODEL_KV_DTYPE="f8")
    try:
        cache = dev.decode_pool.cache
        assert cache["k"].dtype == cache["v"].dtype == jnp.float8_e4m3fn
        assert cache["ssm"].dtype == jnp.float32 and cache["conv"].dtype == jnp.float32
        assert len(dev.generate(_prompts()[0], max_new_tokens=4)) == 4
    finally:
        dev.close()


# -- (e) twenty query heads on one kv head through both forms of the flash forward ---------------

@pytest.mark.parametrize("sq,form", [(1, "decode"), (6, "decode"), (7, "prefill"), (40, "prefill")])
def test_a_group_of_twenty_query_heads_on_one_kv_head_matches_the_reference(sq, form):
    """Jamba2-3B's grouping: not a power of two (the cells that are there
    have 2 and 4). The decode form holds the group's 20 x sq rows in one q
    block (padded to a tile of 16: 32 rows for one token); the prefill form
    revisits the one kv head's block for each of the 20 query heads."""
    from gofr_tpu.ops import flash
    from gofr_tpu.ops.attention import _xla_attention
    from tests.test_flash_decode import _grids

    layers, batch, skv, d, groups = 2, 3, 512, 64, 20
    kk, kv, kq = jax.random.split(jax.random.key(20), 3)
    k = jax.random.normal(kk, (layers, batch, 1, skv, d))
    v = jax.random.normal(kv, (layers, batch, 1, skv, d))
    q = jax.random.normal(kq, (batch, sq, groups, d))
    lens = jnp.asarray([0, 300, 512], jnp.int32)
    offsets = jnp.maximum(lens - sq, 0)

    def call(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, q_offset=offsets, kv_lens=lens,
                                     layer=jnp.int32(1))

    want_grid = (batch, 1) if form == "decode" else (batch, groups, 1)
    assert _grids(call, q, k, v) == [want_grid]
    mask = jnp.arange(skv)[None, :] < lens[:, None]
    want = _xla_attention(q, jnp.swapaxes(k[1], 1, 2), jnp.swapaxes(v[1], 1, 2), True, offsets,
                          mask, None)
    out = np.asarray(call(q, k, v))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(out[live], np.asarray(want)[live], atol=2e-5, rtol=2e-5)
    assert not out[~live].any()
