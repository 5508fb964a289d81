"""Latent attention (MLA) over a latent cache, the top-k gate over a share of
the experts with identity experts, and the shortcut-connected double layer
(``tiny-longcat``'s block) on the serving path: prefill and decode through
the latent cache, chunked prefill from a carried latent and the decode pool's
row moves against the benchmark's plain reference (logits, not tokens), the
two forms of the attention on one cache, the pair form of the routed product
against a dense loop, the share of guide section 4 (all ranks' parts add up to
the whole layer), the counters, and the settings this cache cannot serve.
CPU, tiny widths (hidden 64, 4 heads, latent 16 + 8, 8 routed experts of which
2 are held, 4 identity experts, top-3)."""

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

ARCH = spec.load_module("architectures", "mla_scmoe")
REF_CFG = {
    "_name": "tiny-mla", "attention_bias": False, "vocab_size": 256, "hidden_size": 64,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 3,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 2,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "attention_method": "MLA", "zero_expert_num": 4, "zero_expert_type": "identity",
    "moe_topk": 3, "published": {"n_routed_experts": 8},
    "deployment": {"ep": 4, "ep_rank": 0},
    "serving": {"quant": "", "dtype": "float32"},
}
SEED, PROMPT, STEPS = 13, 23, 16
# float32 on both sides, the sums in another order (a cached latent and the
# absorbed product against keys and values expanded over the whole sequence;
# pairs sorted and summed against a loop over experts): measured 4e-6 on
# logits of size 3. bfloat16 anywhere reads 1e-2 and a dropped term 0.05 up
TOLERANCE = 1e-4


def _program_cfg(ref=REF_CFG, **over):
    sz = ARCH.sizes_of(ref)
    fields = dict(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=1, hidden_dim=sz["dense_ffn"], max_seq=128, rope_theta=10000.0,
        norm_eps=1e-5, dtype=jnp.float32, attn_impl="xla", attn_kind="mla",
        q_lora_rank=sz["q_rank"], kv_lora_rank=sz["kv_rank"], qk_nope_dim=sz["nope"],
        qk_rope_dim=sz["rope"], v_head_dim=sz["v"], ffn_kind="scmoe", router_kind="linear",
        n_experts=sz["experts"], n_routed_experts=sz["routed"],
        n_identity_experts=sz["identity"], top_k=sz["top_k"], routed_scale=sz["scale"],
        ep_rank=sz["ep_rank"], expert_dim=sz["ffn"])
    fields.update(over)
    return T.TransformerConfig(**fields), sz


def _model(ref=REF_CFG, **over):
    cfg, sz = _program_cfg(ref, **over)
    return cfg, ARCH.make_params(SEED, sz)


def _tokens():
    return np.asarray(jax.random.randint(jax.random.key(5), (1, PROMPT + STEPS), 3, 256))


def _reference_logits(mode=None, ref=REF_CFG):
    toks = _tokens()
    cols = np.arange(PROMPT - 1, PROMPT + STEPS - 1)
    (logits,) = list(ARCH.logits_at(SEED, ref, [(toks, np.zeros_like(cols), cols)], mode))
    return np.asarray(logits)  # [STEPS, V]: after the prompt, then after each fed token


def _programs(cfg):
    return (jax.jit(lambda p, t, c, n: T.prefill(p, t, c, cfg, n)),
            jax.jit(lambda p, t, c: T.decode_step(p, t, c, cfg)))


def _decode_on(step_fn, params, logits, cache, first=0, steps=STEPS - 1):
    toks, out = jnp.asarray(_tokens()), [logits[0]]
    for i in range(first, steps):
        logits, cache = step_fn(params, toks[:, PROMPT + i:PROMPT + i + 1], cache)
        out.append(logits[0])
    return out


def _served_logits(cfg, params):
    """Prefill in a right-padded bucket of 32 (the expanded form), then
    decode steps (the absorbed form) that feed the reference's tokens."""
    prefill, step = _programs(cfg)
    toks = jnp.asarray(_tokens())
    padded = jnp.zeros((1, 32), jnp.int32).at[:, :PROMPT].set(toks[:, :PROMPT])
    logits, cache = prefill(params, padded, T.init_cache(cfg, 1), jnp.array([PROMPT]))
    return np.asarray(jnp.stack(_decode_on(step, params, logits, cache)))


# -- (a) the model against the plain reference ----------------------------------------

def test_prefill_then_decode_through_the_latent_cache_gives_the_references_logits():
    got, want = _served_logits(*_model()), _reference_logits()
    assert np.abs(want).max() > 1.0
    assert np.max(np.abs(got - want)) < TOLERANCE


def test_the_whole_sequence_forward_gives_the_references_logits():
    cfg, params = _model()
    got = T.transformer_forward(params, jnp.asarray(_tokens()), cfg)[0, PROMPT - 1:-1]
    assert np.max(np.abs(np.asarray(got) - _reference_logits())) < TOLERANCE


def test_a_prompt_prefilled_in_slices_from_a_carried_latent_gives_the_references_logits():
    """Three slices of 8 in a bucket of 8 (the last one 7 real tokens and a
    pad): each attends over the latent the ones before left, expanded again."""
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


@pytest.mark.parametrize("leaf", ["latent", "k_rope"])
def test_a_second_slice_without_the_first_ones_latent_is_another_answer(leaf):
    cfg, params = _model()
    prefill, _ = _programs(cfg)
    toks = jnp.asarray(_tokens())
    _, cache = prefill(params, toks[:, :16], T.init_cache(cfg, 1), jnp.array([16]))
    want, _ = prefill(params, toks[:, 16:24], cache, jnp.array([8]))
    lost = {**cache, leaf: jnp.zeros_like(cache[leaf])}
    got, _ = prefill(params, toks[:, 16:24], lost, jnp.array([8]))
    assert np.max(np.abs(np.asarray(got - want))) > 100 * TOLERANCE


def test_decode_through_the_pools_row_moves_with_rows_of_unequal_length_and_a_dead_slot():
    """The prefilled row written into slot 2 of a four-slot pool cache (as
    ``decode_pool.write_slot`` writes it: every leaf at its row axis), a
    shorter request of other tokens live in slot 0, slots 1 and 3 not live
    and full of what earlier requests left; pooled steps; the row read back
    out and decoded alone."""
    cfg, params = _model()
    prefill, step = _programs(cfg)
    toks = jnp.asarray(_tokens())
    logits, row = prefill(params, toks[:, :PROMPT], T.init_cache(cfg, 1), jnp.array([PROMPT]))
    _, other = prefill(params, toks[:, 5:14], T.init_cache(cfg, 1), jnp.array([9]))
    pool = T.init_cache(cfg, 4)
    noise = {name: jax.random.normal(jax.random.key(9), leaf.shape).astype(leaf.dtype)
             for name, leaf in pool.items() if leaf.ndim > 1}
    pool = {**pool, **noise, "lengths": jnp.asarray([0, 40, 0, 17], jnp.int32)}
    write = lambda pool, row, i: {  # noqa: E731
        name: jax.lax.dynamic_update_slice_in_dim(leaf, row[name], i, axis=0 if leaf.ndim == 1 else 1)
        for name, leaf in pool.items()}
    pool = {**write(write(pool, row, 2), other, 0), "live": jnp.asarray([1, 0, 1, 0], jnp.int32)}
    out = [logits[0]]
    for i in range(7):
        feed = jnp.full((4, 1), 7, jnp.int32).at[2].set(toks[0, PROMPT + i])
        logits, pool = step(params, feed, pool)
        out.append(logits[2])
    back = {name: leaf[2:3] if leaf.ndim == 1 else leaf[:, 2:3] for name, leaf in pool.items()}
    back["live"] = jnp.ones((1,), jnp.int32)
    steps = _decode_on(step, params, out[-1][None], back, first=7)[1:]
    got = np.asarray(jnp.stack(out + steps))
    assert np.max(np.abs(got - _reference_logits())) < TOLERANCE


@pytest.mark.parametrize("part", ["scale_q", "scale_kv", "kv_norm", "shared_rope", "shortcut",
                                  "factor_six", "identity", "dense_0", "router_bias"])
def test_dropping_a_term_of_the_mathematics_fails_the_tolerance(part, monkeypatch):
    """Each term the reference has and a plainer block has not: the program
    without it is another model."""
    cfg, params = _model()
    sub = params["layers"]["sub"]
    if part == "scale_q":
        sub["q_norm"] = sub["q_norm"] / (cfg.dim / cfg.q_lora_rank) ** 0.5
    elif part == "scale_kv":
        sub["kv_norm"] = sub["kv_norm"] / (cfg.dim / cfg.kv_lora_rank) ** 0.5
    elif part == "kv_norm":
        monkeypatch.setattr(T, "rms_norm", _rms_that_skips(T.rms_norm, cfg.kv_lora_rank))
    elif part == "shared_rope":
        monkeypatch.setattr(T, "_pairs_apart", lambda x: x)  # split-half pairs, not interleaved
    elif part == "shortcut":
        cfg = dataclasses.replace(cfg, routed_scale=0.0)  # the expert product gone
    elif part == "factor_six":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif part == "identity":
        cfg = dataclasses.replace(cfg, n_identity_experts=0, n_routed_experts=12)
    elif part == "dense_0":
        sub["w_down"] = sub["w_down"].at[0::2].set(0.0)
    elif part == "router_bias":
        params["layers"]["router_bias"] = params["layers"]["router_bias"].at[:, 0].set(1.0)
    got = T.transformer_forward(params, jnp.asarray(_tokens()), cfg)[0, PROMPT - 1:-1]
    assert np.max(np.abs(np.asarray(got) - _reference_logits())) > 100 * TOLERANCE


def _rms_that_skips(rms, width):
    def patched(x, weight, eps=1e-5):
        return x if x.shape[-1] == width else rms(x, weight, eps)
    return patched


@pytest.mark.parametrize("mode,fails", [("bf16", True), (None, False)])
def test_a_lower_precision_fails_the_tolerance(mode, fails):
    got = _served_logits(*_model())
    assert (np.max(np.abs(got - _reference_logits(mode))) > TOLERANCE) == fails


# -- (b) the two forms of the attention on one cache ----------------------------------

def _a_latent_cache(sq, places=3, b=3, h=4, nope=16, rope=8, dv=16, rank=32, skv=64):
    keys = jax.random.split(jax.random.key(3), 5)
    q_nope = jax.random.normal(keys[0], (b, sq, h, nope))
    q_rope = jax.random.normal(keys[1], (b, sq, h, rope))
    latent = jax.random.normal(keys[2], (places, b, skv, rank))
    k_rope = jax.random.normal(keys[3], (places, b, rope, skv))
    w = jax.random.normal(keys[4], (rank, h * (nope + dv))) * rank ** -0.5
    lens = jnp.asarray([0, skv * 5 // 8, skv][:b], jnp.int32)
    return q_nope, q_rope, latent, k_rope, w, jnp.maximum(lens - sq, 0), lens


@pytest.mark.parametrize("sq", [1, 2, 5])
def test_the_absorbed_form_is_the_expanded_form_on_the_same_cache(sq):
    from gofr_tpu.ops.mla import latent_attention

    from gofr_tpu.ops import mla

    args = _a_latent_cache(sq)
    q_nope, q_rope, latent, k_rope, w, starts, lens = args
    w = w.reshape(latent.shape[-1], q_nope.shape[2], -1)
    w_uk, w_uv = w[..., :q_nope.shape[-1]], w[..., q_nope.shape[-1]:]
    scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    absorbed, expanded = (np.asarray(form(
        q_nope, q_rope, latent, k_rope, jnp.int32(1), w_uk, w_uv, starts, lens, scale, False))
        for form in (mla.absorbed, mla.expanded))
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-5)
    assert not absorbed[0].any() and np.abs(absorbed[1:]).max() > 0.1  # a dead row reads nothing
    # the form is read from the shape alone: 4 heads x sq rows in one q block of 128
    np.testing.assert_array_equal(
        np.asarray(latent_attention(*args, jnp.int32(1), impl="xla")), absorbed)
    other_place = np.asarray(latent_attention(*args, jnp.int32(2), impl="xla"))
    assert np.abs(other_place[1:] - absorbed[1:]).max() > 0.1


@pytest.mark.parametrize("sq,skv", [(1, 256), (2, 256), (1, 64)])
def test_the_absorbed_kernel_is_the_absorbed_form_in_plain_numpy(sq, skv):
    """``mla_absorbed_decode`` in interpret mode: rows of length 0, of a
    block and a part, and full; two blocks of 128 positions (or one short
    one); the queries of a verify chunk of two at their own positions."""
    from gofr_tpu.ops.mla import latent_attention

    args = _a_latent_cache(sq, rank=128, skv=skv)
    plain = np.asarray(latent_attention(*args, jnp.int32(1), impl="xla"))
    kernel = np.asarray(jax.jit(lambda *a: latent_attention(
        *a, jnp.int32(1), impl="pallas"))(*args))
    np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=2e-5)
    assert not kernel[0].any()


@pytest.mark.parametrize("sq,form", [(4, "decode"), (200, "prefill")])
def test_the_flash_forward_takes_a_value_narrower_than_its_key(sq, form):
    """The expanded form's shapes: keys 24 wide (nope + rope), values 16."""
    from gofr_tpu.ops import flash
    from tests.test_flash_decode import _grids

    batch, heads, skv, dk, dv = 2, 2, 256, 24, 16
    kq, kk, kv = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(kq, (batch, sq, heads, dk))
    k = jax.random.normal(kk, (1, batch, heads, skv, dk))
    v = jax.random.normal(kv, (1, batch, heads, skv, dv))
    lens = jnp.asarray([230, 256], jnp.int32)
    offsets = lens - sq

    def call(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, q_offset=offsets, kv_lens=lens,
                                     layer=jnp.int32(0))

    assert _grids(call, q, k, v) == [(batch, heads) if form == "decode" else (batch, heads, 2)]
    scores = jnp.einsum("bshd,bhtd->bhst", q, k[0]) * dk ** -0.5
    t = jnp.arange(skv)[None, None, None, :]
    seen = (t <= (offsets[:, None, None, None] + jnp.arange(sq)[None, None, :, None])) & (
        t < lens[:, None, None, None])
    want = jnp.einsum("bhst,bhtv->bshv", jax.nn.softmax(jnp.where(seen, scores, -1e30), -1), v[0])
    np.testing.assert_allclose(np.asarray(call(q, k, v)), np.asarray(want), atol=2e-5, rtol=2e-5)


# -- (c) the pair form of the routed product -------------------------------------------

def _pairs_by_a_loop(x, expert, weight, w_gate, w_up, w_down):
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, wt in zip(np.asarray(expert[t]), np.asarray(weight[t])):
            if e < w_gate.shape[0]:
                g, u = x[t] @ np.asarray(w_gate[e], np.float64), x[t] @ np.asarray(w_up[e], np.float64)
                out[t] += wt * ((g / (1 + np.exp(-g)) * u) @ np.asarray(w_down[e], np.float64))
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("tokens,held_share", [(20, 0.2), (300, 0.1), (300, 0.9)])
def test_the_pair_form_is_a_loop_over_every_pair(impl, tokens, held_share):
    """k = 3 pairs a token over 4 held experts; most pairs go to none here
    (``n``). At a share of 0.9 the pairs that land outnumber one pass's
    rows (150 of 300 tokens' 900) and the loop takes several."""
    from gofr_tpu.ops.experts import pair_capacity, routed_experts

    n, d, f, k = 4, 128, 128, 3
    keys = jax.random.split(jax.random.key(tokens), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    wg, wu = (jax.random.normal(kk, (n, d, f)) * d ** -0.5 for kk in keys[1:3])
    wd = jax.random.normal(keys[3], (n, f, d)) * f ** -0.5
    expert = jnp.where(jax.random.uniform(keys[4], (tokens, k)) < held_share,
                       jax.random.randint(keys[5], (tokens, k), 0, n), n).astype(jnp.int32)
    weight = jax.random.uniform(keys[5], (tokens, k))
    y, counts = jax.jit(lambda *a: routed_experts(*a[:5], impl=impl, weight=a[5]))(
        x, expert, wg, wu, wd, weight)
    landed = int((np.asarray(expert) < n).sum())
    assert (landed > pair_capacity(tokens)) == (held_share > 0.5)
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(
        np.asarray(expert).ravel(), minlength=n + 1)[:n])
    np.testing.assert_allclose(np.asarray(y), _pairs_by_a_loop(x, expert, weight, wg, wu, wd),
                               atol=2e-4, rtol=2e-4)


def test_the_routed_layer_is_the_dense_loop_with_identity_experts_and_the_factor_six():
    """``routed_mlp`` under the linear router against the reference's dense
    form: every held expert over every token under the pairs' weights, the
    identity pairs' own input, all times 6; a pad and a dead row in no count."""
    from gofr_tpu.models.moe import routed_mlp

    cfg, params = _model()
    sz = ARCH.sizes_of(REF_CFG)
    layers = params["layers"]
    experts = {n: layers[n] for n in T.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.key(2), (3, 10, cfg.dim))
    mask = jnp.ones((3, 10), bool).at[0, 7:].set(False).at[2].set(False)
    layer_p = {"router": layers["router"][1], "router_bias": layers["router_bias"][1]}
    y, aux = routed_mlp(cfg, layer_p, h, jnp.zeros((3, 10, 0)), experts, jnp.int32(1), mask)
    w = {n: layers[n][1] for n in ("router", "router_bias") + T.EXPERT_LEAVES}
    flat = h.reshape(30, cfg.dim)
    choice, weight = ARCH.route(flat, w, sz)
    per, own = ARCH.held_weights(choice, weight, sz)
    want = (ARCH.experts_dense(flat, per, w) + own[:, None] * flat).reshape(3, 10, cfg.dim)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(y)[real], np.asarray(want)[real], atol=2e-5, rtol=2e-5)
    assert not np.asarray(y)[~real].any()
    counts = np.asarray(aux["expert_counts"])
    assert counts.shape == (cfg.n_experts + 2,) and counts.sum() == cfg.top_k * real.sum()
    chosen = np.asarray(choice).reshape(3, 10, -1)[real]
    assert counts[-2] == (chosen >= sz["routed"]).sum() and counts[-2] > 0
    assert counts[-1] == ((chosen >= sz["experts"]) & (chosen < sz["routed"])).sum()
    # the reference's two forms of the held experts agree
    np.testing.assert_allclose(np.asarray(ARCH.experts_indexed(flat, per, w, 16)),
                               np.asarray(ARCH.experts_dense(flat, per, w)), atol=2e-5)


def test_over_all_ranks_the_parts_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: over the four
    ranks of ep = 4 the routed parts, with the identity part (which every
    chip computes alike for its own tokens) counted once, add up to what the
    uncut reference gives for the whole expert product: all 8 routed experts
    held by one chip."""
    from gofr_tpu.models.moe import routed_mlp

    h = jax.random.normal(jax.random.key(6), (2, 12, 64))
    whole = dict(REF_CFG, n_routed_experts=8, deployment={"ep": 1, "ep_rank": 0})
    sz = ARCH.sizes_of(whole)
    w = {n: v.astype(jnp.float32) for n, v in ARCH.moe_values(
        jnp.uint32(SEED), jnp.int32(1), sz).items()}
    flat = h.reshape(24, 64)
    choice, weight = ARCH.route(flat, w, sz)
    per, own = ARCH.held_weights(choice, weight, sz)
    want = np.asarray(ARCH.experts_dense(flat, per, w) + own[:, None] * flat)
    identity_part = np.asarray(own[:, None] * flat)
    total, pairs = np.zeros_like(want), 0
    for rank in range(4):
        ref = dict(REF_CFG, deployment={"ep": 4, "ep_rank": rank})
        cfg, rank_sz = _program_cfg(ref)
        mine = ARCH.moe_values(jnp.uint32(SEED), jnp.int32(1), rank_sz)
        for name in T.EXPERT_LEAVES:  # a rank's experts ARE the whole model's, at their places
            np.testing.assert_array_equal(np.asarray(mine[name]),
                                          np.asarray(w[name][2 * rank:2 * rank + 2]))
        y, aux = routed_mlp(cfg, {"router": mine["router"], "router_bias": mine["router_bias"]},
                            h, jnp.zeros((2, 12, 0)), {n: mine[n][None] for n in T.EXPERT_LEAVES},
                            jnp.int32(0), None)
        total += np.asarray(y).reshape(24, 64) - identity_part
        pairs += int(np.asarray(aux["expert_counts"])[:2].sum())
    np.testing.assert_allclose(total + identity_part, want, atol=5e-5, rtol=5e-5)
    assert pairs == int((np.asarray(choice) < 8).sum())  # every routed pair landed on one rank


# -- (d) trees, caches and programs ----------------------------------------------------

def test_the_tree_the_cache_and_the_counters_have_the_shapes_the_issue_states():
    cfg = CONFIGS["longcat-flash-ep32"]
    four = dataclasses.replace(cfg, n_layers=4, vocab_size=16384, max_seq=7168)
    cache = jax.eval_shape(lambda: T.init_cache(four, 40, 7168))
    assert {n: (v.shape, str(v.dtype)) for n, v in cache.items() if v.ndim > 1} == {
        "latent": ((8, 40, 7168, 512), "bfloat16"), "k_rope": ((8, 40, 64, 7168), "bfloat16")}
    assert T.cache_leaves(cache) == ("k_rope", "latent")
    assert T.latent_token_bytes(cache) == 9216 and T.state_row_bytes(cache) == 0
    tree = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), four))
    layers = tree["layers"]
    assert layers["sub"]["wq_b"].shape == (8, 1536, 64 * 192)  # sublayer j of layer i at 2 i + j
    assert layers["sub"]["wkv_a"].shape == (8, 6144, 576)
    assert layers["sub"]["wkv_b"].shape == (8, 512, 64 * 256)
    assert layers["sub"]["wo"].shape == (8, 8192, 6144)
    assert layers["sub"]["w_gate"].shape == (8, 6144, 12288)
    assert layers["w_gate"].shape == (4, 16, 6144, 2048) and layers["router"].shape == (4, 6144, 768)
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))
    assert round(count / 1e9, 2) == 5.17  # the issue's 5.17 B parameters
    assert four.routing_width == 18 and four.mixers_per_layer == 2 and four.rope_dim == 64


@pytest.mark.parametrize("name", ["tiny", "tiny-retention", "tiny-zaya", "tiny-jamba", "llama3-8b"])
def test_a_model_of_the_other_kinds_keeps_its_tree_its_cache_and_its_counters(name):
    cfg = CONFIGS[name]
    assert cfg.mixers_per_layer == 1 and cfg.router_kind == "mlp" and cfg.top_k == 1
    assert cfg.routed == (name == "tiny-zaya") and cfg.routing_width == cfg.n_experts
    assert cfg.expert_dim == cfg.hidden_dim
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, 64))
    assert "latent" not in cache and T.latent_token_bytes(cache) == 0
    if name != "llama3-8b":
        tree = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
        assert "sub" not in tree["layers"] and "router" not in tree["layers"]


def test_latent_attention_before_a_plain_feed_forward_serves_what_its_forward_gives():
    """The mixer is a kind of its own: one MLA sublayer and one dense SwiGLU
    a layer (no double layer, no experts), one place a layer in the cache."""
    cfg = dataclasses.replace(CONFIGS["tiny-longcat"], ffn_kind="dense", router_kind="mlp",
                              n_experts=0, n_routed_experts=0, n_identity_experts=0, top_k=1)
    params = T.init_transformer(jax.random.key(2), cfg)
    assert "sub" not in params["layers"] and params["layers"]["wkv_a"].shape == (2, 64, 24)
    toks = jnp.asarray(_tokens())
    full = T.transformer_forward(params, toks, cfg)
    cache = T.init_cache(cfg, 1, 64)
    assert cache["latent"].shape == (2, 1, 64, 16)
    logits, cache = T.prefill(params, toks[:, :PROMPT], cache, cfg, jnp.array([PROMPT]))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full[0, PROMPT - 1]), atol=1e-4)
    logits, cache = T.decode_step(params, toks[:, PROMPT:PROMPT + 1], cache, cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full[0, PROMPT]), atol=1e-4)


def test_the_top_one_product_is_what_it_was():
    """ZAYA1's path: one expert a token through ``routed_experts`` gives the
    bits the sorted product gives on its own, and its counts."""
    from gofr_tpu.ops.experts import _sorted_product, routed_experts

    n, d, f, tokens = 4, 128, 128, 50
    keys = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    wg, wu = (jax.random.normal(kk, (n, d, f)) * d ** -0.5 for kk in keys[1:3])
    wd = jax.random.normal(keys[3], (n, f, d)) * f ** -0.5
    expert = jax.random.randint(keys[4], (tokens,), 0, n + 1).astype(jnp.int32)
    y, counts = routed_experts(x, expert, wg, wu, wd)
    order = np.argsort(np.asarray(expert), kind="stable")
    ys = _sorted_product(x[order], counts, wg[None], wu[None], wd[None], jnp.int32(0), "auto")
    back = np.empty(tokens, np.int64)
    back[order] = np.arange(tokens)
    want = np.where((np.asarray(expert) < n)[:, None], np.asarray(ys)[back], 0.0)
    np.testing.assert_array_equal(np.asarray(y), want)
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(np.asarray(expert),
                                                                  minlength=n + 1)[:n])


# -- (e) on the serving path: the device, the pool, chunked prefill -------------------

def _device(**env):
    defaults = {"MODEL_NAME": "tiny-longcat", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
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
    cfg = CONFIGS["tiny-longcat"]
    params = T.init_transformer(jax.random.key(0), cfg)
    seq = list(prompt)
    for _ in range(n):
        logits = T.transformer_forward(params, jnp.asarray([seq]), cfg)
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def test_the_served_tokens_are_the_whole_sequence_forwards(device):
    """Length 9 takes a batched prefill, 50 a chunked one (above the top
    bucket of 32: two slices, the second over the first's latent)."""
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


def test_a_row_moved_between_pool_slots_keeps_both_latent_leaves(device):
    pool = device.decode_pool
    assert pool is not None and not pool._active
    assert T.cache_leaves(pool.cache) == ("k_rope", "latent") and pool.max_len == 128
    before = jax.tree.map(np.asarray, pool.cache)
    row = {name: jax.random.normal(jax.random.key(i), (1,) + leaf.shape[1:]).astype(leaf.dtype)
           if leaf.ndim == 1 else
           jax.random.normal(jax.random.key(i), leaf.shape[:1] + (1,) + leaf.shape[2:]).astype(leaf.dtype)
           for i, (name, leaf) in enumerate(sorted(pool.cache.items()))}
    pool.cache = pool._write_slot(pool.cache, row, 1)
    moved = pool._read_slot(pool.cache, 1)
    pool.cache = pool._write_slot(pool.cache, moved, 2)
    back = pool._read_slot(pool.cache, 2)
    for name in row:
        np.testing.assert_array_equal(np.asarray(back[name]), np.asarray(row[name]))
    pool.cache = jax.tree.map(jnp.asarray, before)


def _finished_records(device):
    import time

    for _ in range(200):
        records = device.timeline.records(limit=1000)
        if all(r["status"] != "running" for r in records):
            return records
        time.sleep(0.05)
    raise AssertionError("a dispatch stayed running")


def test_dispatch_records_count_where_the_pairs_went_and_the_latent_read(device):
    """The three pair counts add up to top-k x real tokens x layers on every
    dispatch; pads and slots without a request are in none; the latent's
    bytes go by the rows' lengths."""
    cfg = CONFIGS["tiny-longcat"]
    token = 4 * (16 + 8) * 4  # 4 places, latent 16 + rope 8, float32
    assert device.decode_pool._latent_token_bytes == token
    device.timeline._ring.clear()
    short, long_, _ = _prompts()
    device.generate(short, max_new_tokens=9)
    records = _finished_records(device)
    pairs = lambda r: r["expert_tokens"] + r["identity_tokens"] + r["absent_tokens"]  # noqa: E731
    (first,) = [r for r in records if r["kind"] == "prefill"]
    assert pairs(first) == cfg.top_k * 9 * cfg.n_layers  # 7 pads and a padding row: in no count
    assert first["latent_bytes"] == 9 * token
    chunks = sorted((r for r in records if r["kind"] == "decode_chunk" and r["batch_size"]),
                    key=lambda r: r["dispatch_id"])
    assert chunks and all(pairs(r) == cfg.top_k * r["batch_size"] * 4 * cfg.n_layers for r in chunks)
    # the first chunk's four steps read 10, 11, 12 and 13 positions of the one live row
    assert chunks[0]["latent_bytes"] == token * sum(9 + step + 1 for step in range(4))
    assert all(r["experts_read"] <= cfg.n_experts * 4 * cfg.n_layers for r in chunks)
    assert all(r["expert_tokens_max"] <= r["expert_tokens"] for r in chunks)
    assert all(r["kv_blocks_read"] is None and r["state_bytes"] is None for r in chunks)
    device.timeline._ring.clear()
    device.generate(long_, max_new_tokens=2)
    slices = sorted((r for r in _finished_records(device) if r["kind"] == "prefill_chunk"),
                    key=lambda r: r["dispatch_id"])
    assert [r["tokens"] for r in slices] == [32, 18] and [r["carried"] for r in slices] == [False, True]
    assert [pairs(r) for r in slices] == [cfg.top_k * n * cfg.n_layers for n in (32, 18)]
    assert [r["latent_bytes"] for r in slices] == [32 * token, 50 * token]


# -- (f) what this cache cannot serve is refused at boot, by name -------------------------

@pytest.mark.parametrize("setting,value", [
    ("PREFIX_CACHE", "4"), ("KV_BLOCKS", "64"), ("KV_HBM_BUDGET_MB", "8"),
    ("DRAFT_MODEL_NAME", "tiny"), ("SPEC_POOLED", "on"), ("KV_TRANSFER", "on"),
    ("KV_TRANSFER_TRUST_HINT", "on"), ("FLEET_ROLE", "prefill"), ("TPU_MESH", "tp=2"),
    ("MODEL_KV_DTYPE", "f8"),
])
def test_a_setting_that_rests_on_kv_rows_is_refused_for_a_latent_cache(setting, value):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*latent a token, not K/V"):
        _device(**{setting: value})


@pytest.mark.parametrize("setting,value,why", [
    ("MODEL_QUANT", "int8", "expert-stacked leaves"),
    ("LORA_ADAPTERS", "a=/nowhere", "pairs of sublayers"),
])
def test_what_takes_one_plain_stack_of_layers_is_refused_for_the_double_layer(setting, value, why):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*{why}"):
        _device(**{setting: value})
