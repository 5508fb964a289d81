"""A leading dense layer before expert layers, a sigmoid gate whose chosen
scores are normalised, shared experts, every routed expert held here, and
latent attention without a query bottleneck (``tiny-moonlight``'s block) on
the serving path: prefill and decode through the latent cache, chunked
prefill from a carried latent and the decode pool's row moves against the
benchmark's plain reference (logits, not tokens), each term of the equations
dropped in turn, the two forms of the attention, the pair form at one pass
against a loop over pairs, the counters, and the settings this tree cannot
serve. CPU, tiny widths (hidden 64, 4 heads, latent 16 + 8, one dense layer
of 96, two expert layers of 8 routed experts of 32, top-3, 2 shared)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from gofr_tpu.models import transformer as T
from gofr_tpu.models.llama import CONFIGS
from tests.test_mla_block import _device as _longcat_device
from tests.test_mla_block import _finished_records, _pairs_by_a_loop, _prompts

ARCH = spec.load_module("architectures", "mla_moe")
REF_CFG = {
    "_name": "tiny-mla-moe", "attention_bias": False, "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "kv_lora_rank": 16,
    "q_lora_rank": None, "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "routed_scaling_factor": 2.5, "n_routed_experts": 8, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 50000.0, "tie_word_embeddings": False,
    "serving": {"quant": "", "dtype": "float32"},
}
SEED, PROMPT, STEPS = 13, 23, 16
# float32 on both sides, the sums in another order (a cached latent and the
# absorbed product against keys and values expanded over the whole sequence;
# pairs sorted and summed against a loop over experts): measured 4e-6 on
# logits of size 4. bfloat16 anywhere reads 1e-2 and a dropped term 0.02 up
TOLERANCE = 1e-4


def _model():
    """``tiny-moonlight`` IS the reference's tiny configuration: the
    program's own entry, and the reference's seeded weights."""
    return CONFIGS["tiny-moonlight"], ARCH.make_params(SEED, ARCH.sizes_of(REF_CFG))


def _tokens():
    return np.asarray(jax.random.randint(jax.random.key(5), (1, PROMPT + STEPS), 3, 256))


def _reference_logits(mode=None):
    toks = _tokens()
    cols = np.arange(PROMPT - 1, PROMPT + STEPS - 1)
    (logits,) = list(ARCH.logits_at(SEED, REF_CFG, [(toks, np.zeros_like(cols), cols)], mode))
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


def _forward_gap(cfg, params):
    got = T.transformer_forward(params, jnp.asarray(_tokens()), cfg)[0, PROMPT - 1:-1]
    return np.max(np.abs(np.asarray(got) - _reference_logits()))


# -- (a) the model against the plain reference ----------------------------------------

def test_the_programs_entry_is_the_references_tiny_configuration():
    cfg = CONFIGS["tiny-moonlight"]
    sz = ARCH.sizes_of(REF_CFG)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.hidden_dim, cfg.expert_dim) == (
        sz["dim"], sz["layers"], sz["heads"], sz["dense_ffn"], sz["ffn"])
    assert cfg.ffns == ("dense", "moe", "moe") and cfg.ffn_runs == (
        ("dense", 0, 0, 1), ("moe", 0, 1, 2))
    assert (cfg.n_experts, cfg.n_routed_experts, cfg.n_identity_experts, cfg.ep_rank) == (8, 8, 0, 0)
    assert (cfg.top_k, cfg.routed_scale, cfg.n_shared_experts) == (3, 2.5, 2)
    assert cfg.q_lora_rank == 0 and not cfg.mla_scale and cfg.head_dim == 24
    assert cfg.routed and cfg.ffn_stacked and not cfg.mixed and cfg.routing_width == 10


def test_prefill_then_decode_through_the_latent_cache_gives_the_references_logits():
    got, want = _served_logits(*_model()), _reference_logits()
    assert np.abs(want).max() > 1.0
    assert np.max(np.abs(got - want)) < TOLERANCE


def test_the_whole_sequence_forward_gives_the_references_logits():
    assert _forward_gap(*_model()) < TOLERANCE


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


def test_decode_through_the_pools_row_moves_with_rows_of_unequal_length_and_a_dead_slot():
    """The prefilled row written into slot 2 of a four-slot pool cache (every
    leaf at its row axis), a shorter request of other tokens live in slot 0,
    slots 1 and 3 not live and full of what earlier requests left; pooled
    steps; the row read back out and decoded alone."""
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


FAULTS = ["bias_as_weight", "no_normalisation", "softmax_for_sigmoid", "no_shared_expert",
          "dense_layer_given_experts", "factor", "rope_on_q_nope", "scale_factors",
          "split_half_rope", "no_kv_norm", "bias_ignored"]
BIAS = jnp.zeros((8,)).at[0].set(0.4).at[5].set(-0.3)  # moves some choices, and would weigh


def _plain_attention(x, w, sz, fault):
    """``ARCH.attention_one`` written out, with a fault switched in."""
    from benchmark import reference as R

    t, h, nope, rope, dv = x.shape[0], sz["heads"], sz["nope"], sz["rope"], sz["v"]
    turn = (lambda v: R.rope(v, 50000.0)) if fault == "split_half_rope" else (
        lambda v: ARCH.rope_pairs(v, 50000.0))
    hid = R.rms(x, w["attn_norm"], 1e-5)
    q = (hid @ w["wq"]).reshape(t, h, nope + rope)
    ckr = hid @ w["wkv_a"]
    c = ckr[:, :sz["kv_rank"]] if fault == "no_kv_norm" else R.rms(
        ckr[:, :sz["kv_rank"]], w["kv_norm"], 1e-5)
    if fault == "scale_factors":  # LongCat's two: sqrt(dim / rank) on q and on the latent
        q, c = q * 2.0, c * (sz["dim"] / sz["kv_rank"]) ** 0.5
    q_nope = turn(q[..., :nope]) if fault == "rope_on_q_nope" else q[..., :nope]
    q_rope, kr = turn(q[..., nope:]), turn(ckr[:, None, sz["kv_rank"]:])[:, 0]
    kv = (c @ w["wkv_b"]).reshape(t, h, nope + dv)
    scores = (jnp.einsum("shn,thn->hst", q_nope, kv[..., :nope])
              + jnp.einsum("shr,tr->hst", q_rope, kr)) * (nope + rope) ** -0.5
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return x + jnp.einsum("hst,thv->shv", probs, kv[..., nope:]).reshape(t, h * dv) @ w["wo"]


def _plain_route(a, w, sz, fault):
    logits = a @ w["router"]
    s = jax.nn.softmax(logits, axis=-1) if fault == "softmax_for_sigmoid" else jax.nn.sigmoid(logits)
    by = s if fault == "bias_ignored" else s + w["router_bias"]
    choice = jnp.argsort(-by, axis=-1)[:, :sz["top_k"]]
    chosen = jnp.take_along_axis(by if fault == "bias_as_weight" else s, choice, axis=-1)
    if fault != "no_normalisation":
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return choice, (1.0 if fault == "factor" else sz["scale"]) * chosen


def _plain_logits(fault=None, bias=BIAS):
    """The reference's equations over the test's sequence from its own
    building blocks, a layer at a time in plain jax.numpy, with the gate's
    bias set to ``bias`` and one term of the mathematics at fault."""
    from benchmark import reference as R

    sz = ARCH.sizes_of(REF_CFG)
    seed = jnp.uint32(SEED)
    with jax.default_matmul_precision("highest"):
        x = ARCH.leaf_values(seed, jnp.int32(-1), "embed", sz)[_tokens()[0]]
        for i in range(sz["layers"]):
            routed = i >= sz["dense_layers"] or fault == "dense_layer_given_experts"
            w = ARCH.layer_values(seed, jnp.int32(i), sz, routed)
            x = _plain_attention(x, w, sz, fault)
            a = R.rms(x, w["mlp_norm"], 1e-5)
            if not routed:
                x = x + ARCH.swiglu(a, w["w_gate"], w["w_up"], w["w_down"])
                continue
            w["router_bias"] = bias
            per = ARCH.expert_weights(*_plain_route(a, w, sz, fault), sz)
            x = x + ARCH.experts_dense(a, per, w)
            if fault != "no_shared_expert":
                x = x + ARCH.shared_expert(a, w)
        x = R.rms(x, ARCH.norm_values(seed, jnp.int32(-1), "norm_f", sz), 1e-5)
        return np.asarray(x @ ARCH.leaf_values(seed, jnp.int32(-1), "lm_head", sz))[PROMPT - 1:-1]


def _program_logits_with_bias():
    cfg, params = _model()
    moe = params["layers"]["moe"]
    moe["router_bias"] = jnp.broadcast_to(BIAS, moe["router_bias"].shape)
    return np.asarray(T.transformer_forward(params, jnp.asarray(_tokens()), cfg)[0, PROMPT - 1:-1])


def test_the_equations_written_out_are_the_reference_and_the_program():
    assert np.max(np.abs(_plain_logits() - _program_logits_with_bias())) < TOLERANCE
    seeded = jnp.zeros((8,))  # the reference's own bias
    assert np.max(np.abs(_plain_logits(bias=seeded) - _reference_logits())) < TOLERANCE


@pytest.mark.parametrize("fault", FAULTS)
def test_dropping_a_term_of_the_mathematics_fails_the_tolerance(fault):
    """Each term the equations have and a plainer (or a sibling's) block has
    not, dropped in turn from the equations written out: the program is not
    that model. The bias used as a weight, no normalisation over the chosen,
    softmax for sigmoid, the shared expert left out, the dense layer given
    experts, the factor, rotary on the query's nope part, LongCat's scale
    factors, split-half rotary pairs, the latent not normed, the bias
    ignored in the choice."""
    assert np.max(np.abs(_plain_logits(fault) - _program_logits_with_bias())) > 100 * TOLERANCE


@pytest.mark.parametrize("mode,fails", [("bf16", True), (None, False)])
def test_a_lower_precision_fails_the_tolerance(mode, fails):
    got = _served_logits(*_model())
    assert (np.max(np.abs(got - _reference_logits(mode))) > TOLERANCE) == fails


# -- (b) the two forms of the attention, without a bottleneck --------------------------

def test_a_decode_step_in_the_expanded_form_gives_what_the_absorbed_form_gives(monkeypatch):
    """The step picks the absorbed form by its shape (4 heads x 1 row in one
    q block); with no q block at all every call takes the expanded form."""
    from gofr_tpu.ops import mla

    cfg, params = _model()
    toks = jnp.asarray(_tokens())
    _, cache = T.prefill(params, toks[:, :PROMPT], T.init_cache(cfg, 1), cfg, jnp.array([PROMPT]))
    absorbed, _ = T.decode_step(params, toks[:, PROMPT:PROMPT + 1], cache, cfg)
    monkeypatch.setattr(mla, "DEFAULT_BLOCK_Q", 0)
    expanded, _ = T.decode_step(params, toks[:, PROMPT:PROMPT + 1], cache, cfg)
    assert np.max(np.abs(np.asarray(absorbed - expanded))) < TOLERANCE
    assert np.max(np.abs(np.asarray(absorbed[0]) - _reference_logits()[1])) < TOLERANCE


# -- (c) the pair form at one pass ------------------------------------------------------

@pytest.mark.parametrize("tokens,k,held,outputs,want", [
    (40, 12, 16, 768, 40), (2048, 12, 16, 768, 1024), (1024, 12, 16, 768, 512),  # LongCat's
    (48, 6, 64, 64, 288), (512, 6, 64, 64, 3072), (256, 6, 64, 64, 1536),  # every expert held
    (300, 1, 0, 1, 150), (20, 3, 0, 1, 20),  # no gate width stated: the floor
    (300, 3, 4, 8, 900), (300, 3, 2, 8, 450),
])
def test_the_rows_of_a_pass_follow_the_share_of_the_gates_outputs_held(tokens, k, held, outputs, want):
    from gofr_tpu.ops.experts import pair_capacity

    assert pair_capacity(tokens, k, held, outputs) == want


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("tokens", [20, 300])
def test_with_every_expert_held_one_pass_takes_every_pair(impl, tokens, monkeypatch):
    """k = 3 pairs a token over 4 experts, all held and every pair landing:
    one pass of ``tokens * k`` rows, counted as the loop body's runs, gives
    what a loop over every pair gives."""
    from gofr_tpu.ops import experts as E

    n, d, f, k = 4, 128, 128, 3
    keys = jax.random.split(jax.random.key(tokens), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    wg, wu = (jax.random.normal(kk, (n, d, f)) * d ** -0.5 for kk in keys[1:3])
    wd = jax.random.normal(keys[3], (n, f, d)) * f ** -0.5
    expert = jax.random.randint(keys[5], (tokens, k), 0, n).astype(jnp.int32)
    expert = expert.at[3].set(n)  # a pad: its pairs go nowhere
    weight = jax.random.uniform(keys[4], (tokens, k))
    rows = []
    product = E._sorted_product
    monkeypatch.setattr(E, "_sorted_product", lambda xs, *a: rows.append(xs.shape[0]) or product(xs, *a))
    y, counts = E.routed_experts(x, expert, wg, wu, wd, impl=impl, weight=weight, gate_outputs=n)
    assert rows == [tokens * k]  # traced once, at every pair's worth of rows
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(
        np.asarray(expert).ravel(), minlength=n + 1)[:n])
    np.testing.assert_allclose(np.asarray(y), _pairs_by_a_loop(x, expert, weight, wg, wu, wd),
                               atol=2e-4, rtol=2e-4)
    assert not np.asarray(y)[3].any()


def test_the_routed_layer_is_the_dense_loop_with_the_shared_expert():
    """``routed_mlp`` under the sigmoid gate against the reference's dense
    form: every expert over every token under the pairs' normalised weights
    times 2.5, and the shared expert; a pad and a dead row in no count."""
    from gofr_tpu.models.moe import routed_mlp

    cfg, params = _model()
    sz = ARCH.sizes_of(REF_CFG)
    moe = params["layers"]["moe"]
    experts = {n: moe[n] for n in T.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.key(2), (3, 10, cfg.dim))
    mask = jnp.ones((3, 10), bool).at[0, 7:].set(False).at[2].set(False)
    layer_p = {n: moe[n][1] for n in moe if n not in T.EXPERT_LEAVES}
    y, aux = routed_mlp(cfg, layer_p, h, jnp.zeros((3, 10, 0)), experts, jnp.int32(1), mask)
    w = {n: moe[n][1] for n in moe}
    flat = h.reshape(30, cfg.dim)
    choice, weight = ARCH.route(flat, w, sz)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, rtol=1e-6)
    per = ARCH.expert_weights(choice, weight, sz)
    want = (ARCH.experts_dense(flat, per, w) + ARCH.shared_expert(flat, w)).reshape(3, 10, cfg.dim)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(y)[real], np.asarray(want)[real], atol=2e-5, rtol=2e-5)
    counts = np.asarray(aux["expert_counts"])
    assert counts.shape == (cfg.n_experts + 2,) and counts.sum() == cfg.top_k * real.sum()
    assert counts[-2] == 0 and counts[-1] == 0  # no identity expert, none on another chip
    np.testing.assert_array_equal(counts[:8], np.bincount(
        np.asarray(choice).reshape(3, 10, -1)[real].ravel(), minlength=8))
    # the reference's two forms of the experts agree
    np.testing.assert_allclose(np.asarray(ARCH.experts_indexed(flat, per, w, 16)),
                               np.asarray(ARCH.experts_dense(flat, per, w)), atol=2e-5)


# -- (d) trees, caches and programs ----------------------------------------------------

def test_the_tree_the_cache_and_the_counters_have_the_shapes_the_issue_states():
    cfg = CONFIGS["moonlight-16b-a3b-9l"]
    assert cfg.ffns == ("dense",) + ("moe",) * 8 and cfg.ffn_runs == (
        ("dense", 0, 0, 1), ("moe", 0, 1, 8))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 48, 2048))
    assert {n: (v.shape, str(v.dtype)) for n, v in cache.items() if v.ndim > 1} == {
        "latent": ((9, 48, 2048, 512), "bfloat16"), "k_rope": ((9, 48, 64, 2048), "bfloat16")}
    assert T.latent_token_bytes(cache) == 9 * 1152 and T.state_row_bytes(cache) == 0
    tree = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
    dense, moe = tree["layers"]["dense"], tree["layers"]["moe"]
    assert dense["wq"].shape == (1, 2048, 16 * 192) and dense["w_gate"].shape == (1, 2048, 11264)
    assert "wq_a" not in moe and "q_norm" not in moe and "router" not in dense
    assert moe["wkv_a"].shape == (8, 2048, 576) and moe["wkv_b"].shape == (8, 512, 16 * 256)
    assert moe["wo"].shape == (8, 2048, 2048) and moe["router"].shape == (8, 2048, 64)
    assert moe["w_gate"].shape == (8, 64, 2048, 1408) and moe["w_down"].shape == (8, 64, 1408, 2048)
    assert moe["shared_gate"].shape == (8, 2048, 2816) and moe["shared_down"].shape == (8, 2816, 2048)
    assert moe["router_bias"].shape == (8, 64) and str(moe["router_bias"].dtype) == "float32"
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))
    assert round(2 * count / 1e9, 2) == 10.87  # the issue's 5,432 M parameters in bf16, GB
    assert cfg.routing_width == 66 and cfg.mixers_per_layer == 1 and cfg.rope_dim == 64


@pytest.mark.parametrize("ffns,want", [
    (("moe", "moe", "moe"), ("moe", ())), (("dense",) * 3, ("dense", ())),
    (("dense", "moe", "moe"), ("dense", ("dense", "moe", "moe")))])
def test_layers_that_all_take_one_feed_forward_are_one_stack(ffns, want):
    cfg = dataclasses.replace(CONFIGS["tiny-moonlight"], ffn_kinds=ffns)
    assert (cfg.ffn_kind, cfg.ffn_kinds) == want and cfg.ffn_stacked == bool(want[1])
    tree = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
    assert ("wq" in tree["layers"]) == (not want[1])


@pytest.mark.parametrize("over,why", [
    ({"ffn_kinds": ("dense", "moe")}, "names 2 layers"),
    ({"ffn_kinds": ("dense", "moe", "scmoe")}, "a kind is"),
    ({"router_kind": "mlp"}, "takes the linear router"),
    ({"layer_kinds": ("mla", "mla", "ssm")}, "take the dense feed-forward"),
])
def test_a_feed_forward_pattern_the_loop_cannot_run_is_refused(over, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(CONFIGS["tiny-moonlight"], **over)


@pytest.mark.parametrize("name", ["tiny", "tiny-zaya", "tiny-jamba", "tiny-longcat", "llama3-8b"])
def test_a_model_of_the_other_kinds_keeps_its_tree_and_its_gate(name):
    cfg = CONFIGS[name]
    assert not cfg.ffn_stacked and cfg.ffns == (cfg.ffn_kind,) * cfg.n_layers
    assert cfg.gate_scoring == "softmax" and not cfg.norm_topk and cfg.n_shared_experts == 0
    assert cfg.mla_scale  # LongCat's two factors are the default the benchmark's register relies on
    assert cfg.routed == (name in ("tiny-zaya", "tiny-longcat"))
    if name != "llama3-8b":
        tree = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), cfg))
        assert "shared_gate" not in tree["layers"] and "dense" not in tree["layers"]


# -- (e) on the serving path: the device, the pool, chunked prefill -------------------

def _device(**env):
    return _longcat_device(**{"MODEL_NAME": "tiny-moonlight", **env})


@pytest.fixture(scope="module")
def device():
    dev = _device()
    yield dev
    dev.close()


def _greedy_by_the_model(prompt, n):
    """What the whole-sequence forward, which has no cache and no pool,
    puts first after the prompt and after each of its own tokens."""
    cfg = CONFIGS["tiny-moonlight"]
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


@pytest.mark.parametrize("kind", ["prefill", "decode_chunk", "prefill_chunk"])
def test_dispatch_records_count_the_pairs_the_shared_tokens_and_the_latent_read(device, kind):
    """Every one of a real token's top-k pairs lands on an expert held here
    in each of the two expert layers (the dense layer draws none): no
    identity pair, none absent; the shared expert takes every real token of
    an expert layer once; pads and slots without a request are in no count;
    the latent's bytes go by the rows' lengths over all three layers."""
    cfg = CONFIGS["tiny-moonlight"]
    token = 3 * (16 + 8) * 4  # 3 places, latent 16 + rope 8, float32
    assert device.decode_pool._latent_token_bytes == token
    short, long_, _ = _prompts()
    device.timeline._ring.clear()
    device.generate(long_ if kind == "prefill_chunk" else short, max_new_tokens=9)
    records = sorted((r for r in _finished_records(device) if r["kind"] == kind
                      and (r["batch_size"] or kind != "decode_chunk")), key=lambda r: r["dispatch_id"])
    assert records
    if kind == "prefill":
        tokens, latent = [9], [9 * token]  # 7 pads and a padding row: in no count
    elif kind == "decode_chunk":
        tokens = [r["batch_size"] * 4 for r in records]
        latent = [token * sum(9 + step + 1 for step in range(4))] + [None] * (len(records) - 1)
    else:
        tokens, latent = [32, 18], [32 * token, 50 * token]
        assert [r["carried"] for r in records] == [False, True]
    for r, n, nbytes in zip(records, tokens, latent):
        assert r["expert_tokens"] == cfg.top_k * n * 2 and r["shared_tokens"] == n * 2
        assert r["identity_tokens"] == 0 and r["absent_tokens"] == 0
        assert 0 < r["experts_read"] <= cfg.n_experts * 2 * (4 if kind == "decode_chunk" else 1)
        assert 0 < r["expert_tokens_max"] <= r["expert_tokens"]
        assert nbytes is None or r["latent_bytes"] == nbytes
        assert r["kv_blocks_read"] is None and r["state_bytes"] is None


@pytest.mark.parametrize("name,field", [("tiny-longcat", "identity_tokens"), ("tiny-zaya", "expert_tokens")])
def test_a_model_without_shared_experts_counts_no_shared_tokens(name, field):
    dev = _device(MODEL_NAME=name)
    try:
        dev.generate(_prompts()[0], max_new_tokens=3)
        records = [r for r in _finished_records(dev) if r["kind"] == "prefill"]
        assert records and all(r[field] is not None and r["shared_tokens"] is None for r in records)
    finally:
        dev.close()


# -- (f) what this tree and this cache cannot serve is refused at boot, by name -----------

@pytest.mark.parametrize("setting,value", [
    ("PREFIX_CACHE", "4"), ("KV_BLOCKS", "64"), ("DRAFT_MODEL_NAME", "tiny"),
    ("KV_TRANSFER", "on"), ("FLEET_ROLE", "prefill"), ("TPU_MESH", "tp=2"),
    ("MODEL_KV_DTYPE", "f8"),
])
def test_a_setting_that_rests_on_kv_rows_is_refused_for_a_latent_cache(setting, value):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*latent a token, not K/V"):
        _device(**{setting: value})


@pytest.mark.parametrize("setting,value", [("MODEL_QUANT", "int8"), ("LORA_ADAPTERS", "a=/nowhere")])
def test_what_takes_one_plain_stack_of_layers_is_refused_for_stacks_per_feed_forward(setting, value):
    with pytest.raises(ValueError, match=f"{setting} is not supported .*stacked per feed-forward "
                                         "kind .a dense layer before expert layers."):
        _device(**{setting: value})
