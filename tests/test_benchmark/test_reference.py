"""The plain reference against models/transformer.py on the CPU at test
sizes: prefill, then greedy decode through the cache; the same comparison
failing in a lower precision (the KV cache in f8; the weights one step
below the precision the configuration states)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, spec
from gofr_tpu.models import transformer as T

A = spec.load_module("architectures", "dense_gqa")  # the architecture both configurations name

SEED = 11


def sizes_cfg(quant, dtype, dim=64, layers=2, heads=4, kv_heads=2, ffn=128, vocab=256):
    return {
        "hidden_size": dim, "num_hidden_layers": layers, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "head_dim": dim // heads, "intermediate_size": ffn,
        "vocab_size": vocab, "max_position_embeddings": 128, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "architecture": "dense_gqa", "serving": {"quant": quant, "dtype": dtype},
    }


def program_cfg(cfg, **over):
    sz = A.sizes_of(cfg)
    return T.TransformerConfig(
        vocab_size=sz["vocab"], dim=sz["dim"], n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], hidden_dim=sz["ffn"], max_seq=128,
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(sz["dtype"]), attn_impl="xla", **over)


def serve(cfg, pcfg, prompts, n_out):
    """The program: bucketed prefill of a ragged batch, then greedy decode
    through the cache. -> [(prompt, served tokens)]"""
    params = A.make_params(SEED, A.sizes_of(cfg))
    width = max(len(p) for p in prompts)
    tokens = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, : len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    cache = T.init_cache(pcfg, len(prompts))
    logits, cache = T.prefill(params, jnp.asarray(tokens), cache, pcfg, lengths)
    out = []
    for _ in range(n_out):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(nxt))
        logits, cache = T.decode_step(params, nxt[:, None], cache, pcfg)
    served = np.stack(out, axis=1)
    return [(list(p), served[i].tolist()) for i, p in enumerate(prompts)]


def prompts_for(vocab, lens):
    rng = np.random.default_rng(3)
    return [rng.integers(3, vocab, n).tolist() for n in lens]


@pytest.fixture(scope="module")
def tiny():
    cfg = spec.load_json(spec.HERE + "/fixtures/rehearse/tiny-rehearse.json")
    return cfg, prompts_for(256, (9, 23, 40))


def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(tiny):
    cfg, prompts = tiny
    pairs = serve(cfg, program_cfg(cfg), prompts, 24)
    got = reference.served_gaps(A.logits_at, SEED, cfg, pairs, widths=[64, 128], rows=2, scored=48)
    # three samples in blocks of two rows and two widths: every served token is scored
    assert got["gaps"].shape == (72,) and sorted(set(got["sample"])) == [0, 1, 2]
    assert got["gaps"].max() <= 1e-3 and got["agree"] == 1.0


def test_the_same_check_fails_with_the_cache_in_f8(tiny):
    cfg, prompts = tiny
    f8 = program_cfg(cfg, kv_dtype=jnp.float8_e4m3fn)
    pairs = serve(cfg, f8, prompts, 24)
    got = reference.served_gaps(A.logits_at, SEED, cfg, pairs, widths=[64, 128], rows=2, scored=48)
    assert got["gaps"].max() > 1e-3 and got["agree"] < 1.0


def test_an_altered_token_shows_as_a_gap(tiny):
    cfg, prompts = tiny
    pairs = serve(cfg, program_cfg(cfg), prompts, 8)
    pairs[1][1][3] = (pairs[1][1][3] + 1) % 256
    got = reference.served_gaps(A.logits_at, SEED, cfg, pairs, widths=[64, 128], rows=2, scored=16)
    assert got["gaps"].max() > 1e-3 and got["sample"][int(got["gaps"].argmax())] == 1


def test_whole_model_and_per_layer_weights_are_the_same_bits():
    cfg = sizes_cfg("int8", "bfloat16")
    sz = A.sizes_of(cfg)
    whole = A.make_params(SEED, sz)
    seed = jnp.uint32(SEED)
    one = jax.jit(lambda s, i: A.layer_values(s, i, sz))(seed, jnp.int32(1))
    for name, leaf in one.items():
        stacked = whole["layers"][name]
        if isinstance(leaf, dict):
            assert leaf["q"].dtype == jnp.int8 and leaf["scale"].shape[0] == 1
            np.testing.assert_array_equal(leaf["q"], stacked["q"][1])
            np.testing.assert_array_equal(leaf["scale"], stacked["scale"][1])
        else:
            np.testing.assert_array_equal(leaf, stacked[1])
    q = np.asarray(whole["layers"]["w_up"]["q"], np.float32)
    assert abs(q.mean()) < 1.0 and 30 < q.std() < 44 and np.abs(q).max() <= 127
    other = A.make_params(SEED + 1, sz)
    assert not np.array_equal(other["lm_head"]["q"], whole["lm_head"]["q"])


# the controls, at a size a test run can hold (PERF.md has the readings at the
# cells' own sizes). Under int8 weights: the reference itself with int4
# weights, read at every position of the same prompts and tokens. Under bf16:
# the program's own lower-precision path, the KV cache in fp8 (the reference
# with int8 weights reads under three times the bf16 program's own rounding,
# on the chip as here, so no limit holds on it alone).
def _small(quant):
    return sizes_cfg(quant, "bfloat16", dim=256, layers=2, heads=4, kv_heads=2, ffn=512, vocab=2048)


def test_int4_weights_fail_where_the_int8_program_passes():
    cfg = _small("int8")
    pairs = serve(cfg, program_cfg(cfg), prompts_for(2048, (12, 30, 50, 64)), 32)
    got = reference.served_gaps(A.logits_at, SEED, cfg, pairs, widths=[64, 128], rows=2, scored=64, control="int4")
    assert got["control_gaps"].mean() > 3 * got["gaps"].mean()
    assert got["control_gaps"].max() > 3 * got["gaps"].max()


def test_an_fp8_cache_fails_where_the_bf16_program_passes():
    cfg = _small("")
    prompts = prompts_for(2048, (12, 30, 50, 64))
    sound = reference.served_gaps(A.logits_at, SEED, cfg, serve(cfg, program_cfg(cfg), prompts, 32),
                                  widths=[64, 128], rows=2, scored=64, control="int8")
    f8 = program_cfg(cfg, kv_dtype=jnp.float8_e4m3fn)
    low = reference.served_gaps(A.logits_at, SEED, cfg, serve(cfg, f8, prompts, 32),
                                widths=[64, 128], rows=2, scored=64)
    assert low["gaps"].mean() > 3 * sound["gaps"].mean()
    assert sound["control_gaps"].mean() > sound["gaps"].mean()  # int8 weights: worse, not 3x


def test_degrade_weight_modes():
    w = jnp.asarray(np.random.default_rng(0).standard_normal((256, 8)), jnp.float32)
    assert reference.degrade_weight(w, None) is w
    for mode, worst in (("bf16", 0.02), ("int8", 0.02), ("int4", 0.3)):
        err = float(jnp.max(jnp.abs(reference.degrade_weight(w, mode) - w)))
        assert 0 < err < worst * float(jnp.max(jnp.abs(w)))
    with pytest.raises(ValueError):
        reference.degrade_weight(w, "fp2")
