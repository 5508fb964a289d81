"""Golden-logits checkpoint fidelity.

A REAL HF checkpoint — a tiny random-weight ``LlamaForCausalLM`` written
by ``transformers.save_pretrained``, the actual ecosystem writer, NOT
this repo's own exporter (tests/test_ingest.py's round-trips are
circular by construction) — ingested through ``models/ingest.py`` must
teacher-force the same logits/logprobs the HF model computes with torch.
One test proves safetensors parsing, the weight mapping + transposes +
layer stacking, the RoPE split-half convention, GQA head grouping, RMS
norm semantics, and the SiLU MLP all agree with the HF ecosystem end to
end. A second proves the tokenizer against the ``tokenizers`` library on
a real tokenizer.json.

No network: the checkpoint and tokenizer are BUILT locally by the HF
libraries baked into the image — real formats, real writers, no
downloads.
"""

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

# XLA-compile-dominated module: deselect with -m 'not slow'
pytestmark = pytest.mark.slow

PROMPT = [1, 5, 9, 33, 77, 2, 64, 100, 42, 7]


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    """(checkpoint dir, HF logits [S, V] f32): a random HF Llama shaped
    EXACTLY like this repo's registered ``tiny`` config, so the serving
    device can load it by MODEL_NAME=tiny + MODEL_PATH."""
    from transformers import LlamaConfig, LlamaForCausalLM

    from gofr_tpu.models.llama import TINY

    hf_cfg = LlamaConfig(
        vocab_size=TINY.vocab_size, hidden_size=TINY.dim,
        intermediate_size=TINY.hidden_dim,
        num_hidden_layers=TINY.n_layers,
        num_attention_heads=TINY.n_heads,
        num_key_value_heads=TINY.n_kv_heads,
        max_position_embeddings=TINY.max_seq, rope_theta=TINY.rope_theta,
        rms_norm_eps=TINY.norm_eps, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg).eval()
    path = tmp_path_factory.mktemp("hf_ckpt")
    model.save_pretrained(str(path), safe_serialization=True)
    with torch.no_grad():
        logits = model(torch.tensor([PROMPT])).logits[0].float().numpy()
    return str(path), logits


def _gofr_cfg():
    from gofr_tpu.models.llama import TINY

    return TINY


def test_hf_checkpoint_golden_logits(hf_checkpoint):
    import jax.numpy as jnp

    from gofr_tpu.models.ingest import load_llama_params
    from gofr_tpu.models.transformer import transformer_forward

    path, hf_logits = hf_checkpoint
    cfg = _gofr_cfg()
    params = load_llama_params(path, cfg)
    ours = np.asarray(
        transformer_forward(params, jnp.asarray([PROMPT], jnp.int32), cfg)
    )[0]
    # absolute logits agree to f32 numerics (conftest pins highest matmul
    # precision); any convention mismatch — rope layout, norm order, GQA
    # grouping, transpose — diverges by O(1), not O(1e-3)
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-3, atol=2e-3)


def test_hf_checkpoint_golden_teacher_forced_logprobs(hf_checkpoint):
    """The serving-surface form of the same proof: device.score() (the
    completions echo+logprobs primitive) must reproduce HF's
    log p(t_i | t_<i) on the real checkpoint."""
    import os

    import torch.nn.functional as F

    path, hf_logits = hf_checkpoint
    want = F.log_softmax(torch.tensor(hf_logits), dim=-1).numpy()
    golden = [float(want[i - 1, PROMPT[i]]) for i in range(1, len(PROMPT))]

    from gofr_tpu.testutil import serving_device

    ckpt_file = os.path.join(path, "model.safetensors")
    with serving_device(MODEL_NAME="tiny", MODEL_PATH=ckpt_file) as dev:
        got = dev.score(PROMPT)
    np.testing.assert_allclose(got, golden, rtol=2e-3, atol=2e-3)


def test_tokenizer_matches_hf_tokenizers_library(tmp_path):
    """gofr's from_hf_json must encode EXACTLY like the ``tokenizers``
    library on a real byte-level-BPE tokenizer.json built BY that
    library (trained in-process on a tiny corpus — a real artifact, not
    a hand-written fixture)."""
    tokenizers = pytest.importorskip("tokenizers")

    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=300, special_tokens=["<s>", "</s>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    corpus = [
        "the quick brown fox jumps over the lazy dog",
        "hello world, hello TPU serving",
        "pack my box with five dozen liquor jugs",
    ]
    tok.train_from_iterator(corpus, trainer)
    path = str(tmp_path / "tokenizer.json")
    tok.save(path)

    from gofr_tpu.tokenizer import Tokenizer as GofrTokenizer

    ours = GofrTokenizer.from_hf_json(path)
    for text in corpus + ["unseen zebra text!", "  spaces  and\ttabs"]:
        want = tok.encode(text).ids
        got = ours.encode(text)
        assert got == want, (text, got, want)
        assert ours.decode(got) == tok.decode(want, skip_special_tokens=False)
