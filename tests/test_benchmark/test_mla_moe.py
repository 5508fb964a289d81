"""The ``mla_moe`` architecture (Moonlight-16B-A3B's first pipeline stage) in
the harness: the contract, the configuration against the catalog's row and the
issue's arithmetic, the cell as the issue has it, a tiny configuration added
to a copy of the rehearsal data served and checked by its own plain reference
(and called not correct by a reference short of a term, and by another
architecture's), the parent's program refusing the architecture cleanly, the
work sheets against hand counts, each new reader on a small fixture. Its
cell's metric lists are written out here, as ``test_manifest_floors.py`` asks
of a new cell. No chip."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import spec

MANIFEST = spec.load_manifest()
REHEARSE_DIR = os.path.join(spec.HERE, "fixtures", "rehearse")
SEED = 2147483700  # more than 32 signed bits hold
CONFIG = "moonlight-16b-a3b-bf16"
CELL = "moonlight-16b-a3b-bf16.reasoning-steady"
SOURCE = "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"
ARCH = spec.load_module("architectures", "mla_moe")

TINY_CFG = {
    "source": "tests", "architecture": "mla_moe", "attention_bias": False, "vocab_size": 256,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": None, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "routed_scaling_factor": 2.5, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "max_position_embeddings": 128,
    "rms_norm_eps": 1e-5, "rope_theta": 50000.0, "tie_word_embeddings": False,
    "serving": {"quant": "", "dtype": "float32",
                "env": {"MODEL_MAX_SEQ": "128", "MODEL_BUCKETS": "16,32", "BATCH_MAX_SIZE": "2",
                        "DECODE_SLOTS": "4", "MODEL_ATTN_IMPL": "xla", "BATCH_TIMEOUT_MS": "5"}},
}

WRONG = '''"""mla_moe's weights and seam, checked by a reference without the shared expert."""
import jax.numpy as jnp
from benchmark import spec

_own = spec.load_module("architectures", "mla_moe")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register
_shared = _own.shared_expert


def logits_at(*args, **kw):
    _own.shared_expert = lambda a, w: jnp.zeros_like(a)  # the reference's alone
    try:
        yield from _own.logits_at(*args, **kw)
    finally:
        _own.shared_expert = _shared
'''

# another architecture's reference over this model's serving: the dense GQA
# decoder knows no latent and no expert
OTHER = '''"""mla_moe's weights and seam, checked by the dense decoder's reference."""
from benchmark import spec

_own = spec.load_module("architectures", "mla_moe")
_dense = spec.load_module("architectures", "dense_gqa")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register


def logits_at(seed, cfg, blocks, mode=None):
    dense = dict(cfg, num_key_value_heads=1,
                 head_dim=cfg["hidden_size"] // cfg["num_attention_heads"])
    return _dense.logits_at(seed, dense, blocks, mode)
'''


# -- the contract and the configuration -----------------------------------------------------------

def test_the_module_keeps_the_architecture_contract_and_ids_of_its_own():
    cfg = spec.load_config(MANIFEST, CONFIG)
    assert spec.load_architecture(MANIFEST, cfg).__name__ == ARCH.__name__
    taken = set()
    for name in ("dense_gqa", "power_retention", "cca_moe", "hybrid_ssm", "mla_scmoe"):
        module = spec.load_module("architectures", name)
        taken |= {i + k for i in module.LEAF_IDS.values() for k in range(32)}
        taken |= {base + k for base in getattr(module, "EXPERT_IDS", {}).values()
                  for k in range(1024)}
    own = list(ARCH.LEAF_IDS.values())
    experts = {base + k for base in ARCH.EXPERT_IDS.values() for k in range(1024)}
    assert len(own) == len(set(own)) and not set(own) & taken and not experts & (taken | set(own))
    assert len(experts) == 3 * 1024  # no two experts share an id
    source = open(os.path.join(spec.HERE, "architectures", "mla_moe.py")).read()
    # only the seam imports the program; the reference shares no code with it
    assert "import gofr_tpu" not in source.split("def register")[0]
    assert "gofr_tpu" not in source.split("# -- the plain reference")[1]
    assert "ops.mla" not in source and "ops.experts" not in source
    assert 'default_matmul_precision("highest")' in source


# -- served and checked in the harness ---------------------------------------------------------

@pytest.fixture(scope="module")
def with_moonlight(tmp_path_factory):
    """The rehearsal data with a tiny ``mla_moe`` configuration and a cell
    ADDED, one whose reference drops the shared expert and one checked by
    the dense decoder's reference."""
    data = tmp_path_factory.mktemp("data") / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    manifest = spec.load_json(str(data / "BENCHMARK.json"))
    os.makedirs(data / "architectures")
    (data / "architectures" / "mla_moe_wrong.py").write_text(WRONG)
    (data / "architectures" / "mla_moe_other.py").write_text(OTHER)
    for name, arch in (("tiny-moonlight", "mla_moe"), ("tiny-moonlight-wrong", "mla_moe_wrong"),
                       ("tiny-moonlight-other", "mla_moe_other")):
        (data / f"{name}.json").write_text(json.dumps(dict(TINY_CFG, architecture=arch)))
        shutil.copy(data / "cells" / "tiny.open.json", data / "cells" / f"{name}.open.json")
        manifest["configs"].append({"name": name, "source": "tests", "file": f"{name}.json",
                                    "reduced": [], "why": "Moonlight's block at a test shape"})
        manifest["workloads"].append({"name": f"{name}.open", "config": name,
                                      "traffic": "rehearse-open", "chips": 1, "why": "as tiny.open"})
        manifest["end_to_end"][0]["workloads"].append(f"{name}.open")
    (data / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(data / "BENCHMARK.json")


def _rehearse(manifest, workload):
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--rehearse", manifest, "--workload", workload,
         "--seed", str(SEED), "--seconds", "3", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_tiny_configuration_is_served_and_correct(with_moonlight):
    """Batched prefill in padded buckets, one prompt above the top bucket
    (chunked over a carried latent), the pool with rows of unequal length
    and slots without a request, the solo fallback: every served token is
    the reference's best."""
    result = _rehearse(with_moonlight, "tiny-moonlight.open")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert result["check"][0]["agree_share"] == 1.0


@pytest.mark.parametrize("workload", ["tiny-moonlight-wrong.open", "tiny-moonlight-other.open"])
def test_a_reference_short_of_a_term_or_of_another_architecture_is_not_correct(with_moonlight, workload):
    result = _rehearse(with_moonlight, workload)
    assert result["correct"] is False and result["failed"] == 0


def test_the_parents_program_refuses_the_architecture_cleanly(monkeypatch):
    """A program with no feed-forward kind a layer and no sigmoid gate cannot
    serve it: ``register`` says so (``run.py`` exits 3) before anything is
    built."""
    import gofr_tpu.models.transformer as T

    fields = {k: v for k, v in T.TransformerConfig.__dataclass_fields__.items()
              if k not in ("ffn_kinds", "gate_scoring", "n_shared_experts", "mla_scale")}
    monkeypatch.setattr(T.TransformerConfig, "__dataclass_fields__", fields)
    cfg = spec.load_config(MANIFEST, CONFIG)
    run = types.SimpleNamespace(cfg=cfg, sizes=ARCH.sizes_of(cfg), seed=1, log=print,
                                server_env={})
    with pytest.raises(spec.SpecError, match="no feed-forward kind a layer"):
        ARCH.register(run)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 32), ("scoring_func", "softmax"), ("norm_topk_prob", False), ("n_group", 2),
    ("topk_group", 2), ("attention_bias", True), ("tie_word_embeddings", True),
    ("moe_layer_freq", 2), ("ep_size", 8), ("published", {"n_routed_experts": 64}),
    ("first_k_dense_replace", 3), ("num_experts_per_tok", 9)])
def test_a_configuration_the_module_is_not_written_for_is_refused(key, value):
    with pytest.raises(spec.SpecError):
        ARCH.sizes_of(dict(TINY_CFG, **{key: value}))


def test_the_configuration_keeps_every_number_of_the_catalogs_row_but_the_two_it_cuts():
    cfg = spec.load_config(MANIFEST, CONFIG)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
        "max_position_embeddings": 8192, "model_type": "deepseek_v3",
        "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 6, "num_hidden_layers": 27, "num_key_value_heads": 16,
        "num_nextn_predict_layers": 0, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 163840,
    }
    cut = {"num_hidden_layers", "max_position_embeddings"}
    assert {k for k, v in published.items() if cfg.get(k, "absent") != v} == cut
    assert set(cfg["reduced"]) == cut
    assert cfg["max_position_embeddings"] == 2048 and cfg["num_hidden_layers"] in (9, 7, 5)
    assert {k: cfg["published"][k] for k in cut} == {k: published[k] for k in cut}
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == cut and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] == SOURCE and len(SOURCE) < 200
    assert "pipeline" in cfg["deployment"]["stated"] and "head" in cfg["deployment"]["stated"]
    for item in ("layer", "mla", "rotary", "router", "shared_experts", "expert",
                 "tie_word_embeddings", "weights", "tokenizer"):
        assert cfg["assumed"][item]
    assert "interleaved" in cfg["assumed"]["rotary"] and "seeded 0" in cfg["assumed"]["router"]
    assert "NO group limit" in cfg["assumed"]["router"] and "1 / sqrt(fan-in)" in cfg["assumed"]["weights"]
    env = cfg["serving"]["env"]
    assert {k: env[k] for k in ("MODEL_MAX_SEQ", "MODEL_BUCKETS", "BATCH_MAX_SIZE",
                                "DECODE_CHUNK")} == {
        "MODEL_MAX_SEQ": "2048", "MODEL_BUCKETS": "256", "BATCH_MAX_SIZE": "2", "DECODE_CHUNK": "8"}
    assert int(env["DECODE_SLOTS"]) <= 48
    sz = ARCH.sizes_of(cfg)
    assert (sz["experts"], sz["shared"], sz["top_k"], sz["dense_layers"]) == (64, 2, 6, 1)
    assert sz["scale"] == 2.446 and sz["layers"] - sz["dense_layers"] >= 4  # the guide's floor


def test_the_parameters_and_the_memory_are_the_issues_arithmetic():
    cfg = dict(spec.load_config(MANIFEST, CONFIG), num_hidden_layers=9)  # the issue's 1 + 8
    sz = ARCH.sizes_of(cfg)
    sheet = spec.load_module("kernels", "mla_moe_decode_step")
    experts = spec.load_module("kernels", "moe_experts")
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert sheet.attention_params(sz) == mla and round(mla / 1e6, 2) == 13.76
    assert sheet.shared_params(sz) == 3 * 2048 * 2816 and round(sheet.shared_params(sz) / 1e6, 2) == 17.3
    outside = mla + 3 * 2048 * 2816 + 2048 * 64
    assert round(outside / 1e6, 1) == 31.2
    assert experts.expert_bytes(sz) == 2 * 3 * 2048 * 1408 and round(experts.expert_bytes(sz) / 1e6, 1) == 17.3
    layer = outside + 64 * 3 * 2048 * 1408
    assert round(layer / 1e6, 1) == 584.8 and round(2 * layer / 1e9, 3) == 1.170
    dense = mla + 3 * 2048 * 11264
    assert round(dense / 1e6, 1) == 83.0
    matmul, other = sheet.stack_params(sz)
    assert matmul == dense + 8 * outside
    for held, gb in ((8, 10.87), (7, 9.70), (6, 8.53)):
        total = 2 * 163840 * 2048 + dense + held * layer
        assert round(2 * total / 1e9, 2) == gb
    assert "5.43e9" in cfg["parameters"] and "10.87 GB" in cfg["parameters"]
    assert sheet.latent_token_bytes(sz) == 9 * 1152 == 10368
    slot = 10368 * 2048
    assert round(slot / 1e6, 1) == 21.2 and round(48 * slot / 1e9, 2) == 1.02
    # what an imported program makes of the same sizes
    import dataclasses

    import jax

    from gofr_tpu.models import transformer as T
    from gofr_tpu.models.llama import CONFIGS

    program = dataclasses.replace(CONFIGS["moonlight-16b-a3b-9l"], max_seq=2048)
    cache = jax.eval_shape(lambda: T.init_cache(program, 48, 2048))
    assert T.latent_token_bytes(cache) == sheet.latent_token_bytes(sz)
    made = jax.eval_shape(lambda: ARCH.make_params(1, sz))
    own = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), program))
    shapes = lambda tree: jax.tree.map(lambda x: (x.shape, str(x.dtype)), tree)  # noqa: E731
    assert shapes(made) == shapes(own)  # the seeded tree is the program's, leaf for leaf


def test_the_cell_is_the_issues_under_the_accepted_mix():
    mix = spec.load_mix(MANIFEST, "reasoning-steady")
    cfg = spec.load_config(MANIFEST, CONFIG)
    top = int(cfg["serving"]["env"]["MODEL_BUCKETS"].split(",")[-1])
    from benchmark.traffic import quantile_lengths

    lengths = quantile_lengths(mix["prompt_tokens"], 64)
    assert 0.1 <= sum(n > top for n in lengths) / 64 <= 0.3  # a fifth are chunked
    assert -(-max(lengths) // top) == 2  # two slices at most
    load = spec.load_cell_load(MANIFEST, CELL)
    sweep = load["sweep"]
    good = [rate for rate, met, failed in zip(sweep["rates_rps"], sweep["met_share"], sweep["failed"])
            if met >= mix["limits"]["attainment"] and not failed]
    assert load["knee_rps"] == max(good)
    share = load["rate_rps"] / load["knee_rps"]
    assert share == pytest.approx(0.8, abs=0.01) or (
        (share == pytest.approx(0.7, abs=0.01) or share == pytest.approx(0.6, abs=0.01))
        and "fallback" in sweep)
    check = dict(mix["check"], **load["check"])
    assert max(check["widths"]) >= mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert max(check["widths"]) <= cfg["max_position_embeddings"]
    assert check["scored"] >= check["rows"] * mix["output_tokens"]["max"]
    assert set(load["check_reasons"]) >= {"served_gap_mean_limit", "served_gap_max_limit"}
    cell = spec.find_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "reasoning-steady"
    assert len(cell["why"]) <= 200
    assert all(c["chips"] == 1 for c in MANIFEST["workloads"])


NEW = ["kernel.mla_moe.decode_step_roofline", "kernel.mla_moe.decode_step_mfu",
       "kernel.mla_moe.prefill_step_roofline", "kernel.mla_moe.prefill_step_mfu",
       "moe.pairs_read_share", "moe.pair_load_max_share", "mla_moe.latent_read_share"]
LAYERS = {"moe.pairs_read_share": "experts", "moe.pair_load_max_share": "experts",
          "mla_moe.latent_read_share": "latent cache"}


def test_the_cell_reports_the_steady_metrics_and_its_new_readers_wait_for_their_entries():
    """The manifest's last seven per-layer entries are pinned by
    ``test_transport_clock.py`` (PR 38), so this PR cannot append an entry
    and edits no file that is there: the readers and their entries, as a
    ``benchmark`` PR will append them, are in the cell's own file."""
    from tests.test_benchmark.test_manifest_floors import DENSE, OPEN_LOOP, RETENTION, STEADY

    names = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "per_layer")]
    assert [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "end_to_end")] == OPEN_LOOP
    assert sorted(names) == sorted(STEADY + ["state.insert_p50_ms"])  # and nothing else
    assert not set(names) & set(DENSE) and not set(names) & (set(RETENTION) - {"state.insert_p50_ms"})
    assert not any(n.startswith(("kernel.", "ssm.", "moe.", "sse.", "http.")) for n in names)
    waiting = spec.load_cell_load(MANIFEST, CELL)["waiting_per_layer"]
    assert "test_transport_clock.py" in waiting["why"]
    assert [decl["name"] for decl in waiting["entries"]] == NEW
    declared = {m["name"] for m in MANIFEST["per_layer"]}
    for decl in waiting["entries"]:
        assert set(decl) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert decl["workloads"] == [CELL] and decl["unit"] == "%"
        assert decl["layer"] == LAYERS.get(decl["name"], "kernels")
        assert decl["moves"] == ("ttft_mean_ms" if "prefill" in decl["name"] else "tpot_mean_ms")
        assert decl["better"] == ("lower" if decl["name"] == "moe.pair_load_max_share" else "higher")
        assert decl["source"] == ("device_trace" if decl["name"].startswith("kernel.")
                                  else "program_counter")
        assert callable(spec.load_module("layer_metrics", decl["name"]).read)
        assert decl["name"] not in declared  # the PR that appends an entry takes it off this list


# -- the work sheets against hand counts at the tiny shape ----------------------------------------

TINY = ARCH.sizes_of(TINY_CFG)
MLA = 64 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 64 * 64
SHARED = 3 * 64 * 64
STACK = 3 * MLA + 3 * 64 * 96 + 2 * (64 * 8 + SHARED)
OTHER_W = 3 * (2 * 64 + 16) + 2 * 2 * 8
HEAD = 256 * 64
EXPERT = 3 * 64 * 32
TOKEN = 3 * (16 + 8) * 2  # three layers, latent and rope, bf16


def _run(**env):
    """Two chunks of 4 steps (1 and 3 live rows), a prefill of two rows and
    a slice of one that carries 32 positions; every pair lands."""
    chunk = {"kind": "decode_chunk", "status": "ok", "identity_tokens": 0, "absent_tokens": 0}
    return types.SimpleNamespace(
        sizes=TINY, server_env={"DECODE_CHUNK": "4", "DECODE_SLOTS": "6", **env}, w0=0.0, w1=10.0,
        records=[], dispatches=[
            dict(chunk, batch_size=1, experts_read=20, expert_tokens=24, expert_tokens_max=8,
                 shared_tokens=8, latent_bytes=TOKEN * 46),
            dict(chunk, batch_size=3, experts_read=44, expert_tokens=72, expert_tokens_max=16,
                 shared_tokens=24, latent_bytes=TOKEN * 300),
            {"kind": "prefill", "status": "ok", "batch_size": 2, "bucket": 16, "padded_tokens": 22,
             "tokens": 10, "experts_read": 14, "expert_tokens": 60, "latent_bytes": TOKEN * 10},
            {"kind": "prefill_chunk", "status": "ok", "batch_size": 1, "bucket": 32,
             "padded_tokens": 0, "tokens": 30, "experts_read": 16, "expert_tokens": 180,
             "latent_bytes": TOKEN * 62}])


def test_the_decode_sheet_counts_what_a_step_must_move():
    sheet = spec.load_module("kernels", "mla_moe_decode_step")
    assert sheet.attention_params(TINY) == MLA and sheet.shared_params(TINY) == SHARED
    assert sheet.stack_params(TINY) == (STACK, OTHER_W)
    weights = 2 * (STACK + OTHER_W + HEAD)
    assert sheet.weight_bytes(TINY) == weights and sheet.latent_token_bytes(TINY) == TOKEN
    flops, parts = sheet.step_parts(_run())
    # two chunks of four steps: 2 live rows, 64 experts read, 96 pairs, 346 positions
    assert parts["experts"] == pytest.approx(2 * EXPERT * 64 / 2 / 4)
    assert parts["latent"] == pytest.approx(TOKEN * 346 / 2 / 4)
    assert parts["shared"] == 2 * 2 * SHARED and parts["head"] == 2 * HEAD
    assert parts["shared"] + parts["head"] + parts["rest"] == weights
    per_position = (2 * 24 + 2 * 16) * 4 * 3  # score and sum, 4 heads, 3 layers
    assert flops == pytest.approx(2 * 2 * (STACK + HEAD) + 2 * EXPERT * 96 / 2 / 4
                                  + per_position * 346 / 2 / 4)
    moved = sum(parts.values())
    assert sheet.work(_run(), 3) == (pytest.approx(12 * flops), pytest.approx(12 * moved))
    # a row that is not live owes nothing: no chunk, no work beyond the weights
    idle = _run()
    idle.dispatches = idle.dispatches[2:]
    assert sheet.work(idle, 1) == (0.0, pytest.approx(4 * weights))


def test_the_prefill_sheet_counts_real_tokens_and_what_a_slice_reaches():
    sheet = spec.load_module("kernels", "mla_moe_prefill_step")
    decode = spec.load_module("kernels", "mla_moe_decode_step")
    run = _run()
    pair = 2 * (16 + 8 + 16) * 4 * 3
    whole = 2 * STACK * 10 + 2 * HEAD * 2 + pair * 2 * (5 * 5 / 2)
    piece = 2 * STACK * 30 + 2 * HEAD + pair * (30 * 32 + 30 * 30 / 2)
    flops, nbytes = sheet.work(run, 4)
    assert flops == pytest.approx(4 * ((whole + piece) / 2 + 2 * EXPERT * 240 / 2))
    assert nbytes == pytest.approx(4 * (decode.weight_bytes(TINY) + 2 * EXPERT * 30 / 2 + TOKEN * 72 / 2))
    empty = _run()
    empty.dispatches = empty.dispatches[:2]
    assert sheet.work(empty, 4) == (0.0, 0.0)


# -- the readers on a small fixture ----------------------------------------------------------------

PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8, "hbm_bytes": 16e9}


def _traced(pooled_s=4.0, prefill_s=0.5):
    run = _run()
    run.peaks, run.seconds = PEAKS, 10.0
    run.trace = {"device_ops": [], "programs": {
        "jit__lambda(7)": {"seconds": pooled_s, "runs": 2},
        "jit__prefill_fn(3)": {"seconds": prefill_s, "runs": 2}},
        "released": {"gofr.pool.fetch_wait": {"jit__lambda(7)": 5}}}
    return run


def _read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_the_program_readers_are_the_sheets_over_the_traced_time():
    run = _traced()
    flops, nbytes = spec.load_module("kernels", "mla_moe_decode_step").work(run, 2)
    assert _read("kernel.mla_moe.decode_step_mfu", run) == pytest.approx(100 * flops / (1e9 * 4.0))
    assert _read("kernel.mla_moe.decode_step_roofline", run) == pytest.approx(
        100 * max(flops / 1e9, nbytes / 1e8) / 4.0)
    pflops, pbytes = spec.load_module("kernels", "mla_moe_prefill_step").work(run, 2)
    assert _read("kernel.mla_moe.prefill_step_mfu", run) == pytest.approx(100 * pflops / (1e9 * 0.5))
    assert _read("kernel.mla_moe.prefill_step_roofline", run) == pytest.approx(
        100 * max(pflops / 1e9, pbytes / 1e8) / 0.5)


def test_the_counter_readers_say_where_the_pairs_and_the_bytes_went():
    run = _run()
    # 64 experts read of 8 experts x 2 expert layers x 8 steps; the dense layer holds none
    assert _read("moe.pairs_read_share", run) == pytest.approx(100 * 64 / (8 * 2 * 8))
    assert _read("moe.pair_load_max_share", run) == pytest.approx(100 * 24 / 96)
    latent, held = TOKEN * 346, 2 * EXPERT * 64
    weights = spec.load_module("kernels", "mla_moe_decode_step").weight_bytes(TINY)
    assert _read("mla_moe.latent_read_share", run) == pytest.approx(
        100 * latent / (latent + held + 8 * weights))


def test_a_program_without_the_counters_or_a_run_without_a_trace_reads_nothing():
    """As on the parent commit, whose records carry no pair counts for this
    model (it cannot serve it): nothing is raised."""
    bare = _traced()
    for d in bare.dispatches:
        for key in ("latent_bytes", "experts_read", "expert_tokens", "expert_tokens_max"):
            d.pop(key, None)
    for name in ("moe.pairs_read_share", "moe.pair_load_max_share", "mla_moe.latent_read_share"):
        assert _read(name, bare) is None
    untraced = _run()
    untraced.trace = untraced.peaks = None
    for name in NEW:
        if name.startswith("kernel."):
            assert _read(name, untraced) is None
