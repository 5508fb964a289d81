"""The ``mla_scmoe`` architecture (LongCat-Flash-Chat as one chip of ep = 32)
in the harness: the contract, the configuration against the catalog's row and
the issue's arithmetic, the mix and the cell as the issue has them, a tiny
configuration added to a copy of the rehearsal data served and checked by its
own plain reference (and called not correct by a reference short of a term,
and by another architecture's), the parent's program refusing the
architecture cleanly, the work sheets against hand counts, each new reader on
a small fixture. Its cell's metric lists are written out here, as
``test_manifest_floors.py`` asks of a new cell. No chip."""

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
CONFIG = "longcat-flash-ep32-bf16"
CELL = "longcat-flash-ep32-bf16.agent-steady"
ARCH = spec.load_module("architectures", "mla_scmoe")

TINY_CFG = {
    "source": "tests", "architecture": "mla_scmoe", "attention_bias": False, "vocab_size": 256,
    "hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6, "n_routed_experts": 2,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "attention_method": "MLA", "zero_expert_num": 4, "zero_expert_type": "identity",
    "moe_topk": 3, "published": {"n_routed_experts": 8}, "deployment": {"ep": 4, "ep_rank": 0},
    "serving": {"quant": "", "dtype": "float32",
                "env": {"MODEL_MAX_SEQ": "128", "MODEL_BUCKETS": "16,32", "BATCH_MAX_SIZE": "2",
                        "DECODE_SLOTS": "4", "MODEL_ATTN_IMPL": "xla", "BATCH_TIMEOUT_MS": "5"}},
}

WRONG = '''"""mla_scmoe's weights and seam, checked by a reference without the identity experts."""
import jax.numpy as jnp
from benchmark import spec

_own = spec.load_module("architectures", "mla_scmoe")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register
_held = _own.held_weights


def _without_identity(choice, weight, sz):
    per, own = _held(choice, weight, sz)
    return per, jnp.zeros_like(own)


def logits_at(*args, **kw):
    _own.held_weights = _without_identity  # the reference's alone: the program serves them
    try:
        yield from _own.logits_at(*args, **kw)
    finally:
        _own.held_weights = _held
'''

# another architecture's reference over this model's serving: the dense GQA
# decoder knows no latent and no expert
OTHER = '''"""mla_scmoe's weights and seam, checked by the dense decoder's reference."""
from benchmark import spec

_own = spec.load_module("architectures", "mla_scmoe")
_dense = spec.load_module("architectures", "dense_gqa")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register


def logits_at(seed, cfg, blocks, mode=None):
    dense = dict(cfg, num_hidden_layers=cfg["num_layers"], num_key_value_heads=1,
                 intermediate_size=cfg["ffn_hidden_size"],
                 head_dim=cfg["hidden_size"] // cfg["num_attention_heads"])
    return _dense.logits_at(seed, dense, blocks, mode)
'''


# -- the contract and the configuration -----------------------------------------------------------

def test_the_module_keeps_the_architecture_contract_and_ids_of_its_own():
    cfg = spec.load_config(MANIFEST, CONFIG)
    assert spec.load_architecture(MANIFEST, cfg).__name__ == ARCH.__name__
    taken = set()
    for name in ("dense_gqa", "power_retention", "cca_moe", "hybrid_ssm"):
        ids = spec.load_module("architectures", name).LEAF_IDS.values()
        taken |= {i + k for i in ids for k in range(32)}  # a stacked leaf takes an id a slice
    own = list(ARCH.LEAF_IDS.values())
    experts = {base + k for base in ARCH.EXPERT_IDS.values() for k in range(1024)}
    assert len(own) == len(set(own)) and not set(own) & taken and not experts & (taken | set(own))
    assert len(experts) == 3 * 1024  # no two experts of the deployment share an id
    source = open(os.path.join(spec.HERE, "architectures", "mla_scmoe.py")).read()
    # only the seam imports the program; the reference shares no code with it
    assert "import gofr_tpu" not in source.split("def register")[0]
    assert "gofr_tpu" not in source.split("# -- the plain reference")[1]
    assert "ops.mla" not in source and "ops.experts" not in source
    assert 'default_matmul_precision("highest")' in source


def test_the_configuration_keeps_every_number_of_the_catalogs_row_but_the_four_it_cuts():
    cfg = spec.load_config(MANIFEST, CONFIG)
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12,
    }
    cut = {"num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"}
    assert {k for k, v in published.items() if cfg.get(k, "absent") != v} == cut
    assert set(cfg["reduced"]) == cut
    assert {k: cfg[k] for k in cut} == {"num_layers": 4, "n_routed_experts": 16,
                                        "vocab_size": 16384, "max_position_embeddings": 7168}
    assert {k: cfg["published"][k] for k in cut} == {k: published[k] for k in cut}
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == cut and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json")
    assert cfg["deployment"]["ep"] == 32 and cfg["deployment"]["ep_rank"] == 0
    assert cfg["deployment"]["vocab_shards"] == 8 and "pipeline" in cfg["deployment"]["stated"]
    for item in ("mla_scale", "rotary", "softmax_scale", "layer", "router", "identity_experts",
                 "parallelism", "tie_word_embeddings", "expert", "weights", "tokenizer"):
        assert cfg["assumed"][item]
    assert "interleaved" in cfg["assumed"]["rotary"] and "seeded 0" in cfg["assumed"]["router"]
    env = cfg["serving"]["env"]
    assert {k: env[k] for k in ("MODEL_MAX_SEQ", "MODEL_BUCKETS", "BATCH_MAX_SIZE",
                                "DECODE_CHUNK")} == {
        "MODEL_MAX_SEQ": "7168", "MODEL_BUCKETS": "1024", "BATCH_MAX_SIZE": "2",
        "DECODE_CHUNK": "8"}  # one bucket: a program each, and a cold run has 340 s (serving.buckets)
    assert "256" in cfg["serving"]["buckets"]
    assert int(env["DECODE_SLOTS"]) <= 40
    sz = ARCH.sizes_of(cfg)
    assert (sz["experts"], sz["routed"], sz["identity"], sz["top_k"]) == (16, 512, 256, 12)


def test_the_parameters_and_the_memory_are_the_issues_arithmetic():
    cfg = spec.load_config(MANIFEST, CONFIG)
    sz = ARCH.sizes_of(cfg)
    sheet = spec.load_module("kernels", "mla_scmoe_decode_step")
    experts = spec.load_module("kernels", "moe_experts")
    matmul, other = sheet.layer_params(sz)
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 8192 * 6144
    assert round(mla / 1e6, 1) == 90.6
    assert matmul == 2 * mla + 2 * 3 * 6144 * 12288 + 6144 * 768
    assert round(matmul / 1e6, 1) == 638.8 and round(2 * matmul / 1e9, 3) == 1.278
    assert experts.expert_bytes(sz) == 2 * 3 * 6144 * 2048 and round(
        experts.expert_bytes(sz) / 1e6, 1) == 75.5
    total = 4 * (matmul + other + 16 * 3 * 6144 * 2048) + 2 * 16384 * 6144 + 6144
    assert round(total / 1e9, 2) == 5.17 and round(2 * total / 1e9, 2) == 10.35
    assert "5.17e9" in cfg["parameters"] and "10.35 GB" in cfg["parameters"]
    assert sheet.latent_token_bytes(sz) == 9216
    slot = 9216 * 7168
    assert round(slot / 1e6, 1) == 66.1 and round(40 * slot / 1e9, 2) == 2.64
    # what an imported program makes of the same sizes
    import dataclasses

    import jax

    from gofr_tpu.models import transformer as T
    from gofr_tpu.models.llama import CONFIGS

    program = dataclasses.replace(CONFIGS["longcat-flash-ep32"], n_layers=4, vocab_size=16384,
                                  max_seq=7168)
    cache = jax.eval_shape(lambda: T.init_cache(program, 40, 7168))
    assert T.latent_token_bytes(cache) == sheet.latent_token_bytes(sz)
    made = jax.eval_shape(lambda: ARCH.make_params(1, sz))
    own = jax.eval_shape(lambda: T.init_transformer(jax.random.key(0), program))
    shapes = lambda tree: jax.tree.map(lambda x: (x.shape, str(x.dtype)), tree)  # noqa: E731
    assert shapes(made) == shapes(own)  # the seeded tree is the program's, leaf for leaf


def test_the_mix_and_the_cell_are_the_issues():
    mix = spec.load_mix(MANIFEST, "agent-steady")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.6,
                                    "min": 512, "max": 6144}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5,
                                    "min": 128, "max": 1024}
    assert mix["limits"] == {"ttft_ms": 3000, "tpot_ms": 50, "attainment": 0.9}
    assert (mix["loop"], mix["ramp_s"], mix["drain_s"], mix["trace_s"]) == ("open", 8.0, 60.0, 4.0)
    steady = spec.load_mix(MANIFEST, "reasoning-steady")
    assert mix["arrivals"] == steady["arrivals"]
    cfg = spec.load_config(MANIFEST, CONFIG)
    top = int(cfg["serving"]["env"]["MODEL_BUCKETS"].split(",")[-1])
    from benchmark.traffic import quantile_lengths

    lengths = quantile_lengths(mix["prompt_tokens"], 64)
    assert 0.8 <= sum(n > top for n in lengths) / 64 <= 0.95  # seven in eight are chunked
    assert -(-max(lengths) // top) == 6  # up to six slices
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
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "agent-steady"
    assert len(cell["why"]) <= 200 and "PREFIX_CACHE" in cell["why"] and "rows/64" in cell["why"]
    assert len(MANIFEST["workloads"]) >= 8 and all(c["chips"] == 1 for c in MANIFEST["workloads"])


NEW = ["kernel.mla.decode_step_roofline", "kernel.mla.decode_step_mfu", "mla.latent_read_share",
       "moe.identity_share", "moe.held_share", "kernel.mla.prefill_step_roofline",
       "kernel.mla.prefill_step_mfu", "moe.held_read_share", "moe.held_load_max_share"]
LAYERS = {"mla.latent_read_share": "latent cache", "moe.identity_share": "experts",
          "moe.held_share": "experts", "moe.held_read_share": "experts",
          "moe.held_load_max_share": "experts"}


def test_the_cell_reports_the_steady_metrics_and_its_nine_new_readers_wait_for_their_entries():
    """The manifest's last seven per-layer entries are pinned by
    ``test_transport_clock.py`` (PR 38), so this PR cannot append an entry
    and edits no file that is there: the nine readers and their entries, as
    a ``benchmark`` PR will append them, are in the cell's own file."""
    from tests.test_benchmark.test_manifest_floors import DENSE, OPEN_LOOP, RETENTION, STEADY

    names = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "per_layer")]
    assert [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "end_to_end")] == OPEN_LOOP
    # floors: what the cell reports at least (a later PR may append)
    assert set(names) >= set(STEADY) | {"state.insert_p50_ms"}
    # whose readers do not hold for pairs (rows [DECODE_SLOTS, ...]) or for latent rows
    assert not set(names) & (set(DENSE) | {"kernel.moe.experts_roofline"})
    assert not set(names) & (set(RETENTION) - {"state.insert_p50_ms"})
    assert not any(n.startswith(("kernel.ssm.", "ssm.", "kernel.moe.")) for n in names)
    assert len(names) == len(set(names))
    waiting = spec.load_cell_load(MANIFEST, CELL)["waiting_per_layer"]
    assert "test_transport_clock.py" in waiting["why"]
    assert [decl["name"] for decl in waiting["entries"]] == NEW
    declared = {m["name"] for m in MANIFEST["per_layer"]}
    for decl in waiting["entries"]:
        assert set(decl) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert decl["workloads"] == [CELL] and decl["unit"] == "%"
        assert decl["layer"] == LAYERS.get(decl["name"], "kernels")
        assert decl["moves"] == ("ttft_mean_ms" if "prefill" in decl["name"] else "tpot_mean_ms")
        assert decl["better"] == ("lower" if decl["name"] == "moe.held_load_max_share" else "higher")
        assert callable(spec.load_module("layer_metrics", decl["name"]).read)
        # the PR that appends an entry takes it off the waiting list
        assert decl["name"] not in declared or decl["name"] not in NEW


# -- served and checked in the harness ---------------------------------------------------------

@pytest.fixture(scope="module")
def with_longcat(tmp_path_factory):
    """The rehearsal data with a tiny ``mla_scmoe`` configuration and a cell
    ADDED, one whose reference drops the identity experts and one checked by
    the dense decoder's reference."""
    data = tmp_path_factory.mktemp("data") / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    manifest = spec.load_json(str(data / "BENCHMARK.json"))
    os.makedirs(data / "architectures")
    (data / "architectures" / "mla_scmoe_wrong.py").write_text(WRONG)
    (data / "architectures" / "mla_scmoe_other.py").write_text(OTHER)
    for name, arch in (("tiny-longcat", "mla_scmoe"), ("tiny-longcat-wrong", "mla_scmoe_wrong"),
                       ("tiny-longcat-other", "mla_scmoe_other")):
        (data / f"{name}.json").write_text(json.dumps(dict(TINY_CFG, architecture=arch)))
        shutil.copy(data / "cells" / "tiny.open.json", data / "cells" / f"{name}.open.json")
        manifest["configs"].append({"name": name, "source": "tests", "file": f"{name}.json",
                                    "reduced": [], "why": "LongCat-Flash's block at a test shape"})
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


def test_a_tiny_configuration_is_served_and_correct(with_longcat):
    """Batched prefill in padded buckets, one prompt above the top bucket
    (chunked over a carried latent), the pool with rows of unequal length
    and slots without a request, the solo fallback: every served token is
    the reference's best."""
    result = _rehearse(with_longcat, "tiny-longcat.open")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert result["check"][0]["agree_share"] == 1.0


@pytest.mark.parametrize("workload", ["tiny-longcat-wrong.open", "tiny-longcat-other.open"])
def test_a_reference_short_of_a_term_or_of_another_architecture_is_not_correct(with_longcat, workload):
    result = _rehearse(with_longcat, workload)
    assert result["correct"] is False and result["failed"] == 0


def test_the_parents_program_refuses_the_architecture_cleanly(monkeypatch):
    """A program with no latent attention and no top-k gate cannot serve
    it: ``register`` says so (``run.py`` exits 3) before anything is built."""
    import gofr_tpu.models.transformer as T

    fields = {k: v for k, v in T.TransformerConfig.__dataclass_fields__.items()
              if k not in ("kv_lora_rank", "router_kind")}
    monkeypatch.setattr(T.TransformerConfig, "__dataclass_fields__", fields)
    cfg = spec.load_config(MANIFEST, CONFIG)
    run = types.SimpleNamespace(cfg=cfg, sizes=ARCH.sizes_of(cfg), seed=1, log=print,
                                server_env={})
    with pytest.raises(spec.SpecError, match="no latent attention"):
        ARCH.register(run)


@pytest.mark.parametrize("key,value", [
    ("attention_method", "MHA"), ("zero_expert_type", "copy"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("n_routed_experts", 3), ("moe_topk", 13),
    ("deployment", {"ep": 4, "ep_rank": 4})])
def test_a_configuration_the_module_is_not_written_for_is_refused(key, value):
    with pytest.raises(spec.SpecError):
        ARCH.sizes_of(dict(TINY_CFG, **{key: value}))


# -- the work sheets against hand counts at the tiny shape ----------------------------------------

TINY = ARCH.sizes_of(TINY_CFG)
MLA = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 64 * 64
LAYER = 2 * MLA + 2 * 3 * 64 * 96 + 64 * 12
OTHER_W = 2 * (2 * 64 + 32 + 16) + 2 * 12
HEAD = 256 * 64
EXPERT = 3 * 64 * 32
TOKEN = 2 * 2 * (16 + 8) * 2  # four sublayers, latent and rope, bf16


def _run(**env):
    """Two chunks of 4 steps (1 and 3 live rows), a prefill of two rows and
    a slice of one that carries 32 positions."""
    chunk = {"kind": "decode_chunk", "status": "ok"}
    pairs = lambda held, ident, absent: {  # noqa: E731
        "expert_tokens": held, "identity_tokens": ident, "absent_tokens": absent}
    return types.SimpleNamespace(
        sizes=TINY, server_env={"DECODE_CHUNK": "4", "DECODE_SLOTS": "6", **env}, w0=0.0, w1=10.0,
        records=[], dispatches=[
            dict(chunk, batch_size=1, experts_read=5, latent_bytes=TOKEN * 46, **pairs(6, 8, 10)),
            dict(chunk, batch_size=3, experts_read=11, latent_bytes=TOKEN * 300, **pairs(20, 22, 30)),
            {"kind": "prefill", "status": "ok", "batch_size": 2, "bucket": 16,
             "padded_tokens": 22, "tokens": 10, "experts_read": 4, "latent_bytes": TOKEN * 10,
             **pairs(12, 20, 28)},
            {"kind": "prefill_chunk", "status": "ok", "batch_size": 1, "bucket": 32,
             "padded_tokens": 0, "tokens": 30, "experts_read": 4, "latent_bytes": TOKEN * 62,
             **pairs(40, 60, 80)}])


def test_the_decode_sheet_counts_what_a_step_must_move():
    sheet = spec.load_module("kernels", "mla_scmoe_decode_step")
    assert sheet.layer_params(TINY) == (LAYER, OTHER_W)
    weights = 2 * (2 * (LAYER + OTHER_W) + HEAD)
    assert sheet.weight_bytes(TINY) == weights and sheet.latent_token_bytes(TINY) == TOKEN
    flops, moved, latent = sheet.step_work(_run())
    # two chunks of four steps: 2 live rows, 8 experts read, 13 pairs landed, 173 positions
    assert moved == pytest.approx(weights + 2 * EXPERT * 16 / 2 / 4)
    assert latent == pytest.approx(TOKEN * 346 / 2 / 4)
    per_position = (2 * 24 + 2 * 16) * 4 * 4  # score and sum, 4 heads, 4 sublayers
    assert flops == pytest.approx(2 * 2 * (2 * LAYER + HEAD) + 2 * EXPERT * 26 / 2 / 4
                                  + per_position * 346 / 2 / 4)
    assert sheet.work(_run(), 3) == (pytest.approx(12 * flops), pytest.approx(12 * (moved + latent)))
    # a row that is not live owes nothing: no chunk, no work beyond the weights
    idle = _run()
    idle.dispatches = idle.dispatches[2:]
    assert sheet.step_work(idle) == (0.0, weights, 0.0)


def test_the_prefill_sheet_counts_real_tokens_and_what_a_slice_reaches():
    sheet = spec.load_module("kernels", "mla_scmoe_prefill_step")
    decode = spec.load_module("kernels", "mla_scmoe_decode_step")
    run = _run()
    pair = 2 * (16 + 8 + 16) * 4 * 4
    whole = 2 * 2 * LAYER * 10 + 2 * HEAD * 2 + pair * 2 * (5 * 5 / 2)
    piece = 2 * 2 * LAYER * 30 + 2 * HEAD + pair * (30 * 32 + 30 * 30 / 2)
    flops, nbytes = sheet.work(run, 4)
    assert flops == pytest.approx(4 * ((whole + piece) / 2 + 2 * EXPERT * 52 / 2))
    assert nbytes == pytest.approx(4 * (decode.weight_bytes(TINY) + 2 * EXPERT * 8 / 2 + TOKEN * 72 / 2))
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
    decode = spec.load_module("kernels", "mla_scmoe_decode_step")
    flops, nbytes = decode.work(run, 2)
    assert _read("kernel.mla.decode_step_mfu", run) == pytest.approx(100 * flops / (1e9 * 4.0))
    assert _read("kernel.mla.decode_step_roofline", run) == pytest.approx(
        100 * max(flops / 1e9, nbytes / 1e8) / 4.0)
    pflops, pbytes = spec.load_module("kernels", "mla_scmoe_prefill_step").work(run, 2)
    assert _read("kernel.mla.prefill_step_mfu", run) == pytest.approx(100 * pflops / (1e9 * 0.5))
    assert _read("kernel.mla.prefill_step_roofline", run) == pytest.approx(
        100 * max(pflops / 1e9, pbytes / 1e8) / 0.5)


def test_the_counter_readers_say_where_the_pairs_and_the_bytes_went():
    run = _run()
    assert _read("moe.identity_share", run) == pytest.approx(100 * 30 / 96)
    assert _read("moe.held_share", run) == pytest.approx(100 * 26 / 96)
    latent, held = TOKEN * 346, 2 * EXPERT * 16
    weights = spec.load_module("kernels", "mla_scmoe_decode_step").weight_bytes(TINY)
    assert _read("mla.latent_read_share", run) == pytest.approx(
        100 * latent / (latent + held + 8 * weights))
    # the accepted expert shares' readers read pairs as they read tokens
    assert _read("moe.held_read_share", run) == pytest.approx(100 * 16 / (2 * 2 * 8))
    for d in run.dispatches[:2]:
        d["expert_tokens_max"] = d["expert_tokens"] // 2
    assert _read("moe.held_load_max_share", run) == pytest.approx(100 * 13 / 26)


def test_a_program_without_the_counters_or_a_run_without_a_trace_reads_nothing():
    """As on the parent commit, whose records carry no ``latent_bytes`` and
    no pair counts: nothing is raised."""
    bare = _traced()
    for d in bare.dispatches:
        for key in ("latent_bytes", "identity_tokens", "absent_tokens", "experts_read",
                    "expert_tokens"):
            d.pop(key, None)
    for name in ("mla.latent_read_share", "moe.identity_share", "moe.held_share",
                 "moe.held_read_share", "moe.held_load_max_share"):
        assert _read(name, bare) is None
    untraced = _run()
    untraced.trace = untraced.peaks = None
    for name in NEW:
        if name.startswith("kernel."):
            assert _read(name, untraced) is None
