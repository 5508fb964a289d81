"""The ``hybrid_ssm`` architecture (AI21-Jamba2-3B) in the harness: the
contract, the configuration against the catalog's row and the issue's
arithmetic, a tiny configuration added to a copy of the rehearsal data
served and checked by its own plain reference (and called not correct by a
reference that drops a term, and by another architecture's), the work sheets
against hand counts, each new reader on a small fixture. Its cell's metric
lists are written out here, as ``test_manifest_floors.py`` asks of a new
cell. No chip."""

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
CONFIG = "jamba2-3b-bf16"
CELL = "jamba2-3b-bf16.reasoning-steady"
ARCH = spec.load_module("architectures", "hybrid_ssm")

# two periods of (ssm, ssm, softmax, ssm): the layer loop's outer scan runs
TINY_CFG = {
    "source": "tests", "architecture": "hybrid_ssm", "hidden_size": 64, "num_hidden_layers": 8,
    "attn_layer_period": 4, "attn_layer_offset": 2, "expert_layer_period": 2,
    "expert_layer_offset": 1, "num_experts": 1, "num_experts_per_tok": 1,
    "num_attention_heads": 4, "num_key_value_heads": 1, "intermediate_size": 128,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 4, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "vocab_size": 256,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    "serving": {"quant": "", "dtype": "float32",
                "env": {"MODEL_MAX_SEQ": "128", "MODEL_BUCKETS": "16,32", "BATCH_MAX_SIZE": "2",
                        "DECODE_SLOTS": "4", "MODEL_ATTN_IMPL": "xla", "BATCH_TIMEOUT_MS": "5"}},
}

WRONG = '''"""hybrid_ssm's weights and seam, checked by a reference without the skip D * u."""
import jax.numpy as jnp
from benchmark import spec

_own = spec.load_module("architectures", "hybrid_ssm")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register
_values = _own.vector_values


def _without_d(seed, layer, name, sz):
    value = _values(seed, layer, name, sz)
    return jnp.zeros_like(value) if name == "ssm_d" else value


def logits_at(*args, **kw):
    _own.vector_values = _without_d  # the reference's alone: the program is served the real D
    try:
        yield from _own.logits_at(*args, **kw)
    finally:
        _own.vector_values = _values
'''

# another architecture's reference over this model's serving: the dense GQA
# decoder knows no state-space layer
OTHER = '''"""hybrid_ssm's weights and seam, checked by the dense decoder's reference."""
from benchmark import spec

_own = spec.load_module("architectures", "hybrid_ssm")
_dense = spec.load_module("architectures", "dense_gqa")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register


def logits_at(seed, cfg, blocks, mode=None):
    dense = dict(cfg, rope_theta=10000.0, head_dim=cfg["hidden_size"] // cfg["num_attention_heads"])
    return _dense.logits_at(seed, dense, blocks, mode)
'''


# -- the contract and the configuration -----------------------------------------------------------

def test_the_module_keeps_the_architecture_contract_and_ids_of_its_own():
    cfg = spec.load_config(MANIFEST, CONFIG)
    assert spec.load_architecture(MANIFEST, cfg).__name__ == ARCH.__name__
    taken = set()
    for name in ("dense_gqa", "power_retention", "cca_moe"):
        ids = spec.load_module("architectures", name).LEAF_IDS.values()
        taken |= {i + k for i in ids for k in range(32)}  # a stacked leaf takes an id a slice
    own = list(ARCH.LEAF_IDS.values())
    assert len(own) == len(set(own)) and not set(own) & taken
    source = open(os.path.join(spec.HERE, "architectures", "hybrid_ssm.py")).read()
    # only the seam imports the program; the reference shares no code with it
    assert "import gofr_tpu" not in source.split("def register")[0]
    assert "gofr_tpu" not in source.split("# -- the plain reference")[1]
    assert "ops.ssm" not in source and "ops import ssm" not in source


def test_the_configuration_keeps_every_number_of_the_catalogs_row_but_the_context():
    cfg = spec.load_config(MANIFEST, CONFIG)
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
        "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536,
    }
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == {"max_position_embeddings"}
    assert set(cfg["reduced"]) == {"max_position_embeddings"}
    assert cfg["published"]["max_position_embeddings"] == 262144
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["max_position_embeddings"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    for item in ("layer_order", "expert_layers", "mixer", "inner_norms", "positional_embedding",
                 "state_type", "state_layout", "weights", "tokenizer"):
        assert cfg["assumed"][item]
    env = cfg["serving"]["env"]
    assert env == {"MODEL_MAX_SEQ": "2048", "MODEL_BUCKETS": "128,256", "BATCH_MAX_SIZE": "2",
                   "DECODE_SLOTS": "64", "DECODE_CHUNK": "8"}
    kinds = ARCH.kinds_of(cfg)
    assert [i for i, k in enumerate(kinds) if k == "softmax"] == [7, 21]
    assert kinds.count("ssm") == 26


def test_the_parameters_and_the_memory_are_the_issues_arithmetic():
    cfg = spec.load_config(MANIFEST, CONFIG)
    sz = ARCH.sizes_of(cfg)
    sheet = spec.load_module("kernels", "hybrid_ssm_decode_step")
    per = sheet.layer_params(sz)
    mixer = (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)  # the four matmuls
    assert per["ssm"][0] == mixer + 3 * 2560 * 8192
    assert per["softmax"][0] == 2 * 2560 * 2560 + 2 * 2560 * 128 + 3 * 2560 * 8192
    small = 5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120 + 192 + 2 * 2560
    assert round((per["ssm"][0] + small) / 1e6, 2) == 104.16
    assert round((per["softmax"][0] + 2 * 2560) / 1e6, 2) == 76.68
    total = 26 * (per["ssm"][0] + small) + 2 * (per["softmax"][0] + 2 * 2560) + 65536 * 2560 + 2560
    assert round(total / 1e9, 2) == 3.03 and round(2 * total / 1e9, 2) == 6.06
    assert "3.03e9" in cfg["parameters"] and "6.06 GB" in cfg["parameters"]
    run = types.SimpleNamespace(sizes=sz, server_env={})
    state, tail = sheet.state_row_bytes(run)
    assert (state, tail) == (327680, 30720)  # 16 x 5120 x 4 B, 3 x 5120 x 2 B
    assert round(26 * state / 1e6, 2) == 8.52 and round(26 * tail / 1e6, 2) == 0.80
    assert sheet.kv_bytes_per_token(run) == 1024  # 1 KB a token: the two attention layers
    # what an imported program makes of the same sizes
    import jax

    from gofr_tpu.models import transformer as T
    from gofr_tpu.models.llama import CONFIGS

    program = CONFIGS["jamba2-3b"]
    assert program.layer_kinds == ARCH.kinds_of(cfg) and program.rope_dim == 0
    assert program.layer_period == (14, (("ssm", 0, 7), ("softmax", 0, 1), ("ssm", 7, 6)))
    cache = jax.eval_shape(lambda: T.init_cache(program, 64, 2048))
    assert {n: (v.shape, str(v.dtype)) for n, v in cache.items() if v.ndim > 1} == {
        "k": ((2, 64, 1, 2048, 128), "bfloat16"), "v": ((2, 64, 1, 2048, 128), "bfloat16"),
        "ssm": ((26, 64, 16, 5120), "float32"), "conv": ((26, 64, 15360), "bfloat16")}
    assert T.state_row_bytes(cache) == 26 * (state + tail)


def test_the_mix_and_the_cell_are_the_issues():
    mix = spec.load_mix(MANIFEST, "reasoning-steady")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                                    "min": 32, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 640, "sigma": 0.5,
                                    "min": 192, "max": 1536}
    assert mix["limits"] == {"ttft_ms": 2000, "tpot_ms": 50, "attainment": 0.9}
    cfg = spec.load_config(MANIFEST, CONFIG)
    assert mix["prompt_tokens"]["max"] > int(cfg["serving"]["env"]["MODEL_BUCKETS"].split(",")[-1])
    load = spec.load_cell_load(MANIFEST, CELL)
    assert load["rate_rps"] <= 0.8 * load["knee_rps"] * 1.02
    # the rate is ``knee.py``'s rule over the sweep the file holds, not a hand's: the
    # highest swept rate at which the mix's share met both limits and nothing failed,
    # times 0.8 (the issue's fallbacks, 0.7 and 0.6, only with the six seeds that forced one)
    sweep = load["sweep"]
    good = [rate for rate, met, failed in zip(sweep["rates_rps"], sweep["met_share"], sweep["failed"])
            if met >= mix["limits"]["attainment"] and not failed]
    assert load["knee_rps"] == max(good)
    share = load["rate_rps"] / load["knee_rps"]
    assert share == pytest.approx(0.8) or (
        share in (pytest.approx(0.7), pytest.approx(0.6)) and "fallback" in sweep)
    # what ``correct`` cannot tell stands beside the limits' reasons
    assert "bfloat16" in load["check_reasons"]["not_covered"]
    check = dict(mix["check"], **load["check"])
    assert max(check["widths"]) >= mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert check["scored"] >= check["rows"] * mix["output_tokens"]["max"]
    cell = spec.find_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG and cell["traffic"] == "reasoning-steady"
    assert len(cell["why"]) <= 200


NEW = ["kernel.ssm.decode_step_roofline", "kernel.ssm.decode_step_mfu",
       "kernel.ssm.prefill_step_roofline", "kernel.ssm.prefill_step_mfu",
       "ssm.state_move_share"]


def test_the_cell_reports_the_steady_metrics_its_kv_share_the_inserts_and_the_five_new_ones():
    from tests.test_benchmark.test_manifest_floors import DENSE, OPEN_LOOP, RETENTION, STEADY

    names = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "per_layer")]
    assert [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "end_to_end")] == OPEN_LOOP
    # floors: what the cell reports at least (a later PR may append)
    assert set(names) >= (set(STEADY) | {"kernel.decode_kv_read_share", "state.insert_p50_ms"}
                          | set(NEW))
    assert not set(names) & (set(DENSE) - {"kernel.decode_kv_read_share"})
    assert not set(names) & (set(RETENTION) - {"state.insert_p50_ms"})
    assert len(names) == len(set(names))
    for decl in MANIFEST["per_layer"]:
        if decl["name"] in NEW:
            assert decl["workloads"][0] == CELL and decl["unit"] == "%"
            assert decl["layer"] == ("state" if decl["name"].startswith("ssm.") else "kernels")
            assert callable(spec.load_module("layer_metrics", decl["name"]).read)


# -- served and checked in the harness ---------------------------------------------------------

@pytest.fixture(scope="module")
def with_jamba(tmp_path_factory):
    """The rehearsal data with a tiny ``hybrid_ssm`` configuration and a
    cell ADDED, one whose reference drops ``D * u`` and one checked by the
    dense decoder's reference."""
    data = tmp_path_factory.mktemp("data") / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    manifest = spec.load_json(str(data / "BENCHMARK.json"))
    os.makedirs(data / "architectures")
    (data / "architectures" / "hybrid_ssm_wrong.py").write_text(WRONG)
    (data / "architectures" / "hybrid_ssm_other.py").write_text(OTHER)
    for name, arch in (("tiny-jamba", "hybrid_ssm"), ("tiny-jamba-wrong", "hybrid_ssm_wrong"),
                       ("tiny-jamba-other", "hybrid_ssm_other")):
        (data / f"{name}.json").write_text(json.dumps(dict(TINY_CFG, architecture=arch)))
        shutil.copy(data / "cells" / "tiny.open.json", data / "cells" / f"{name}.open.json")
        manifest["configs"].append({"name": name, "source": "tests", "file": f"{name}.json",
                                    "reduced": [], "why": "Jamba's stack at a test shape"})
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


def test_a_tiny_hybrid_configuration_is_served_and_correct(with_jamba):
    """Batched prefill in padded buckets, one prompt above the top bucket
    (chunked from a carried state, tail and K/V rows), the pool and the
    solo fallback, two periods of the layer pattern: every served token is
    the reference's best."""
    result = _rehearse(with_jamba, "tiny-jamba.open")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert result["check"][0]["agree_share"] == 1.0


@pytest.mark.parametrize("workload", ["tiny-jamba-wrong.open", "tiny-jamba-other.open"])
def test_a_reference_without_a_term_or_of_another_architecture_is_not_correct(with_jamba, workload):
    result = _rehearse(with_jamba, workload)
    assert result["correct"] is False and result["failed"] == 0


def test_the_parents_program_refuses_the_architecture_cleanly(monkeypatch):
    """A program whose layers are all of one kind cannot serve it:
    ``register`` says so (``run.py`` exits 3) before anything is built."""
    import gofr_tpu.models.transformer as T

    fields = dict(T.TransformerConfig.__dataclass_fields__)
    del fields["layer_kinds"]
    monkeypatch.setattr(T.TransformerConfig, "__dataclass_fields__", fields)
    cfg = spec.load_config(MANIFEST, CONFIG)
    run = types.SimpleNamespace(cfg=cfg, sizes=ARCH.sizes_of(cfg), seed=1, log=print,
                                server_env={})
    with pytest.raises(spec.SpecError, match="all of one kind"):
        ARCH.register(run)


@pytest.mark.parametrize("key,value", [("num_experts", 16), ("mamba_proj_bias", True),
                                       ("mamba_conv_bias", False),
                                       ("tie_word_embeddings", False)])
def test_a_configuration_the_module_is_not_written_for_is_refused(key, value):
    with pytest.raises(spec.SpecError):
        ARCH.sizes_of(dict(TINY_CFG, **{key: value}))


def test_the_registered_model_holds_its_state_in_float32(monkeypatch):
    """The state's type is the program's: ``register`` passes none, and no
    field of the config names one (``kv_dtype`` is what K and V take)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import gofr_tpu.models.transformer as T
    from gofr_tpu.models.llama import CONFIGS

    # ``register`` puts its seeded tree in place of the program's init: put back after
    monkeypatch.setattr(T, "init_transformer", T.init_transformer)
    cfg = dict(TINY_CFG, _name="tiny-jamba-registered")
    run = types.SimpleNamespace(cfg=cfg, sizes=ARCH.sizes_of(cfg), seed=1, log=print,
                                server_env={})
    try:
        assert ARCH.register(run) == "tiny-jamba-registered"
        model = dataclasses.replace(CONFIGS["tiny-jamba-registered"], kv_dtype=jnp.float8_e4m3fn)
        cache = jax.eval_shape(lambda: T.init_cache(model, 2, 32))
        assert cache["k"].dtype == jnp.float8_e4m3fn
        assert cache["ssm"].dtype == jnp.float32 and cache["conv"].dtype == model.dtype
    finally:
        CONFIGS.pop("tiny-jamba-registered", None)


# -- the work sheets against hand counts at the tiny shape ----------------------------------------

TINY = ARCH.sizes_of(TINY_CFG)
FFN = 3 * 64 * 128
SSM = 64 * 256 + 128 * 36 + 4 * 128 + 128 * 64  # in, x, dt, out
SSM_OTHER = 2 * (4 * 128 + 128 + 4 + 32 + 128) + 4 * (128 + 16 * 128 + 128)
ATTN = 2 * 64 * 64 + 2 * 64 * 16
HEAD = 256 * 64
STATE, TAIL = 16 * 128 * 4, 3 * 128 * 2


def _run(**env):
    """Two chunks of 4 steps (1 and 3 live rows), a prefill of two rows and
    a slice of one."""
    chunk = {"kind": "decode_chunk", "status": "ok"}
    rec = lambda t0, n, times: {"n_prompt": n, "times": [t0 + 0.5 * j for j in range(times)]}  # noqa: E731
    return types.SimpleNamespace(
        sizes=TINY, server_env={"DECODE_CHUNK": "4", "DECODE_SLOTS": "6", **env}, w0=0.0, w1=10.0,
        records=[rec(1.0, 10, 5), rec(2.0, 20, 3)],
        dispatches=[dict(chunk, batch_size=1, state_bytes=1 * 2 * 6 * (STATE + TAIL) * 4),
                    dict(chunk, batch_size=3, state_bytes=3 * 2 * 6 * (STATE + TAIL) * 4),
                    {"kind": "prefill", "status": "ok", "batch_size": 2, "bucket": 16,
                     "padded_tokens": 22, "tokens": 10},
                    {"kind": "prefill_chunk", "status": "ok", "batch_size": 1, "bucket": 32,
                     "padded_tokens": 0, "tokens": 30}])


def test_the_decode_sheet_counts_what_a_step_must_move():
    sheet = spec.load_module("kernels", "hybrid_ssm_decode_step")
    assert TINY["ssm_layers"] == 6 and TINY["attn_layers"] == 2
    assert sheet.layer_params(TINY) == {
        "ssm": (SSM + FFN, 2 * (SSM + FFN) + SSM_OTHER),
        "softmax": (ATTN + FFN, 2 * (ATTN + FFN) + 4 * 64)}
    weights = 6 * (2 * (SSM + FFN) + SSM_OTHER) + 2 * (2 * (ATTN + FFN) + 256) + 2 * HEAD
    assert sheet.weight_bytes(TINY) == weights
    run = _run()
    assert sheet.state_row_bytes(run) == (STATE, TAIL)
    assert sheet.state_row_bytes(_run(MODEL_KV_DTYPE="f8")) == (STATE, TAIL)  # K/V's type alone
    assert sheet.kv_bytes_per_token(run) == 2 * 2 * 16 * 2  # the two attention layers alone
    assert sheet.kv_bytes_per_token(_run(MODEL_KV_DTYPE="f8")) == 2 * 2 * 16
    live = (0.5 * (11 + 12 + 13 + 14) + 0.5 * (21 + 22)) / 10.0
    flops, moved, state = sheet.step_work(run)
    body = 6 * (SSM + FFN) + 2 * (ATTN + FFN)
    assert moved == pytest.approx(weights + 128 * live)
    assert state == 2 * 6 * 2 * (STATE + TAIL)  # 2 live rows on average
    assert flops == pytest.approx(2 * 2 * (body + HEAD) + 7 * 16 * 128 * 6 * 2
                                  + 4 * 16 * 4 * 2 * live)
    assert sheet.work(run, 3) == (pytest.approx(12 * flops), pytest.approx(12 * (moved + state)))
    # what the program's counter says of the same chunks: the sheet's state bytes
    assert sum(d["state_bytes"] for d in run.dispatches[:2]) == 2 * 4 * state


def test_the_prefill_sheet_counts_real_tokens_alone():
    sheet = spec.load_module("kernels", "hybrid_ssm_prefill_step")
    decode = spec.load_module("kernels", "hybrid_ssm_decode_step")
    run = _run()
    body = 6 * (SSM + FFN) + 2 * (ATTN + FFN)
    one = lambda tokens, rows: (  # noqa: E731
        2 * body * tokens + 2 * HEAD * rows + 7 * 16 * 128 * 6 * tokens
        + rows * 4 * 16 * 4 * 2 * (tokens / rows) ** 2 / 2)
    flops, nbytes = sheet.work(run, 4)
    assert flops == pytest.approx(4 * (one(10, 2) + one(30, 1)) / 2)
    assert nbytes == 4 * decode.weight_bytes(TINY)
    empty = _run()
    empty.dispatches = empty.dispatches[:2]
    assert sheet.work(empty, 4) == (0.0, 0.0)


# -- the readers on a small fixture ----------------------------------------------------------------

PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8, "hbm_bytes": 16e9}


def _traced(ops, pooled_s=4.0, prefill_s=0.5):
    run = _run()
    run.peaks, run.seconds = PEAKS, 10.0
    run.trace = {"device_ops": ops, "programs": {
        "jit__lambda(7)": {"seconds": pooled_s, "runs": 2},
        "jit__prefill_fn(3)": {"seconds": prefill_s, "runs": 2}},
        "released": {"gofr.pool.fetch_wait": {"jit__lambda(7)": 5}}}
    return run


def _read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_the_program_readers_are_the_sheets_over_the_traced_time():
    run = _traced([])
    decode = spec.load_module("kernels", "hybrid_ssm_decode_step")
    flops, nbytes = decode.work(run, 2)
    assert _read("kernel.ssm.decode_step_mfu", run) == pytest.approx(100 * flops / (1e9 * 4.0))
    assert _read("kernel.ssm.decode_step_roofline", run) == pytest.approx(
        100 * max(flops / 1e9, nbytes / 1e8) / 4.0)
    pflops, pbytes = spec.load_module("kernels", "hybrid_ssm_prefill_step").work(run, 2)
    assert _read("kernel.ssm.prefill_step_mfu", run) == pytest.approx(100 * pflops / (1e9 * 0.5))
    assert _read("kernel.ssm.prefill_step_roofline", run) == pytest.approx(
        100 * max(pflops / 1e9, pbytes / 1e8) / 0.5)
    _, moved, state = decode.step_work(run)
    assert _read("ssm.state_move_share", run) == pytest.approx(100 * state / (state + moved))


def test_the_programs_shares_read_whichever_operations_the_trace_names():
    """``run.trace`` keeps the ten largest operations and each Pallas
    kernel shows under a name a compiled body of the layer loop, of which
    one, both or none are among the ten: the cell's shares are of whole
    programs, by their module names, and do not move with that."""
    for name in NEW[:4]:
        assert _read(name, _traced([])) == pytest.approx(
            _read(name, _traced([["ssm_step.1 custom-call", 0.3], ["ssm_scan.4 custom-call", 0.1]])))


def test_a_program_without_the_counter_or_a_run_without_a_trace_reads_nothing():
    """As on the parent commit, whose records carry no ``state_bytes`` for
    this model and whose trace names no such kernel: nothing is raised."""
    bare = _traced([["fusion.1 f32[4] fusion", 0.5]])
    for d in bare.dispatches:
        d.pop("state_bytes", None)
    assert _read("ssm.state_move_share", bare) is None
    untraced = _run()
    untraced.trace = untraced.peaks = None
    for name in NEW:
        if name != "ssm.state_move_share":
            assert _read(name, untraced) is None
