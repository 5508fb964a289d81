"""The ``cca_moe`` architecture (ZAYA1-8B) in the harness: the contract, the
configuration's cut and arithmetic, a tiny configuration added to a copy of
the rehearsal data served and checked by its own plain reference, the three
work sheets against hand counts, each new reader on a small fixture, and
that no share passes 100% whether a step reads one expert a layer or all
sixteen. Its cell's metric lists are written out here, as
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
CELL = "zaya1-8b-bf16.reasoning-steady"
ARCH = spec.load_module("architectures", "cca_moe")

TINY_CFG = {
    "source": "tests", "architecture": "cca_moe", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 1,
    "router_hidden_size": 16, "vocab_size": 256, "max_position_embeddings": 128,
    "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"rope_theta": 10000.0}}, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True,
    "serving": {"quant": "", "dtype": "float32",
                "env": {"MODEL_MAX_SEQ": "128", "MODEL_BUCKETS": "16,32", "BATCH_MAX_SIZE": "2",
                        "DECODE_SLOTS": "4", "MODEL_ATTN_IMPL": "xla", "BATCH_TIMEOUT_MS": "5"}},
}

WRONG = '''"""cca_moe's weights and seam, checked by a reference without the value shift."""
import jax.numpy as jnp
from benchmark import spec

_own = spec.load_module("architectures", "cca_moe")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register
_own._before = lambda x: jnp.zeros_like(x)  # nothing comes from the token before


def logits_at(*args, **kw):
    return _own.logits_at(*args, **kw)
'''


# -- the contract and the configuration -----------------------------------------------------------

def test_the_module_keeps_the_architecture_contract_and_ids_of_its_own():
    cfg = spec.load_config(MANIFEST, "zaya1-8b-bf16")
    assert spec.load_architecture(MANIFEST, cfg).__name__ == ARCH.__name__
    taken = set()
    for name in ("dense_gqa", "power_retention"):
        taken |= set(spec.load_module("architectures", name).LEAF_IDS.values())
    sz = ARCH.sizes_of(cfg)
    own = set()
    for name, first in ARCH.LEAF_IDS.items():
        n = sz["experts"] if name in ARCH.EXPERTS else (
            2 * (sz["heads"] + sz["kv_heads"]) if name == "cca_w1" else 1)
        span = set(range(first, first + n))
        assert not span & own, name
        own |= span
    assert not own & taken
    source = open(os.path.join(spec.HERE, "architectures", "cca_moe.py")).read()
    # only the seam imports the program; the reference shares no code with it
    assert "import gofr_tpu" not in source.split("def register")[0]
    assert "gofr_tpu" not in source.split("# -- the plain reference")[1]


def test_the_configuration_keeps_every_published_number_but_the_depth():
    cfg = spec.load_config(MANIFEST, "zaya1-8b-bf16")
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272,
    }
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "zaya1-8b-bf16")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 20 and cfg["published"]["num_hidden_layers"] == 40
    assert cfg["layer_types"] == ["hybrid"] * 40  # a nested group is copied whole
    assert cfg["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}
    for item in ("convolutions", "qk_mean", "l2_norm", "value_shift", "rotary", "router",
                 "expert", "not_modelled", "weights", "tokenizer"):
        assert cfg["assumed"][item]
    assert "zaya_use_mod" in cfg["assumed"]["not_modelled"]
    assert "scale_residual_merge" in cfg["assumed"]["not_modelled"]
    env = cfg["serving"]["env"]
    assert (env["MODEL_MAX_SEQ"], env["MODEL_BUCKETS"], env["BATCH_MAX_SIZE"],
            env["DECODE_CHUNK"]) == ("2048", "128,256", "2", "8")


def test_the_parameters_and_the_memory_are_the_issues_arithmetic():
    cfg = spec.load_config(MANIFEST, "zaya1-8b-bf16")
    sz = ARCH.sizes_of(cfg)
    sheet = spec.load_module("kernels", "cca_moe_decode_step")
    matmul, other = sheet.dense_params(sz)
    expert = 3 * 2048 * 2048
    assert expert == 12582912 and matmul + other == 6240514  # 12.58 M and 6.24 M
    layer = 16 * expert + matmul + other
    table = 262272 * 2048
    assert round(layer / 1e6, 1) == 207.6 and round(2 * layer / 1e9, 3) == 0.415
    assert round(2 * table / 1e9, 3) == 1.074
    assert round(2 * (20 * layer + table) / 1e9, 2) == 9.38  # what one chip holds
    assert round(2 * (40 * layer + table) / 1e9, 2) == 17.68  # more than it has
    assert f"{(20 * layer + table) / 1e9:.2f}e9 held" in cfg["parameters"]
    assert sheet.tail_values(sz) == 2688
    run = types.SimpleNamespace(sizes=sz, server_env={})
    assert sheet.kv_bytes_per_token(run) == 20 * 1024  # 1 KB a token and layer
    # what an imported program makes of the same sizes
    from gofr_tpu.models.llama import CONFIGS

    program = CONFIGS["zaya1-8b"]
    assert (program.q_dim, program.tail_dim, program.n_experts) == (1024, 2688, 16)


def test_the_mix_and_the_cell_are_the_issues():
    mix = spec.load_mix(MANIFEST, "reasoning-steady")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                                    "min": 32, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 640, "sigma": 0.5,
                                    "min": 192, "max": 1536}
    assert (mix["ramp_s"], mix["drain_s"], mix["trace_s"]) == (8.0, 60.0, 4.0)
    assert mix["limits"] == {"ttft_ms": 2000, "tpot_ms": 50, "attainment": 0.9}
    assert mix["check"] == {"widths": [1024, 2048], "rows": 1, "scored": 1536}
    assert max(mix["check"]["widths"]) >= mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    cfg = spec.load_config(MANIFEST, "zaya1-8b-bf16")
    # the fifth of the prompts above the top bucket are chunked from a carried tail
    assert mix["prompt_tokens"]["max"] > int(cfg["serving"]["env"]["MODEL_BUCKETS"].split(",")[-1])
    load = spec.load_cell_load(MANIFEST, CELL)
    assert load["rate_rps"] == pytest.approx(0.8 * load["knee_rps"], rel=0.02)
    assert set(load["check"]) == {"served_gap_mean_limit", "served_gap_max_limit"}
    cell = spec.find_cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and cell["config"] == "zaya1-8b-bf16"


NEW = ["kernel.moe.decode_step_roofline", "kernel.moe.decode_step_mfu",
       "kernel.moe.prefill_step_roofline", "kernel.moe.prefill_step_mfu",
       "kernel.moe.experts_roofline", "moe.experts_read_share", "moe.load_max_share"]


def test_the_cell_reports_the_steady_metrics_its_kv_share_and_the_seven_new_ones():
    from tests.test_benchmark.test_manifest_floors import DENSE, OPEN_LOOP, RETENTION, STEADY

    names = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "per_layer")]
    assert [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL, "end_to_end")] == OPEN_LOOP
    assert set(names) == set(STEADY) | {"kernel.decode_kv_read_share"} | set(NEW)
    assert not set(names) & (set(DENSE) - {"kernel.decode_kv_read_share"})
    assert not set(names) & set(RETENTION)
    for decl in MANIFEST["per_layer"]:
        if decl["name"] in NEW:
            assert decl["workloads"] == [CELL] and decl["unit"] == "%"
            assert decl["layer"] == ("experts" if decl["name"].startswith("moe.") else "kernels")
            assert callable(spec.load_module("layer_metrics", decl["name"]).read)


# -- served and checked in the harness ---------------------------------------------------------

@pytest.fixture(scope="module")
def with_zaya(tmp_path_factory):
    """The rehearsal data with a tiny ``cca_moe`` configuration and a cell
    ADDED, and a second one whose reference drops the value shift."""
    data = tmp_path_factory.mktemp("data") / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    manifest = spec.load_json(str(data / "BENCHMARK.json"))
    os.makedirs(data / "architectures")
    (data / "architectures" / "cca_moe_wrong.py").write_text(WRONG)
    for name, arch in (("tiny-zaya", "cca_moe"), ("tiny-wrong", "cca_moe_wrong")):
        (data / f"{name}.json").write_text(json.dumps(dict(TINY_CFG, architecture=arch)))
        shutil.copy(data / "cells" / "tiny.open.json", data / "cells" / f"{name}.open.json")
        manifest["configs"].append({"name": name, "source": "tests", "file": f"{name}.json",
                                    "reduced": [], "why": "ZAYA1's block at a test shape"})
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


def test_a_tiny_cca_moe_configuration_is_served_and_correct(with_zaya):
    """Batched prefill in padded buckets, one prompt above the top bucket
    (chunked from a carried tail), the pool and the solo fallback: every
    served token is the reference's best."""
    result = _rehearse(with_zaya, "tiny-zaya.open")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert result["check"][0]["agree_share"] == 1.0


def test_a_reference_without_the_value_shift_calls_the_same_serving_not_correct(with_zaya):
    result = _rehearse(with_zaya, "tiny-wrong.open")
    assert result["correct"] is False and result["failed"] == 0


def test_the_parents_program_refuses_the_architecture_cleanly(monkeypatch):
    """A program with no feed-forward kind cannot serve it: ``register``
    says so (``run.py`` exits 3) before anything is built."""
    import gofr_tpu.models.transformer as T

    fields = dict(T.TransformerConfig.__dataclass_fields__)
    del fields["ffn_kind"]
    monkeypatch.setattr(T.TransformerConfig, "__dataclass_fields__", fields)
    cfg = spec.load_config(MANIFEST, "zaya1-8b-bf16")
    run = types.SimpleNamespace(cfg=cfg, sizes=ARCH.sizes_of(cfg), seed=1, log=print)
    with pytest.raises(spec.SpecError, match="no routed experts"):
        ARCH.register(run)


@pytest.mark.parametrize("key,value", [("cca_time0", 3), ("num_experts_per_tok", 2),
                                       ("tie_word_embeddings", False)])
def test_a_configuration_the_module_is_not_written_for_is_refused(key, value):
    with pytest.raises(spec.SpecError):
        ARCH.sizes_of(dict(TINY_CFG, **{key: value}))


# -- the work sheets against hand counts at the tiny shape ----------------------------------------

TINY = ARCH.sizes_of(TINY_CFG)
MATMUL = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 16 + 2 * 16 * 16 + 16 * 4 + 2 * 6 * 16 * 16  # 16960
OTHER = 4 * 96 + 2 * 64 + 5 * 16 + 2  # 594
HEAD = 256 * 64
EXPERT = 3 * 64 * 32  # one expert's weights


def _run(reads=(5, 9), tokens=(12, 36), most=(9, 20), **env):
    """Two chunks of 4 steps over 3 layers (1 and 3 live rows), a prefill of
    two rows and a slice of one."""
    chunk = {"kind": "decode_chunk", "status": "ok"}
    rec = lambda t0, n, times: {"n_prompt": n, "times": [t0 + 0.5 * j for j in range(times)]}  # noqa: E731
    return types.SimpleNamespace(
        sizes=TINY, server_env={"DECODE_CHUNK": "4", "DECODE_SLOTS": "6", **env}, w0=0.0, w1=10.0,
        records=[rec(1.0, 10, 5), rec(2.0, 20, 3)],
        dispatches=[dict(chunk, batch_size=1, expert_tokens=tokens[0], experts_read=reads[0],
                         expert_tokens_max=most[0]),
                    dict(chunk, batch_size=3, expert_tokens=tokens[1], experts_read=reads[1],
                         expert_tokens_max=most[1]),
                    {"kind": "prefill", "status": "ok", "batch_size": 2, "bucket": 16,
                     "padded_tokens": 22, "tokens": 10, "expert_tokens": 30, "experts_read": 10,
                     "expert_tokens_max": 14},
                    {"kind": "prefill_chunk", "status": "ok", "batch_size": 1, "bucket": 32,
                     "padded_tokens": 0, "tokens": 30, "expert_tokens": 90, "experts_read": 12,
                     "expert_tokens_max": 40}])


def test_the_expert_sheet_counts_an_expert_read_once_and_a_token_once():
    sheet = spec.load_module("kernels", "moe_experts")
    assert sheet.expert_bytes(TINY) == 2 * EXPERT and sheet.token_flops(TINY) == 2 * EXPERT
    run = _run()
    assert sheet.mean_work(run, ("decode_chunk",)) == (2 * EXPERT * 24, 2 * EXPERT * 7)
    assert sheet.mean_work(run, ("prefill", "prefill_chunk")) == (2 * EXPERT * 60, 2 * EXPERT * 11)
    assert sheet.work(run, 3, 2) == (2 * EXPERT * (3 * 24 + 2 * 60), 2 * EXPERT * (3 * 7 + 2 * 11))
    bare = _run()
    for d in bare.dispatches:
        for key in ("expert_tokens", "experts_read", "expert_tokens_max"):
            d.pop(key)
    assert sheet.work(bare, 3, 2) == (0.0, 0.0)  # a program that counts nothing: nothing to read


def test_the_decode_sheet_counts_what_a_step_must_move():
    sheet = spec.load_module("kernels", "cca_moe_decode_step")
    assert sheet.dense_params(TINY) == (MATMUL, OTHER) == (16960, 594)
    assert sheet.tail_values(TINY) == 2 * 96 + 16
    run = _run()
    assert sheet.kv_bytes_per_token(run) == 2 * 3 * 2 * 16 * 2
    assert sheet.kv_bytes_per_token(_run(MODEL_KV_DTYPE="f8")) == 2 * 3 * 2 * 16
    # live tokens: (10 + 1..4) x 0.5 s and (20 + 1..2) x 0.5 s over a window of 10 s
    live = (0.5 * (11 + 12 + 13 + 14) + 0.5 * (21 + 22)) / 10.0
    flops, nbytes = sheet.step_work(run)
    # 2 live rows on average; the experts a step reads and the tokens it routes: means a chunk / 4
    assert nbytes == pytest.approx(
        2 * (3 * (MATMUL + OTHER) + HEAD) + 2 * EXPERT * 7 / 4 + 384 * live + 2 * 3 * 208 * 4)
    assert flops == pytest.approx(
        2 * 2 * (3 * MATMUL + HEAD) + 2 * EXPERT * 24 / 4 + 4 * 16 * 4 * 3 * live)
    assert sheet.work(run, 3) == (pytest.approx(12 * flops), pytest.approx(12 * nbytes))


def test_a_traced_run_is_held_to_the_routing_of_the_steps_its_trace_holds():
    """The profiler runs ``trace_s`` seconds from one second into the window;
    the live rows still grow then, so the window's mean routing would be set
    against the time of steps that read fewer experts."""
    sheet = spec.load_module("kernels", "moe_experts")
    decode = spec.load_module("kernels", "cca_moe_decode_step")
    run = _run()
    run.trace, run.mix, run.wall0 = {}, {"trace_s": 2.0}, 100.0
    for d, at in zip(run.dispatches, (100.5, 102.0, 100.2, 104.0)):
        d["start_ts"] = at  # the profiler held [101, 103): the second chunk alone
    assert sheet.traced_span(run) == (1.0, 3.0)
    assert sheet.mean_work(run, ("decode_chunk",), traced=True) == (2 * EXPERT * 36, 2 * EXPERT * 9)
    assert sheet.mean_work(run, ("decode_chunk",)) == (2 * EXPERT * 24, 2 * EXPERT * 7)
    live = (0.5 * (11 + 12 + 13 + 14) + 0.5 * (21 + 22)) / 2.0  # all of it falls in [1, 3) s
    flops, nbytes = decode.step_work(run)
    assert nbytes == pytest.approx(
        2 * (3 * (MATMUL + OTHER) + HEAD) + 2 * EXPERT * 9 / 4 + 384 * live + 3 * 3 * 208 * 4)
    assert flops == pytest.approx(
        2 * 3 * (3 * MATMUL + HEAD) + 2 * EXPERT * 36 / 4 + 4 * 16 * 4 * 3 * live)
    for d in run.dispatches:
        d["start_ts"] = 50.0  # none began under the profiler: the window's mean
    assert sheet.mean_work(run, ("decode_chunk",), traced=True) == (2 * EXPERT * 24, 2 * EXPERT * 7)
    run.trace = None  # an untraced run
    assert sheet.traced_span(run) is None


def test_the_prefill_sheet_counts_real_tokens_and_the_experts_read():
    sheet = spec.load_module("kernels", "cca_moe_prefill_step")
    flops, nbytes = sheet.work(_run(), 2)
    attn = lambda n: 4 * 16 * 4 * 3 * n * n / 2  # noqa: E731
    per = lambda tokens, rows: (2 * 3 * MATMUL * tokens + 2 * HEAD * rows  # noqa: E731
                                + attn(tokens / rows) * rows)
    assert flops == pytest.approx(2 * ((per(10, 2) + per(30, 1)) / 2 + 2 * EXPERT * 60))
    assert nbytes == pytest.approx(2 * (2 * (3 * (MATMUL + OTHER) + HEAD) + 2 * EXPERT * 11))


# -- the readers on a small fixture ------------------------------------------------------------------

POOL, PREFILL = "jit__lambda(7)", "jit__prefill_fn(3)"


def _traced(run, pooled_s, prefill_s, experts_s):
    run.peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}
    run.trace = {
        "programs": {POOL: {"seconds": pooled_s, "runs": 3}, PREFILL: {"seconds": prefill_s, "runs": 2}},
        "released": {"gofr.pool.fetch_wait": {POOL: 3}},
        # a pooled step's product writes [slots, width]; a prefill's, a row a token
        "device_ops": [["moe_experts_gated.10 bf16[6,64] custom-call", 0.6 * experts_s],
                       ["moe_experts_down.10 bf16[6,64] custom-call", 0.4 * experts_s],
                       ["moe_experts_gated.10 bf16[32,64] custom-call", 0.5], ["fusion.1", 1.0]],
    }
    return run


def _read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_the_counter_readers_read_the_decode_chunks():
    run = _run()
    assert _read("moe.experts_read_share", run) == pytest.approx(100 * 14 / (4 * 3 * 8))
    assert _read("moe.load_max_share", run) == pytest.approx(100 * 29 / 48)
    bare = _run()
    for d in bare.dispatches:
        for key in ("expert_tokens", "experts_read", "expert_tokens_max"):
            d.pop(key)
    assert _read("moe.experts_read_share", bare) is None
    assert _read("moe.load_max_share", bare) is None


def test_the_trace_readers_divide_the_sheets_by_the_traced_time():
    run = _traced(_run(), pooled_s=0.05, prefill_s=0.02, experts_s=0.01)
    decode = spec.load_module("kernels", "cca_moe_decode_step").work(run, 3)
    prefill = spec.load_module("kernels", "cca_moe_prefill_step").work(run, 2)
    experts = spec.load_module("kernels", "moe_experts").work(run, 3, 0)  # the pooled program's alone
    least = lambda w: max(w[0] / 1e9, w[1] / 1e8)  # noqa: E731
    assert _read("kernel.moe.decode_step_roofline", run) == pytest.approx(100 * least(decode) / 0.05)
    assert _read("kernel.moe.decode_step_mfu", run) == pytest.approx(100 * decode[0] / 1e9 / 0.05)
    assert _read("kernel.moe.prefill_step_roofline", run) == pytest.approx(100 * least(prefill) / 0.02)
    assert _read("kernel.moe.prefill_step_mfu", run) == pytest.approx(100 * prefill[0] / 1e9 / 0.02)
    assert _read("kernel.moe.experts_roofline", run) == pytest.approx(100 * least(experts) / 0.01)
    # no trace, no pooled program, or the kernels not among the largest operations: nothing
    run.trace["device_ops"] = [["fusion.1", 1.0]]
    assert _read("kernel.moe.experts_roofline", run) is None
    run.trace["released"] = {}
    assert _read("kernel.moe.decode_step_roofline", run) is None
    run.trace = None
    assert all(_read(name, run) is None for name in NEW[:5])


@pytest.mark.parametrize("per_layer_step", [1, 16], ids=["one_expert", "all_sixteen"])
def test_no_share_reads_over_100_whether_a_step_reads_one_expert_or_all(per_layer_step):
    """At the published sizes, a program that runs AT the chip's peaks: its
    time is what the sheet says it must move over the HBM peak (or its
    FLOPs over the bf16 peak, whichever is larger). Every share then reads
    100 at most, and the decode ones exactly 100: the sheet counts the same
    work whatever the routing."""
    cfg = spec.load_config(MANIFEST, "zaya1-8b-bf16")
    sz = ARCH.sizes_of(cfg)
    peaks = spec.load_json(os.path.join(spec.HERE, "peaks.json"))["TPU v5 lite"]
    rows = 1 if per_layer_step == 1 else 32
    chunk = {"kind": "decode_chunk", "status": "ok", "batch_size": rows,
             "expert_tokens": rows * 20 * 8, "experts_read": per_layer_step * 20 * 8,
             "expert_tokens_max": max(rows // per_layer_step, 1) * 20 * 8}
    slice_ = {"kind": "prefill_chunk", "status": "ok", "batch_size": 1, "bucket": 256,
              "padded_tokens": 0, "tokens": 256, "expert_tokens": 256 * 20,
              "experts_read": per_layer_step * 20, "expert_tokens_max": 256 * 20 // per_layer_step}
    run = types.SimpleNamespace(
        sizes=sz, server_env={"DECODE_CHUNK": "8", "DECODE_SLOTS": "32"}, w0=0.0, w1=10.0, peaks=peaks,
        records=[{"n_prompt": 200, "times": [0.0, 10.0]}] * rows, dispatches=[chunk, slice_])
    least = lambda w: max(w[0] / peaks["bf16_flops_per_s"], w[1] / peaks["hbm_bytes_per_s"])  # noqa: E731
    decode = least(spec.load_module("kernels", "cca_moe_decode_step").work(run, 5))
    prefill = least(spec.load_module("kernels", "cca_moe_prefill_step").work(run, 4))
    experts = least(spec.load_module("kernels", "moe_experts").work(run, 5, 0))
    assert experts < decode  # the product is a part of the pooled program
    run.trace = {
        "programs": {POOL: {"seconds": decode, "runs": 5}, PREFILL: {"seconds": prefill, "runs": 4}},
        "released": {"gofr.pool.fetch_wait": {POOL: 5}},
        "device_ops": [["moe_experts_gated.10 bf16[32,2048] custom-call", 0.7 * experts],
                       ["moe_experts_down.10 bf16[32,2048] custom-call", 0.3 * experts],
                       ["moe_experts_gated.10 bf16[512,2048] custom-call", 0.01]],
    }
    shares = {name: _read(name, run) for name in NEW}
    assert all(value is not None and 0 < value <= 100.0 + 1e-9 for value in shares.values()), shares
    assert shares["kernel.moe.decode_step_roofline"] == pytest.approx(100.0)
    assert shares["kernel.moe.prefill_step_roofline"] == pytest.approx(100.0)
    assert shares["kernel.moe.experts_roofline"] == pytest.approx(100.0)
    assert shares["moe.experts_read_share"] == pytest.approx(100.0 * per_layer_step / 16)
    # the bytes of a step at one expert a layer and at sixteen, as the issue counts them
    step = spec.load_module("kernels", "cca_moe_decode_step").step_work(run)[1]
    weights = 2 * (20 * (6240514 + per_layer_step * 12582912) + 262272 * 2048)
    assert step == pytest.approx(weights + rows * (201 * 20 * 1024 + 20 * 2688 * 4))
