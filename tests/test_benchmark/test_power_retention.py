"""The ``power_retention`` architecture in the harness: a tiny configuration
added to a copy of the rehearsal data is served and checked by its own plain
reference (and comes out not correct against ``dense_gqa``'s), and the
retention work sheets against hand counts at the tiny shape (its cell's
metric lists: ``test_manifest_floors.py``). No chip."""

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

WRONG = '''"""power_retention's weights and seam, checked by the dense decoder's reference."""
from benchmark import spec

_own = spec.load_module("architectures", "power_retention")
sizes_of, make_params, register = _own.sizes_of, _own.make_params, _own.register
logits_at = spec.load_module("architectures", "dense_gqa").logits_at
'''


@pytest.fixture(scope="module")
def with_retention(tmp_path_factory):
    """The rehearsal data with a tiny retention configuration and a cell
    ADDED, and a second one whose architecture module borrows the dense
    decoder's reference."""
    data = tmp_path_factory.mktemp("data") / "rehearse"
    shutil.copytree(REHEARSE_DIR, data)
    manifest = spec.load_json(str(data / "BENCHMARK.json"))
    os.makedirs(data / "architectures")
    (data / "architectures" / "retention_wrong.py").write_text(WRONG)
    for name, arch in (("tiny-retention", "power_retention"), ("tiny-wrong", "retention_wrong")):
        cfg = spec.load_json(str(data / "tiny-rehearse.json"))
        cfg.update(architecture=arch, rms_norm_eps=1e-6)
        (data / f"{name}.json").write_text(json.dumps(cfg))
        shutil.copy(data / "cells" / "tiny.open.json", data / "cells" / f"{name}.open.json")
        manifest["configs"].append({"name": name, "source": "tests", "file": f"{name}.json",
                                    "reduced": [], "why": "a retention model at a test shape"})
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


def test_a_tiny_retention_configuration_is_served_and_correct(with_retention):
    """Batched prefill in padded buckets, one prompt above the top bucket
    (chunked from a carried state), the pool and the solo fallback: every
    served token is the reference's best."""
    result = _rehearse(with_retention, "tiny-retention.open")
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert result["check"][0]["agree_share"] == 1.0


def test_the_dense_decoders_reference_calls_the_same_serving_not_correct(with_retention):
    result = _rehearse(with_retention, "tiny-wrong.open")
    assert result["correct"] is False and result["failed"] == 0


def test_the_parents_program_refuses_the_architecture_cleanly(monkeypatch):
    """A program with no attention kind cannot serve it: ``register`` says
    so (``run.py`` exits 3) before anything is built."""
    import gofr_tpu.models.transformer as T

    arch = spec.load_module("architectures", "power_retention")
    fields = dict(T.TransformerConfig.__dataclass_fields__)
    del fields["attn_kind"]
    monkeypatch.setattr(T.TransformerConfig, "__dataclass_fields__", fields)
    cfg = spec.load_config(MANIFEST, "brumby-14b-base-bf16")
    run = types.SimpleNamespace(cfg=cfg, sizes=arch.sizes_of(cfg), seed=1, log=print)
    with pytest.raises(spec.SpecError, match="no attention kind"):
        arch.register(run)


def test_the_brumby_configuration_keeps_every_published_number():
    cfg = spec.load_config(MANIFEST, "brumby-14b-base-bf16")
    published = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
                 "hidden_size": 5120, "intermediate_size": 17408,
                 "max_position_embeddings": 32768, "max_window_layers": 40,
                 "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
                 "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
                 "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and set(cfg["reduced"]) == differs | {"max_position_embeddings"}
    assert int(cfg["serving"]["env"]["MODEL_MAX_SEQ"]) >= 12288 + 768
    assert cfg["num_hidden_layers"] == 8 and cfg["published"]["num_hidden_layers"] == 40
    mix = spec.load_mix(MANIFEST, "longdoc-steady")
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert max(mix["check"]["widths"]) >= longest == 12288 + 768
    assert mix["check"]["scored"] >= mix["check"]["rows"] * mix["output_tokens"]["max"]


# -- the work sheets against hand counts at the tiny shape ----------------------------------------

TINY = {"dim": 64, "layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 16, "ffn": 128,
        "vocab": 256, "quant": "", "dtype": "float32", "phi": 144}


def _run(**env):
    chunk = {"kind": "decode_chunk", "status": "ok"}
    return types.SimpleNamespace(
        sizes=TINY, server_env={"DECODE_CHUNK": "4", **env},
        dispatches=[dict(chunk, batch_size=1, state_bytes=313344),
                    dict(chunk, batch_size=3, state_bytes=940032),
                    {"kind": "prefill", "status": "ok", "batch_size": 2, "bucket": 16,
                     "padded_tokens": 22, "tokens": 10},
                    {"kind": "prefill_chunk", "status": "ok", "batch_size": 1, "bucket": 32,
                     "padded_tokens": 0, "tokens": 30}])


def test_the_decode_sheet_counts_weights_once_and_the_live_rows_state_twice():
    sheet = spec.load_module("kernels", "retention_decode_step")
    run = _run()
    # one row, one layer: 2 kv heads x (16 + 1) x 144 x 4 bytes
    assert sheet.state_row_bytes(run) == 19584
    assert sheet.state_row_bytes(_run(MODEL_KV_DTYPE="bf16")) == 9792
    # the pool's own count: rows x layers x 2 x row x steps
    assert run.dispatches[0]["state_bytes"] == 1 * 2 * 2 * 19584 * 4
    flops, weights, state = sheet.step_work(run)
    # weights: 2 layers x (64x64 + 2x64x32 + 64x64 + 3x64x128) + head 64x256, bf16; gate 2x64x2
    assert weights == 2 * (2 * 36864 + 16384) + 2 * 2 * 64 * 2 == 180736
    assert state == 2 * 2 * 2 * 19584  # mean 2 live rows x 2 layers x read and write
    # 2 x weights x 2 rows, and per row, layer, kv head: 2 x 17 x 144 x (1 update + 2 query heads)
    assert flops == 2 * 73728 * 2 + 2 * 16384 * 2 + 2 * 2 * 2 * 14688
    assert sheet.work(run, 3) == (12 * flops, 12 * (weights + state))


def test_the_prefill_sheet_counts_real_tokens_only():
    sheet = spec.load_module("kernels", "retention_prefill_step")
    # per token and layer: state 2 x 17 x 144 x (4 + 2 heads), inside 4 x 16 x 4 x 64
    assert sheet.retention_flops(TINY, 10) == 2 * 10 * (29376 + 16384)
    flops, nbytes = sheet.work(_run(), 2)
    per = lambda tokens, rows: (2 * 73728 * tokens + 2 * 16384 * rows  # noqa: E731
                                + sheet.retention_flops(TINY, tokens))
    assert flops == 2 * (per(10, 2) + per(30, 1)) / 2 and nbytes == 2 * 180224


def test_state_move_share_and_insert_read_the_programs_counters():
    run = _run()
    run.flights = [{"status": "ok", "state_insert_s": 0.002}, {"status": "ok", "state_insert_s": 0.004},
                   {"status": "ok"}]
    share = spec.load_module("layer_metrics", "state.move_share").read(run)
    state = 313344 + 940032
    assert share == pytest.approx(100 * state / (state + 2 * 4 * 180736))
    assert spec.load_module("layer_metrics", "state.insert_p50_ms").read(run) == pytest.approx(3.0)
    # a program that stamps neither (the parent) gives nothing to read
    bare = _run()
    for d in bare.dispatches:
        d.pop("state_bytes", None)
    bare.flights = [{"status": "ok"}]
    assert spec.load_module("layer_metrics", "state.move_share").read(bare) is None
    assert spec.load_module("layer_metrics", "state.insert_p50_ms").read(bare) is None
