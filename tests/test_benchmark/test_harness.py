"""The benchmark's manifest, data files, traffic generator and arithmetic,
and one CPU rehearsal of the whole command. No chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import metrics as M
from benchmark import model_work as mw
from benchmark import spec
from benchmark.traffic import build_schedule, quantile_lengths

MANIFEST = spec.load_manifest()
READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
CELLS = [c["name"] for c in MANIFEST["workloads"]]
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_has_exactly_the_contract_keys():
    keys = set(MANIFEST) - {"_dir"}
    assert keys == {"command", "paths", "run_seconds", "configs", "workloads",
                    "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    four = sum(1 for c in MANIFEST["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_resolves(cell):
    entry = spec.find_cell(MANIFEST, cell)
    cfg = spec.load_config(MANIFEST, entry["config"])
    mix = spec.load_mix(MANIFEST, entry["traffic"])
    load = spec.load_cell_load(MANIFEST, cell)
    assert cfg["serving"]["env"] and mix["loop"] in ("open", "closed")
    assert ("rate_rps" in load) == (mix["loop"] == "open")
    assert 0 < load["check"]["served_gap_mean_limit"] < load["check"]["served_gap_max_limit"]
    for section, folder in READER_DIRS.items():
        for decl in spec.metrics_of_cell(MANIFEST, cell, section):
            assert callable(spec.load_module(folder, decl["name"]).read)


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda e: e["name"])
def test_configuration_keeps_published_sizes_and_says_what_it_cut(entry):
    path = entry["file"]
    assert any(path.startswith(p + "/") for p in MANIFEST["paths"])
    cfg = spec.load_json(os.path.join(spec.ROOT, path))
    assert cfg["source"] == entry["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    for key in entry["reduced"]:  # never a width; a vocabulary is a count of rows, and may be a slice
        assert key == "vocab_size" or not key.endswith(("_dim", "_rank", "_size"))
        assert "head" not in key
    assert any(c["config"] == entry["name"] for c in MANIFEST["workloads"])


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_names_and_units_use_the_allowed_characters(metric):
    assert spec.NAME_RE.match(metric["name"]) and spec.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.1
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_no_two_entries_share_a_name():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[section]]
        assert len(names) == len(set(names))
        assert all(spec.NAME_RE.match(n) for n in names)
    names = [m["name"] for m in ALL_METRICS]
    assert len(names) == len(set(names))
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(len(c["why"]) <= 200 and "\n" not in c["why"] for c in MANIFEST["workloads"])


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_moves_is_an_end_to_end_metric_of_every_cell_that_reports_it(metric):
    reporting = [c for c in CELLS if any(
        m["name"] == metric["name"] for m in spec.metrics_of_cell(MANIFEST, c, "per_layer"))]
    assert reporting, "no cell reports this metric"
    for cell in reporting:
        e2e = {m["name"] for m in spec.metrics_of_cell(MANIFEST, cell, "end_to_end")}
        assert metric["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    e2e = {m["name"] for m in spec.metrics_of_cell(MANIFEST, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_of_cell(MANIFEST, cell, "per_layer")


CHAT = {
    "loop": "open", "ramp_s": 2.0,
    "prompt_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.9, "min": 16, "max": 1024},
    "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 8, "max": 256},
}


def test_two_seeds_offer_the_same_schedule_with_other_tokens():
    a = build_schedule(CHAT, {"rate_rps": 3.0}, 32768, 7, 40.0)
    b = build_schedule(CHAT, {"rate_rps": 3.0}, 32768, 2**31 + 12345, 40.0)

    def shape(s):
        return [(r["due"], len(r["prompt"]), r["max_tokens"], r["measured"]) for r in s["requests"]]

    assert shape(a) == shape(b), "the seed must not move the work"
    assert [r["prompt"] for r in a["requests"]] != [r["prompt"] for r in b["requests"]]
    assert a["requests"] == build_schedule(CHAT, {"rate_rps": 3.0}, 32768, 7, 40.0)["requests"]
    window = [r for r in a["requests"] if r["measured"]]
    assert len(window) == 120
    lens = sorted(len(r["prompt"]) for r in window)
    assert lens == sorted(quantile_lengths(CHAT["prompt_tokens"], 120)) and lens != [len(r["prompt"]) for r in window]
    dues = [r["due"] for r in a["requests"]]
    assert dues == sorted(dues) and dues[0] >= 0.0
    assert 2.0 <= window[0]["due"] and window[-1]["due"] <= 42.0
    ramp = [r for r in a["requests"] if not r["measured"]]
    assert 2 <= len(ramp) <= 12 and all(r["due"] < window[0]["due"] for r in ramp)
    # no key of a mix picks another order: the order is a constant of the generator
    assert shape(build_schedule(dict(CHAT, order_seed=1), {"rate_rps": 3.0}, 32768, 7, 40.0)) == shape(a)
    assert all(3 <= t < 32768 for r in a["requests"] for t in r["prompt"])


def test_stratified_lengths_follow_the_stated_distribution():
    lens = quantile_lengths(CHAT["prompt_tokens"], 1000)
    assert min(lens) == 16 and max(lens) == 1024
    assert sorted(lens)[500] in (191, 192, 193)
    assert quantile_lengths({"dist": "uniform", "min": 16, "max": 48}, 4) == [20, 28, 36, 44]


def test_closed_loop_schedule_has_clients_and_no_due_times():
    s = build_schedule(dict(CHAT, loop="closed"), {"clients": 16, "requests": 64}, 1000, 3, 40.0)
    assert s["clients"] == 16 and len(s["requests"]) == 64
    assert all(r["due"] == 0.0 for r in s["requests"])


def test_percentile_on_a_hand_made_sample():
    sample = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert M.percentile(sample, 50) == 30.0
    assert M.percentile(sample, 90) == pytest.approx(46.0)
    assert M.percentile(sample, 0) == 10.0 and M.percentile(sample, 100) == 50.0
    assert M.percentile([7.0], 90) == 7.0


def test_ttft_tpot_and_failure_arithmetic():
    rec = {"due": 100.0, "sent": 100.002, "times": [100.25, 100.25, 100.35, 100.55],
           "tokens": [5, 6, 7, 8], "asked": 4, "error": None, "done": 100.56, "n_prompt": 9}
    assert M.ttft_s(rec, 200.0) == pytest.approx(0.25)
    assert M.tpot_s(rec) == pytest.approx(0.1)
    assert M.late_s(rec) == pytest.approx(0.002)
    assert M.frame_gaps_s(rec) == pytest.approx([0.0, 0.1, 0.2])
    assert not M.is_failed(rec)
    assert M.is_failed(dict(rec, tokens=[5, 6, 7])) and M.is_failed(dict(rec, done=None))
    assert M.is_failed(dict(rec, error="503"))
    silent = dict(rec, times=[], tokens=[])
    assert M.ttft_s(silent, 130.0) == pytest.approx(30.0) and M.tpot_s(silent) is None
    assert M.tokens_in_window([rec], 100.3, 100.56) == 2
    # all decode time over all decode steps: (0.3 + 0.9) / (3 + 1)
    other = dict(rec, times=[101.0, 101.9], tokens=[1, 2], asked=2)
    assert M.tpot_mean_s([rec, other, silent]) == pytest.approx(0.3)
    assert M.tpot_mean_s([silent]) is None


def test_longest_silence_counts_only_gaps_with_a_request_in_flight():
    a = {"sent": 10.0, "done": 11.0, "times": [10.2, 10.4, 10.9]}
    b = {"sent": 13.0, "done": None, "times": [13.5]}
    # 10.4 -> 10.9 while a is in flight; 10.9 -> 13.5 is idle time, not a stall;
    # 13.5 -> 15.0 (the window's end) with b never done
    assert M.longest_silence_s([a, b], 10.0, 15.0) == pytest.approx(1.5)
    assert M.longest_silence_s([a], 10.0, 15.0) == pytest.approx(0.5)
    assert M.longest_silence_s([], 10.0, 15.0) is None


SHAPE = {"dim": 8, "layers": 2, "heads": 2, "kv_heads": 1, "head_dim": 4, "ffn": 16,
         "vocab": 32, "quant": "int8", "dtype": "bfloat16"}


def test_bytes_and_flops_against_a_hand_count():
    # per layer: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + three 8x16 = 576; head 8x32 = 256
    assert mw.matmul_params(SHAPE) == (2 * 576, 256)
    # int8: one byte a weight; float32 scales: per layer 8+4+4+8+16+16+8 = 64, head 32
    assert mw.weight_bytes(SHAPE) == 1152 + 256 + 4 * (2 * 64 + 32)
    assert mw.weight_bytes(dict(SHAPE, quant="")) == 2 * (1152 + 256)
    assert mw.kv_bytes_per_token(SHAPE) == 2 * 2 * 1 * 4 * 2
    assert mw.forward_flops(SHAPE, tokens=10, head_rows=2) == 2 * 1152 * 10 + 2 * 256 * 2
    # 4 x head_dim x heads x layers x (n x before + n^2 / 2), n = 6 after 10
    assert mw.causal_attention_flops(SHAPE, 6, 10) == 4 * 4 * 2 * 2 * (60 + 18)


def _rehearse(workload, *extra):
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--rehearse", "--workload", workload,
         "--seed", "2147483659", "--seconds", "3", *extra],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
    )
    return proc


@pytest.mark.parametrize("workload,trace", [("tiny.open", "0"), ("tiny.closed", "1")])
def test_rehearsal_of_the_whole_command_on_the_cpu(workload, trace):
    proc = _rehearse(workload, "--trace", trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, "stdout carries the result line and nothing else"
    result = json.loads(lines[0])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert result["rehearse"] is True and result["metrics"] == {}, "no metric from a CPU run"
    assert result["device"]["platform"] == "cpu" and "busy_s" not in result["device"]
    assert result["counts"]["flights"] > result["attempted"] // 2
    assert result["check"][0]["value"] <= result["check"][0]["limit"]


def test_the_real_command_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


BROKEN = """
import sys
import gofr_tpu.ops.sampling as S
real = S.sample_logits_rows
def altered(logits, *a, **k):  # a token altered where it is produced
    import jax.numpy as jnp
    return (real(logits, *a, **k) + 1) % logits.shape[-1]
S.sample_logits_rows = altered
sys.argv = ["benchmark.run", "--rehearse", "--workload", "tiny.open", "--seed", "77",
            "--seconds", "3", "--trace", "0"]
import runpy
runpy.run_module("benchmark.run", run_name="__main__")
"""


def test_a_broken_timed_path_comes_out_not_correct():
    """The harness minus its look for a chip, with the decode pool's sampler
    altered underneath: every pooled token is the one after the best."""
    proc = subprocess.run([sys.executable, "-c", BROKEN], cwd=spec.ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert any(n["value"] > n["limit"] for n in result["check"])
