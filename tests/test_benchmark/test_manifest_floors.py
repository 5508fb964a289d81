"""Which cell reports which metric, written out once, as floors. A PR that
adds a cell, a configuration or a per-layer metric adds entries and files
and edits nothing here: a name written here that a cell no longer reports,
or a cell written here that the manifest no longer has, fails; a name or a
cell appended passes. A cell's end-to-end names alone are compared whole,
since an existing cell's never change by addition. A new cell's own
expectations go into a test file of its own. No chip."""

import copy

import pytest

from benchmark import spec

MANIFEST = spec.load_manifest()

STEADY = [
    "client.ttft_p50_ms", "client.ttft_p90_ms", "client.tpot_p50_ms", "client.tpot_p90_ms",
    "client.late_p99_ms", "client.frame_gap_p99_ms", "client.stall_max_ms.steady",
    "batcher.queue_wait_p50_ms", "batcher.pad_share", "sched.defer_p90_ms",
    "pool.chunk_rows_mean.steady", "pool.reject_share.steady", "step.prefill_p50_ms",
    "step.decode_chunk_p50_ms.steady", "device.idle_share.steady", "device.hbm_peak_gb.steady",
    "step.decode_chunk_cadence_p50_ms.steady", "step.prefill_chunks_ahead_mean",
    "step.prefill_issue_p50_ms", "pool.host_share.steady", "pool.admit_p50_ms",
    "request.parse_p50_ms", "request.first_frame_p50_ms", "request.server_ttft_mean_ms",
]
DENSE = ["kernel.steady.decode_step_roofline", "kernel.prefill_step_roofline",
         "kernel.decode_kv_read_share", "kernel.steady.decode_step_mfu", "kernel.prefill_step_mfu"]
RETENTION = [
    "kernel.retention.decode_step_roofline", "kernel.retention.prefill_step_roofline",
    "kernel.retention.step_roofline", "kernel.retention.chunk_roofline", "state.move_share",
    "state.insert_p50_ms", "kernel.retention.decode_step_mfu", "kernel.retention.prefill_step_mfu",
]
SATURATED = [
    "pool.chunk_rows_mean.saturated", "pool.reject_share.saturated",
    "client.stall_max_ms.saturated", "step.decode_chunk_p50_ms.saturated",
    "kernel.saturated.decode_step_roofline", "device.idle_share.saturated",
    "device.hbm_peak_gb.saturated", "step.decode_chunk_cadence_p50_ms.saturated",
    "step.solo_chunk_p50_ms.saturated", "pool.host_share.saturated",
    "kernel.decode_kv_read_share.saturated", "kernel.saturated.decode_step_mfu",
]
OPEN_LOOP = ["ttft_mean_ms", "tpot_mean_ms", "setup_s"]
# cell -> (its end-to-end names, whole and in order; per-layer names it reports at least)
CELLS = {
    "mistral-7b-int8.chat-steady": (OPEN_LOOP, STEADY + DENSE),
    "internlm2-1.8b-bf16.chat-steady": (OPEN_LOOP, STEADY + DENSE),
    "mistral-7b-int8.chat-saturated": (["out_tok_s", "setup_s"], SATURATED),
    "mistral-7b-int8.docqa-steady": (OPEN_LOOP, STEADY + DENSE),
    "brumby-14b-bf16.longdoc-steady": (OPEN_LOOP, STEADY + RETENTION),
}


def _names(manifest, cell, section):
    return [m["name"] for m in spec.metrics_of_cell(manifest, cell, section)]


def floor_faults(manifest, cells=CELLS):
    """What ``manifest`` has lost of what ``cells`` writes out, one line a
    fault; nothing for a manifest that only has more."""
    faults = []
    have = {c["name"] for c in manifest["workloads"]}
    for cell, (end_to_end, per_layer) in sorted(cells.items()):
        if cell not in have:
            faults.append(f"{cell}: no longer a cell of the manifest")
            continue
        reported = _names(manifest, cell, "end_to_end")
        if reported != end_to_end:
            faults.append(f"{cell}: end-to-end {reported}, written here {end_to_end}")
        missing = sorted(set(per_layer) - set(_names(manifest, cell, "per_layer")))
        if missing:
            faults.append(f"{cell}: no longer reports {', '.join(missing)}")
    return faults


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_end_to_end_names_are_the_ones_written_out(cell):
    assert _names(MANIFEST, cell, "end_to_end") == CELLS[cell][0]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_reports_every_per_layer_name_written_out(cell):
    assert not floor_faults(MANIFEST, {cell: CELLS[cell]})
    reported = _names(MANIFEST, cell, "per_layer")
    assert len(reported) == len(set(reported))


# -- the room: what a later PR may add, and what still fails ---------------------------------

def _one_more_of_each(manifest):
    """A configuration, a cell of it (its name appended where an open-loop
    dense cell reports) and a per-layer metric that lists a cell that is
    there: the entries a ``model_config`` PR brings."""
    manifest["configs"].append({"name": "room-8b", "source": "tests", "reduced": ["vocab_size"],
                                "file": "benchmark/configs/room-8b.json", "why": "a sixth cell's"})
    manifest["workloads"].append({"name": "room-8b.room-test", "config": "room-8b",
                                  "traffic": "room-test", "chips": 1, "why": "a sixth cell"})
    appended = {"ttft_mean_ms", "tpot_mean_ms", "client.ttft_p50_ms", "pool.chunk_rows_mean.steady",
                "kernel.decode_kv_read_share"}
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if metric["name"] in appended:
            metric["workloads"].append("room-8b.room-test")
    manifest["per_layer"].append({
        "name": "kernel.room-test.expert_matmul_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "tpot_mean_ms",
        "workloads": ["mistral-7b-int8.chat-steady", "room-8b.room-test"]})


def _a_name_dropped(manifest):
    metric = next(m for m in manifest["per_layer"] if m["name"] == "batcher.pad_share")
    metric["workloads"].remove("mistral-7b-int8.docqa-steady")


def _a_metric_dropped(manifest):
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] != "kernel.decode_kv_read_share.saturated"]


def _a_cell_removed(manifest):
    manifest["workloads"] = [c for c in manifest["workloads"]
                             if c["name"] != "brumby-14b-bf16.longdoc-steady"]


def _an_end_to_end_name_appended(manifest):
    metric = next(m for m in manifest["end_to_end"] if m["name"] == "out_tok_s")
    metric["workloads"].append("mistral-7b-int8.chat-steady")


@pytest.mark.parametrize("edit,fault", [
    (None, None),
    (_one_more_of_each, None),
    (_a_name_dropped, "mistral-7b-int8.docqa-steady: no longer reports batcher.pad_share"),
    (_a_metric_dropped, "mistral-7b-int8.chat-saturated: no longer reports kernel.decode_kv_read_share.saturated"),
    (_a_cell_removed, "brumby-14b-bf16.longdoc-steady: no longer a cell of the manifest"),
    (_an_end_to_end_name_appended, "mistral-7b-int8.chat-steady: end-to-end"),
], ids=["as-it-is", "one-more-of-each", "a-name-dropped", "a-metric-dropped", "a-cell-removed",
        "an-end-to-end-name-appended"])
def test_additions_pass_the_floors_and_removals_fail_them(edit, fault):
    manifest = copy.deepcopy(MANIFEST)
    if edit is not None:
        edit(manifest)
    faults = floor_faults(manifest)
    if fault is None:
        assert faults == []
        return
    assert len(faults) == 1 and faults[0].startswith(fault)
    assert floor_faults(MANIFEST) == [], "the edit went to the copy alone"


def test_the_added_cell_reports_what_it_appended_its_name_to():
    """An appended cell inherits nothing: it reports the metrics that list
    it, and an existing cell gains the metric that lists it."""
    manifest = copy.deepcopy(MANIFEST)
    _one_more_of_each(manifest)
    assert _names(manifest, "room-8b.room-test", "end_to_end") == OPEN_LOOP
    assert _names(manifest, "room-8b.room-test", "per_layer") == [
        "client.ttft_p50_ms", "pool.chunk_rows_mean.steady", "kernel.decode_kv_read_share",
        "kernel.room-test.expert_matmul_roofline"]
    base = _names(manifest, "mistral-7b-int8.chat-steady", "per_layer")
    assert base == _names(MANIFEST, "mistral-7b-int8.chat-steady", "per_layer") + [
        "kernel.room-test.expert_matmul_roofline"]
