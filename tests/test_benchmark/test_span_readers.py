"""The readers of the phase marks (benchmark/span_readers.py and the files
under layer_metrics/ that read them) on hand-built records, what
they do with records of a program that stamps no such field, and
tools/trace_spans.py on a hand-built trace. No chip, no server."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

from benchmark import spec


def _dispatch(kind, **fields):
    return dict({"kind": kind, "status": "ok", "duration_s": 0.8}, **fields)


def _flight(**fields):
    return dict({"status": "ok", "queue_wait_s": 0.005}, **fields)


DISPATCHES = [
    _dispatch("decode_chunk", issue_s=0.002, in_flight_s=0.5, fetch_wait_s=0.26, deliver_s=0.001,
              cadence_s=0.250, chunks_ahead=2),
    _dispatch("decode_chunk", issue_s=0.004, in_flight_s=0.5, fetch_wait_s=0.27, deliver_s=0.003,
              cadence_s=0.270, chunks_ahead=2),
    _dispatch("decode_chunk", issue_s=0.003, in_flight_s=0.5, fetch_wait_s=0.28, deliver_s=0.002,
              cadence_s=0.290, chunks_ahead=1),
    _dispatch("prefill", issue_s=0.006, in_flight_s=0.0, fetch_wait_s=0.7, deliver_s=0.001,
              chunks_ahead=3),
    _dispatch("prefill_chunk", issue_s=0.002, chunks_ahead=2),
    _dispatch("prefill", issue_s=0.004, in_flight_s=0.0, fetch_wait_s=0.6, deliver_s=0.001,
              chunks_ahead=1),
    _dispatch("decode_solo", issue_s=0.001, in_flight_s=0.1, fetch_wait_s=0.199, deliver_s=0.001,
              chunks_ahead=3),
    _dispatch("decode_solo", issue_s=0.001, in_flight_s=0.1, fetch_wait_s=0.299, deliver_s=0.001,
              chunks_ahead=3),
    _dispatch("decode_solo", issue_s=0.001, in_flight_s=0.1, fetch_wait_s=0.399, deliver_s=0.001,
              chunks_ahead=3),
    # neither an errored dispatch nor an abandoned solo chunk is read
    dict(_dispatch("decode_chunk", issue_s=9.0, deliver_s=9.0, cadence_s=9.0), status="error"),
    dict(_dispatch("decode_solo", issue_s=9.0), status="abandoned"),
]
FLIGHTS = [
    _flight(parse_s=0.001, first_frame_s=0.0004, pool_admit_s=0.003, server_ttft_s=0.700),
    _flight(parse_s=0.002, first_frame_s=0.0006, pool_admit_s=0.005, server_ttft_s=0.800),
    _flight(parse_s=0.003, first_frame_s=0.0008, pool_admit_s=0.004, server_ttft_s=0.900),
    _flight(parse_s=0.5),  # not streamed: no first frame, no server-side TTFT, never pooled
    dict(_flight(parse_s=9.0, first_frame_s=9.0, server_ttft_s=9.0), status="error"),
]
EXPECTED = {
    "step.decode_chunk_cadence_p50_ms.steady": 270.0,
    "step.decode_chunk_cadence_p50_ms.saturated": 270.0,
    "step.prefill_chunks_ahead_mean": 2.0,
    "step.prefill_issue_p50_ms": 4.0,
    "step.solo_chunk_p50_ms.saturated": 300.0,
    "pool.host_share.steady": 100.0 * 0.015 / 10.0,
    "pool.host_share.saturated": 100.0 * 0.015 / 10.0,
    "pool.admit_p50_ms": 4.0,
    "request.parse_p50_ms": 2.5,
    "request.first_frame_p50_ms": 0.6,
    "request.server_ttft_mean_ms": 800.0,
}


def _run(dispatches, flights):
    return SimpleNamespace(dispatches=dispatches, flights=flights, seconds=10.0)


def test_the_manifest_lists_these_readers_each_with_its_cells():
    """A ``.saturated`` reader is one whose cells are judged by throughput
    (each reports ``out_tok_s``), however many such cells there are."""
    manifest = spec.load_manifest()
    declared = {m["name"]: m for m in manifest["per_layer"]}
    out_tok_s = next(m for m in manifest["end_to_end"] if m["name"] == "out_tok_s")
    assert set(EXPECTED) <= set(declared)
    for name in EXPECTED:
        assert declared[name]["workloads"], name
        assert declared[name]["source"] in ("program_span", "program_counter")
        saturated = all(cell in out_tok_s["workloads"] for cell in declared[name]["workloads"])
        assert saturated == name.endswith(".saturated")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_built_records(name):
    read = spec.load_module("layer_metrics", name).read
    assert read(_run(DISPATCHES, FLIGHTS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_records_without_the_fields(name):
    """The parent commit's records: the kinds and the requests are there,
    the new fields are not. Nothing is read and nothing raises."""
    old_dispatches = [
        {"kind": kind, "status": "ok", "duration_s": 0.8, "batch_size": 2, "tokens": 8}
        for kind in ("prefill", "prefill_chunk", "decode_chunk")
    ]
    old_flights = [{"status": "ok", "queue_wait_s": 0.005, "ttft_s": 0.7}]
    read = spec.load_module("layer_metrics", name).read
    assert read(_run(old_dispatches, old_flights)) is None
    assert read(_run([], [])) is None


# -- tools/trace_spans.py on a hand-built trace -----------------------------------

@pytest.fixture(scope="module")
def joined():
    from jax.profiler import ProfileData

    path = os.path.join(spec.ROOT, "tools", "trace_spans.py")
    module_spec = importlib.util.spec_from_file_location("trace_spans", path)
    tool = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(tool)
    fixture = os.path.join(spec.HERE, "fixtures", "trace_spans.textproto")
    with open(fixture, encoding="utf-8") as fh:
        raw = ProfileData.text_proto_to_serialized_xspace(fh.read())
    return tool.summary(ProfileData.from_serialized_xspace(raw), ops=3, raw=raw)


def test_programs_and_which_kind_runs_which(joined):
    programs = joined["programs"]
    assert programs["jit__lambda_(22)"]["runs"] == 3
    assert programs["jit__lambda_(22)"]["ms_per_run"] == pytest.approx(10.0)
    assert programs["jit__prefill_fn(11)"]["ms_per_run"] == pytest.approx(5.0)
    # the pooled chunk is the lambda with most device time, the solo chunk the next
    assert [r["run_ms"] for r in joined["kinds"]["decode_chunk"]["rows"]] == pytest.approx([10, 10])
    assert joined["kinds"]["decode_solo"]["rows"][0]["run_ms"] == pytest.approx(2.0)


def test_a_prefill_is_placed_behind_the_chunks_it_waited_for(joined):
    (row,) = joined["kinds"]["prefill"]["rows"]
    assert row["dispatch_id"] == 200
    assert row["issue_ms"] == pytest.approx(1.0)
    assert row["wait_ms"] == pytest.approx(16.0)  # issue ended at 4, its program ran at 20
    assert row["chunks_in_wait"] == 2 and row["chunk_ms_in_wait"] == pytest.approx(17.0)
    assert row["after_run_ms"] == pytest.approx(0.3)


def test_pool_chunks_are_placed_by_their_blocked_fetch(joined):
    rows = {r["dispatch_id"]: r for r in joined["kinds"]["decode_chunk"]["rows"]}
    assert set(rows) == {101, 102}  # 103's fetch did not block: not placed
    assert rows[101]["wait_ms"] == pytest.approx(8.0) and rows[101]["chunks_in_wait"] == 1
    assert rows[102]["wait_ms"] == pytest.approx(12.0) and rows[102]["chunks_in_wait"] == 1
    assert joined["kinds"]["decode_chunk"]["chunks_in_wait_counts"] == {1: 2}
    (solo,) = joined["kinds"]["decode_solo"]["rows"]
    assert solo["dispatch_id"] == 300 and solo["chunks_in_wait"] == 2


def test_device_operations_name_their_scope(joined):
    ops = {op["op"].split(" ")[0]: op for op in joined["ops"]}
    assert ops["fusion.9"]["seconds"] == pytest.approx(9e-3)
    assert ops["fusion.9"]["scope"] == "jit(<lambda>)/while/body/attn.flash/pallas_call:"
    assert ops["fusion.9"]["source"] == "/root/repo/gofr_tpu/ops/flash.py:224"
    # a copy the compiler put in carries the loop it serves and no source
    assert ops["copy.245"]["scope"] == "jit(<lambda>)/while:" and ops["copy.245"]["source"] == ""
    assert ops["custom-call.7"]["scope"] == "" and ops["custom-call.7"]["source"] == ""
