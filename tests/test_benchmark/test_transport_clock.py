"""The seven readers of the transport's own clock (PR 38):
``request.accept_p50_ms``, ``sse.frame_lag_mean_ms``, ``sse.frame_lag_max_ms``,
``sse.frames_per_s``, ``http.loop_lag_mean_ms``, ``http.loop_lag_max_ms`` and
``pool.deliver_gap_max_ms``, from the FlightRecord's ``accept_s``, ``frames``,
``frame_lag_mean_s``, ``frame_lag_max_s``, ``loop_lag_mean_s``,
``loop_lag_max_s`` and ``deliver_gap_max_s``. Hand-built records; no chip, no
server."""

from types import SimpleNamespace

import pytest

from benchmark import spec


def _flight(**fields):
    return dict({"status": "ok", "queue_wait_s": 0.005, "parse_s": 0.002}, **fields)


FLIGHTS = [
    _flight(accept_s=0.001, frames=100, frame_lag_mean_s=0.002, frame_lag_max_s=0.010,
            deliver_gap_max_s=0.30, loop_lag_mean_s=0.0002, loop_lag_max_s=0.001),
    _flight(accept_s=0.002, frames=300, frame_lag_mean_s=0.004, frame_lag_max_s=0.020,
            deliver_gap_max_s=0.28, loop_lag_mean_s=0.0004, loop_lag_max_s=0.003),
    _flight(accept_s=0.003, frames=600, frame_lag_mean_s=0.001, frame_lag_max_s=0.005,
            deliver_gap_max_s=0.27, loop_lag_mean_s=0.0006, loop_lag_max_s=0.002),
    # not streamed: no frame of its own, and too short a life for a tick of the loop's clock
    _flight(accept_s=0.004, frames=None, frame_lag_mean_s=None, frame_lag_max_s=None,
            deliver_gap_max_s=0.1, loop_lag_mean_s=None, loop_lag_max_s=None),
    # not served: not read
    dict(_flight(accept_s=9.0, frames=9000, frame_lag_mean_s=9.0, frame_lag_max_s=9.0,
                 deliver_gap_max_s=9.0, loop_lag_mean_s=9.0, loop_lag_max_s=9.0), status="error"),
]
EXPECTED = {
    "request.accept_p50_ms": 2.5,
    "sse.frame_lag_mean_ms": (100 * 2.0 + 300 * 4.0 + 600 * 1.0) / 1000,  # weighted by frames
    "sse.frame_lag_max_ms": 20.0,
    "sse.frames_per_s": 1000 / 10.0,
    "http.loop_lag_mean_ms": 0.4,
    "http.loop_lag_max_ms": 3.0,
    "pool.deliver_gap_max_ms": 300.0,
}
# ZAYA1's cell is left off: tests/test_benchmark/test_cca_moe.py pins that cell's
# per-layer names as an exact set, and a file the benchmark already has is a
# `benchmark` PR's to edit (the readers hold there too: PERF.md section 6, PR 38)
LISTED = [
    "mistral-7b-int8.chat-steady", "internlm2-1.8b-bf16.chat-steady",
    "mistral-7b-int8.docqa-steady", "brumby-14b-bf16.longdoc-steady",
    "jamba2-3b-bf16.reasoning-steady",
]


def _run(flights):
    return SimpleNamespace(dispatches=[], flights=flights, seconds=10.0)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_built_records(name):
    read = spec.load_module("layer_metrics", name).read
    assert read(_run(FLIGHTS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_whose_flights_have_no_such_field_reads_nothing(name):
    """The parent's records: the requests are there, the fields are not.
    Nothing is read and nothing raises, and the harness leaves the metric
    out of the line."""
    old = [{"status": "ok", "queue_wait_s": 0.005, "parse_s": 0.002, "first_frame_s": 0.001,
            "server_ttft_s": 0.7, "ttft_s": 0.7}]
    read = spec.load_module("layer_metrics", name).read
    assert read(_run(old)) is None
    assert read(_run([])) is None


def test_streams_that_framed_nothing_weigh_nothing():
    """A stream cut before its first token has ``frames`` 0 and no lag."""
    cut = _flight(accept_s=0.001, frames=0, frame_lag_mean_s=None, frame_lag_max_s=None)
    assert spec.load_module("layer_metrics", "sse.frame_lag_mean_ms").read(_run([cut])) is None
    assert spec.load_module("layer_metrics", "sse.frames_per_s").read(_run([cut])) == 0.0


def test_five_steady_cells_report_all_seven_and_the_saturated_cell_none():
    manifest = spec.load_manifest()
    declared = {m["name"]: m for m in manifest["per_layer"]}
    pool = {"pool.deliver_gap_max_ms"}
    tpot = {"sse.frame_lag_mean_ms", "sse.frame_lag_max_ms"} | pool
    for name in EXPECTED:
        assert declared[name] == {
            "name": name, "unit": "frames/s" if name == "sse.frames_per_s" else "ms",
            "better": "lower", "source": "program_span",
            "layer": "decode pool" if name in pool else "transport",
            "moves": "tpot_mean_ms" if name in tpot else "ttft_mean_ms", "workloads": LISTED,
        }
    # appended: what the manifest had before them stands before them
    assert [m["name"] for m in manifest["per_layer"]][-7:] == [
        "request.accept_p50_ms", "sse.frame_lag_mean_ms", "sse.frame_lag_max_ms",
        "sse.frames_per_s", "http.loop_lag_mean_ms", "http.loop_lag_max_ms",
        "pool.deliver_gap_max_ms"]
    for cell in (c["name"] for c in manifest["workloads"]):
        names = {m["name"] for m in spec.metrics_of_cell(manifest, cell, "per_layer")}
        assert (set(EXPECTED) <= names) == (cell in LISTED)
        assert not (set(EXPECTED) & names) or cell in LISTED
