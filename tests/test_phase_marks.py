"""Phase marks inside a dispatch and a request (gofr_tpu/profiling.py
``phase``): the DispatchRecord's split of running -> done into issue / in
flight / fetch wait / deliver, ``chunks_ahead``, the ``decode_solo`` kind,
the FlightRecord's partition of the server-side TTFT, the transport's own
clock (a request's wait before its record, every token frame's wait between
its delivery and its write, the event loop's lag), and the rule that the
profiler annotations are leaves. The request path runs on the no-JAX echo
model over HTTP; the decode pool, the wait for a seat in it and the solo
decode on the tiny transformer (ONE compiled bucket, two slots: a few
seconds of CPU compiles, the price of keeping the pool's marks in tier-1)."""

import asyncio
import json
import os
import socket
import threading
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from gofr_tpu import profiling
from gofr_tpu.config import EnvConfig
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.ops.sampling import Sampler
from gofr_tpu.telemetry import FlightRecorder, activate_record
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import new_device
from gofr_tpu.tpu.introspect import DispatchTimeline

PHASES = ("issue_s", "in_flight_s", "fetch_wait_s", "deliver_s")
ALL_NAMES = {
    profiling.BATCHER_COLLECT, profiling.PREFILL_ISSUE, profiling.PREFILL_FETCH_WAIT,
    profiling.POOL_ISSUE, profiling.POOL_FETCH_WAIT, profiling.POOL_DELIVER,
    profiling.POOL_WAIT_WORK, profiling.SOLO_ISSUE, profiling.SOLO_FETCH_WAIT,
    profiling.SSE_FIRST_FRAME, profiling.POOL_STATE_INSERT,
    profiling.POOL_SEAT_WAIT, profiling.HTTP_LOOP_TICK, profiling.POOL_HOLD,
}


def assert_marks_in_order(record: dict) -> None:
    assert record["status"] == "ok", record
    for name in PHASES:
        assert record[name] is not None and record[name] >= 0, (name, record)
    assert sum(record[name] for name in PHASES) == pytest.approx(
        record["duration_s"], abs=1e-3)


class RecordingAnnotations:
    """Stands in for ``profiling._annotation``: records every name opened
    and fails the opener if another phase is open on its thread."""

    def __init__(self):
        self.names: set[str] = set()
        self.nested: list[tuple[str, str]] = []
        self._open = threading.local()

    def __call__(self, name, dispatch_id):
        outer = self

        class _Ann:
            def __enter__(self):
                stack = outer._open.__dict__.setdefault("stack", [])
                if stack:
                    outer.nested.append((stack[-1], name))
                stack.append(name)
                outer.names.add(name)

            def __exit__(self, *exc):
                outer._open.stack.pop()

        return _Ann()


# -- the helper ---------------------------------------------------------------

def test_phase_stamps_set_once_marks_and_takes_no_record():
    timeline = DispatchTimeline(capacity=4)
    rec = timeline.begin("decode_chunk", batch_size=1)
    with profiling.phase(profiling.POOL_ISSUE, rec, end="t_issued"):
        pass
    first = rec.t_issued
    assert first is not None and first >= rec.t_running
    with profiling.phase(profiling.POOL_ISSUE, rec, end="t_issued"):
        pass
    assert rec.t_issued == first  # set once
    with profiling.phase(profiling.POOL_FETCH_WAIT, rec, start="t_fetch", end="t_fetched"):
        time.sleep(0.002)
    timeline.finish(rec)
    out = rec.to_dict()
    assert out["fetch_wait_s"] >= 0.002
    assert_marks_in_order(out)
    with profiling.phase(profiling.POOL_WAIT_WORK, None, start="t_fetch", end="t_fetched"):
        pass  # no record: the annotation alone


def test_unmarked_record_reports_no_split():
    timeline = DispatchTimeline(capacity=4)
    rec = timeline.begin("warmup_compile")
    timeline.finish(rec)
    out = rec.to_dict()
    assert out["duration_s"] is not None
    assert all(out[name] is None for name in PHASES[:3])
    assert out["cadence_s"] is None and out["chunks_ahead"] is None


# -- the request path: echo model over HTTP -------------------------------------

@pytest.fixture(scope="module")
def echo_app(tmp_path_factory):
    import gofr_tpu
    from gofr_tpu.openai_compat import register_openai_routes

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {"HTTP_PORT": str(port), "LOG_LEVEL": "FATAL", "MODEL_NAME": "echo",
           "TOKENIZER": "byte", "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "1",
           "ECHO_STEP_MS": "2", "FLIGHT_SLOW_MS": "60000"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("phase_marks"))
    try:
        app = gofr_tpu.new()
    finally:
        os.chdir(cwd)
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    register_openai_routes(app)
    app.start()
    yield app, f"http://127.0.0.1:{port}"
    app.shutdown()


def _complete(base, stream, prompt="hello there"):
    req = urllib.request.Request(
        base + "/v1/completions",
        data=json.dumps({"model": "echo", "prompt": prompt, "max_tokens": 4,
                         "stream": stream}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        resp.read()
        return resp.headers["X-Correlation-ID"]


def _flight(app, trace_id):
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:  # a stream's record closes behind its last frame
        for record in app.container.telemetry.records(limit=200):
            if record["trace_id"] == trace_id and record["status"] == "ok":
                return record
        time.sleep(0.01)
    raise AssertionError(f"no finished flight record for {trace_id}")


def test_streamed_request_partitions_its_server_side_ttft(echo_app):
    app, base = echo_app
    flight = _flight(app, _complete(base, stream=True))
    parts = ("parse_s", "queue_wait_s", "prefill_s", "first_frame_s")
    for name in parts:
        assert flight[name] is not None and flight[name] >= 0, (name, flight)
    total = sum(flight[name] for name in parts) + (flight["sched_defer_s"] or 0.0)
    assert total == pytest.approx(flight["server_ttft_s"], abs=1e-9)
    assert flight["server_ttft_s"] >= flight["ttft_s"]  # the frame left after the token existed
    assert flight["prefill_s"] >= 0.002  # ECHO_STEP_MS inside the dispatch


def test_non_streamed_request_has_no_first_frame(echo_app):
    app, base = echo_app
    flight = _flight(app, _complete(base, stream=False))
    assert flight["first_frame_s"] is None and flight["server_ttft_s"] is None
    assert flight["parse_s"] >= 0 and flight["prefill_s"] >= 0
    assert flight["pool_admit_s"] is None  # the echo runner has no pool to ask


def test_prefill_record_splits_its_duration(echo_app):
    app, base = echo_app
    flight = _flight(app, _complete(base, stream=False, prompt="split me"))
    records = {r["dispatch_id"]: r
               for r in app.container.tpu.timeline.records(limit=500, kind="prefill")}
    mine = [records[i] for i in flight["dispatch_ids"]]
    assert mine
    for record in mine:
        assert_marks_in_order(record)
        assert record["fetch_wait_s"] >= 0.002  # the echo runner's step is its "device"
        assert record["chunks_ahead"] == 0 and record["cadence_s"] is None


def test_prefill_records_the_pool_chunks_it_was_issued_behind(echo_app):
    """The echo runner has no pool; a stand-in with k chunks in flight,
    swapped in by the stall hook as the dispatch starts, must read back."""
    app, base = echo_app
    runner = app.container.tpu.runner
    runner.stall_hook = lambda: setattr(
        runner, "decode_pool", SimpleNamespace(chunks_in_flight=2, holding=False))
    try:
        flight = _flight(app, _complete(base, stream=False, prompt="behind two"))
    finally:
        runner.stall_hook = None
        runner.decode_pool = None
    records = {r["dispatch_id"]: r
               for r in app.container.tpu.timeline.records(limit=500, kind="prefill")}
    assert [records[i]["chunks_ahead"] for i in flight["dispatch_ids"]] == [2]


def test_request_path_annotations_are_leaves(echo_app, monkeypatch):
    app, base = echo_app
    stub = RecordingAnnotations()
    monkeypatch.setattr(profiling, "_annotation", stub)
    _flight(app, _complete(base, stream=True, prompt="leaves"))
    assert not stub.nested
    assert {profiling.BATCHER_COLLECT, profiling.PREFILL_ISSUE,
            profiling.PREFILL_FETCH_WAIT, profiling.SSE_FIRST_FRAME} <= stub.names
    assert stub.names <= ALL_NAMES


# -- the transport's own clock: echo model over HTTP ---------------------------

def _stream_frames(base, path, body):
    """POST a streamed request; (its trace id, the decoded ``data:`` frames
    the client read, ``[DONE]`` left out)."""
    req = urllib.request.Request(
        base + path, data=json.dumps(dict(body, stream=True)).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        lines = resp.read().decode().splitlines()
        trace_id = resp.headers["X-Correlation-ID"]
    datas = [ln[len("data: "):] for ln in lines if ln.startswith("data: ")]
    return trace_id, [json.loads(d) for d in datas if d != "[DONE]"]


def _token_frames(frames):
    """Completions frames that carry a token: a choice that is not the
    terminal one."""
    return [f for f in frames if f["choices"] and f["choices"][0]["finish_reason"] is None]


def _live_record(app, trace_id):
    _flight(app, trace_id)  # finished
    return next(r for r in app.container.telemetry.finished_since(0.0) if r.trace_id == trace_id)


def test_stream_counts_its_token_frames_and_their_wait(echo_app):
    app, base = echo_app
    trace_id, frames = _stream_frames(
        base, "/v1/completions", {"model": "echo", "prompt": "count me", "max_tokens": 7})
    flight = _flight(app, trace_id)
    assert flight["frames"] == len(_token_frames(frames)) == flight["tokens_out"] == 7
    assert flight["frame_lag_max_s"] >= flight["frame_lag_mean_s"] >= 0
    assert flight["frame_lag_max_s"] >= flight["first_frame_s"]  # the first frame's lag IS first_frame_s
    # ECHO_STEP_MS between two tokens: the producer's side of the stream
    assert 0.002 <= flight["deliver_gap_max_s"] < 1.0


def test_accept_and_the_six_terms_sum_to_received_to_first_frame(echo_app):
    app, base = echo_app
    trace_id, _ = _stream_frames(
        base, "/v1/completions", {"model": "echo", "prompt": "six terms", "max_tokens": 3})
    record = _live_record(app, trace_id)
    flight = record.to_dict()
    assert flight["accept_s"] is not None and 0 <= flight["accept_s"] < 5.0
    terms = ("accept_s", "parse_s", "queue_wait_s", "prefill_s", "first_frame_s")
    total = sum(flight[name] for name in terms) + (flight["sched_defer_s"] or 0.0)
    assert total == pytest.approx(record.t_first_frame - record.t_received, abs=1e-3)
    assert record.t_received <= record.t_start  # the server's mark, one clock


def test_non_streamed_request_counts_no_frame(echo_app):
    app, base = echo_app
    flight = _flight(app, _complete(base, stream=False, prompt="no frames"))
    assert flight["frames"] is None
    assert flight["frame_lag_mean_s"] is None and flight["frame_lag_max_s"] is None
    assert flight["accept_s"] >= 0 and flight["deliver_gap_max_s"] >= 0.002


@pytest.mark.parametrize("temperature, streams", [(0.7, 2), (0, 1)],
                         ids=["sampled_two_streams", "greedy_one_stream_replicated"])
def test_fanout_counts_every_candidates_frames_on_its_one_record(echo_app, temperature, streams):
    app, base = echo_app
    trace_id, frames = _stream_frames(
        base, "/v1/completions",
        {"model": "echo", "prompt": "two of us", "max_tokens": 5, "n": 2,
         "temperature": temperature})
    flight = _flight(app, trace_id)
    token_frames = _token_frames(frames)
    assert {f["choices"][0]["index"] for f in token_frames} == {0, 1}
    assert flight["frames"] == len(token_frames) == 10
    assert flight["tokens_out"] == 5 * streams
    assert flight["frame_lag_max_s"] >= flight["frame_lag_mean_s"] >= 0


def test_chat_token_without_a_frame_is_taken_off_the_count(echo_app):
    """Two-byte characters under the byte tokenizer: the first byte of each
    decodes to no text and makes no frame; the role and terminal frames
    carry no token."""
    app, base = echo_app
    trace_id, frames = _stream_frames(
        base, "/v1/chat/completions",
        {"model": "echo", "messages": [{"role": "user", "content": "\u00e9\u00e9\u00e9\u00e9"}],
         "max_tokens": 80})  # the echo cycles the rendered prompt: the template, then these
    flight = _flight(app, trace_id)
    with_text = [f for f in frames if f["choices"][0]["delta"].get("content")]
    assert flight["tokens_out"] == 80
    assert 0 < flight["frames"] == len(with_text) < 80
    assert flight["frame_lag_max_s"] < 1.0  # later frames stay matched to their own delivery


def test_blocked_loop_shows_on_the_request_the_histogram_the_page_and_one_log_line(
        echo_app, monkeypatch):
    app, base = echo_app
    clock = app.http_server.loop_clock
    logger = MockLogger(Level.WARN)
    monkeypatch.setattr(clock, "_logger", logger)

    async def hold_the_loop():
        time.sleep(0.3)  # on the loop's own thread: nothing else runs

    done: list = []
    stream = threading.Thread(target=lambda: done.append(_stream_frames(
        base, "/v1/completions", {"model": "echo", "prompt": "across it", "max_tokens": 400})))
    stream.start()
    time.sleep(0.15)  # the stream is under way (400 tokens of 2 ms)
    asyncio.run_coroutine_threadsafe(hold_the_loop(), app.http_server._loop).result(10.0)
    stream.join(30.0)
    assert not stream.is_alive() and done
    flight = _flight(app, done[0][0])
    assert flight["frames"] == 400
    assert 0.2 <= flight["loop_lag_max_s"] < 5.0
    assert flight["loop_lag_max_s"] >= flight["loop_lag_mean_s"] > 0
    # tokens went on being delivered while nothing was written: the frame's
    # wait holds the loop's, the producer's gap does not
    assert flight["frame_lag_max_s"] >= 0.2
    assert flight["frame_lag_max_s"] > flight["deliver_gap_max_s"]
    page = json.loads(urllib.request.urlopen(base + "/admin/engine", timeout=10).read())["data"]
    assert page["http"]["loop_lag_max_ms"] >= 200.0
    assert page["http"]["loop_lag_max_ms"] >= page["http"]["loop_lag_p99_ms"] >= 0
    assert page["http"]["frames_total"] >= 400
    # a write carries what its stream had ready: never more writes than frames
    assert 1 <= page["http"]["frame_writes_total"] <= page["http"]["frames_total"]
    assert 1 <= flight["frame_writes"] <= flight["frames"]
    text = app.container.metrics.expose()
    buckets = {
        ln.split('le="')[1].split('"')[0]: float(ln.rsplit(" ", 1)[1])
        for ln in text.splitlines()
        if ln.startswith("gofr_tpu_http_loop_lag_seconds_bucket")
    }
    assert buckets["+Inf"] > buckets["0.1"]  # an observation past 100 ms
    late = [json.loads(ln) for ln in logger.lines if "http_loop_late" in ln]
    # one line a late tick (pinned below); a loaded machine may add a tick of its own
    assert late and max(ln["message"]["lag_s"] for ln in late) >= 0.2
    assert all("running" in ln["message"] for ln in late)


def test_loop_clock_reads_the_ticks_of_a_life_and_says_what_ran(monkeypatch):
    from gofr_tpu.http.server import LoopClock

    timeline = DispatchTimeline(capacity=4)
    rec = timeline.begin("decode_chunk", batch_size=2)
    with profiling.phase(profiling.POOL_ISSUE, rec, end="t_issued"):
        pass
    logger = MockLogger(Level.WARN)
    clock = LoopClock(logger=logger, running=timeline.running)
    assert clock.lags(0.0, time.perf_counter()) == (None, None)
    assert clock.snapshot() == {"loop_lag_p99_ms": None, "loop_lag_max_ms": None}
    t0 = time.perf_counter()
    for lag in (0.001, 0.003, 0.002):
        clock.note(lag)
    t1 = time.perf_counter()
    clock.note(0.4)  # late: logged, and after t1
    mean, worst = clock.lags(t0, t1)
    assert mean == pytest.approx(0.002) and worst == pytest.approx(0.003)
    assert clock.lags(t1, time.perf_counter()) == (pytest.approx(0.4), pytest.approx(0.4))
    assert clock.snapshot() == {"loop_lag_p99_ms": pytest.approx(400.0),
                                "loop_lag_max_ms": pytest.approx(400.0)}
    (line,) = [json.loads(ln) for ln in logger.lines]
    assert line["message"] == {
        "event": "http_loop_late", "lag_s": 0.4,
        "running": [{"dispatch_id": rec.dispatch_id, "kind": "decode_chunk",
                     "phase": "in_flight"}],
    }
    # a tick late while the engine boots (imports, warm-up compiles) is no news
    booting = LoopClock(logger=logger, ready=lambda: False)
    booting.note(0.9)
    assert len(logger.lines) == 1 and booting.snapshot()["loop_lag_max_ms"] == pytest.approx(900.0)
    timeline.finish(rec)
    assert timeline.running() == [] and rec.phase == "done"


def test_frames_are_matched_to_the_oldest_delivery_with_tokens_left():
    """The record's own arithmetic, no server: a burst of 3 then a burst of
    2, five token frames and a terminal one."""
    record = FlightRecorder().start("m", "/t", stream=True, activate=False)
    assert not record.note_frame(1.0)  # the role frame: nothing delivered yet
    record.note_delivered(3, now=10.0)
    record.note_delivered(2, now=10.5)
    record.note_delivered(0, now=99.0)  # an empty burst is no delivery
    for now in (10.1, 10.2, 10.7, 10.8, 10.9):
        assert record.note_frame(now)
    assert not record.note_frame(11.0)  # the terminal frame: none left
    flight = record.to_dict()
    assert flight["frames"] == 5
    assert flight["frame_lag_mean_s"] == pytest.approx((0.1 + 0.2 + 0.7 + 0.3 + 0.4) / 5)
    assert flight["frame_lag_max_s"] == pytest.approx(0.7)
    assert flight["deliver_gap_max_s"] == pytest.approx(0.5)
    assert flight["accept_s"] is None and flight["loop_lag_max_s"] is None  # no server


def test_end_of_the_token_frames_drops_what_was_never_framed():
    """A stop string ends the token loop with tokens delivered and never
    framed: the terminal, usage and [DONE] frames must not take them, and
    a producer that runs on until it sees the cancel notes no more."""
    record = FlightRecorder().start("m", "/t", stream=True, activate=False)
    record.note_delivered(3, now=10.0)
    assert record.note_frame(10.1) and record.note_frame(10.2)
    record.end_token_frames()
    record.note_delivered(4, now=10.5)  # the pool's next burst, before the cancel lands
    assert not record.note_frame(10.6)  # the terminal frame
    assert not record.note_frame(10.7)  # [DONE]
    flight = record.to_dict()
    assert flight["frames"] == 2 and flight["frame_lag_max_s"] == pytest.approx(0.2)
    assert flight["deliver_gap_max_s"] == pytest.approx(0.5)  # the producer's side is kept


_CHAT_ABC = [{"role": "user", "content": "abcdefgh"}]


@pytest.mark.parametrize("path, body, slack", [
    ("/v1/completions", {"prompt": "abcdefgh", "stop": ["ef"]}, 0),
    ("/v1/completions", {"prompt": "abcdefgh", "stop_token_ids": [ord("f")]}, 0),
    ("/v1/completions", {"prompt": "abcdefgh", "stop": ["ef"], "n": 2, "temperature": 0}, 0),
    ("/v1/chat/completions", {"messages": _CHAT_ABC, "stop": ["ef"]}, 0),
    ("/v1/chat/completions",
     {"messages": _CHAT_ABC, "stop": ["ef"], "n": 2, "temperature": 0,
      "stream_options": {"include_usage": True}}, 0),
    # two generations on one record share its deliveries: the terminal
    # frame of the one that stops first may take a token of the other's
    ("/v1/completions", {"prompt": "abcdefgh", "stop": ["ef"], "n": 2, "temperature": 0.7}, 1),
], ids=["completions_stop_string", "completions_stop_token", "completions_stop_string_n2",
        "chat_stop_string", "chat_stop_string_n2_usage", "completions_stop_string_n2_sampled"])
def test_stream_ended_by_a_stop_counts_only_its_token_frames(echo_app, path, body, slack):
    """The frames behind a stop (terminal, usage) carry no token, though
    tokens were delivered past it before the decode was cancelled."""
    app, base = echo_app
    trace_id, frames = _stream_frames(base, path, dict(body, model="echo", max_tokens=200))
    flight = _flight(app, trace_id)
    chosen = [f["choices"][0] for f in frames if f["choices"]]
    assert {c["finish_reason"] for c in chosen if c["finish_reason"]} == {"stop"}
    if path.endswith("/chat/completions"):
        tokens = [c for c in chosen if c["delta"].get("content")]
    else:
        tokens = [c for c in chosen if c["finish_reason"] is None]
    assert 0 < len(tokens) <= flight["frames"] <= len(tokens) + slack < 200
    assert flight["frame_lag_max_s"] >= flight["frame_lag_mean_s"] >= 0


# -- the decode pool, the wait for a seat and the solo decode: tiny transformer ---

_TINY = {
    "MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
    "MODEL_BUCKETS": "64", "DECODE_SLOTS": "2", "DECODE_CHUNK": "4", "PREFIX_CACHE": "0",
}


@pytest.fixture(scope="module")
def held_pool():
    """A two-slot pool whose worker is held at its first fetch with both
    slots taken and the pipeline full; a prefill and a seeded request (it
    decodes solo) served meanwhile, and a third pooled request that finds
    no seat and stands waiting; then the worker is let go: (device, depth,
    the flights, the annotation names seen, nested pairs)."""
    old = {k: os.environ.get(k) for k in _TINY}
    os.environ.update(_TINY)
    try:
        dev = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    dev.wait_ready(300.0)
    pool = dev.decode_pool
    depth = pool.pipeline_depth
    stub = RecordingAnnotations()
    real_annotation, profiling._annotation = profiling._annotation, stub
    gate = threading.Event()
    real_fetch = pool._fetch_and_deliver

    def held_fetch(in_flight, last_fetch_done):
        gate.wait(60.0)
        return real_fetch(in_flight, last_fetch_done)

    pool._fetch_and_deliver = held_fetch
    # the worker's first chunk waits (the pool's lock let go meanwhile) until
    # BOTH riders are seated: they ride the same chunks and end together,
    # whichever thread reached submit first
    aboard = threading.Event()
    real_dispatch = pool._dispatch_chunk

    def dispatch_both_aboard(in_flight):
        if not aboard.is_set():
            pool._work.wait_for(lambda: len(pool._active) == 2, timeout=60.0)
            aboard.set()
        return real_dispatch(in_flight)

    pool._dispatch_chunk = dispatch_both_aboard
    recorder = FlightRecorder()

    def serve(prompt, n, out, sampler=None):
        record = recorder.start(model="tiny", endpoint="/t")
        try:
            dev.generate(prompt, max_new_tokens=n, sampler=sampler)
        finally:
            recorder.finish(record)
            activate_record(None)
        out.append(record.to_dict())

    riders: list[dict] = []
    threads = [threading.Thread(target=serve, args=([3 + i, 1, 4, 1, 5], 21, riders))
               for i in range(2)]
    try:
        for thread in threads:
            thread.start()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and not (
            pool.chunks_in_flight == depth and len(pool._active) == 2
        ):
            time.sleep(0.005)
        assert pool.chunks_in_flight == depth and len(pool._active) == 2
        prefill_only: list[dict] = []
        serve([9, 8, 7], 1, prefill_only)  # one token: prefill, never the pool
        seeded: list[dict] = []  # a seeded request never asks the pool: solo
        serve([6, 2, 8], 9, seeded, Sampler(temperature=1.0, seed=5))
        waited: list[dict] = []  # no free slot: waits for a rider's
        threads.append(threading.Thread(target=serve, args=([2, 7, 1, 8], 9, waited)))
        threads[-1].start()
        while time.monotonic() < deadline and pool.occupancy()["waiting"] != 1:
            time.sleep(0.005)
        assert pool.occupancy()["waiting"] == 1 and not waited
        time.sleep(0.02)  # a wait the clock can see
    finally:
        gate.set()
        for thread in threads:
            thread.join(60.0)
        pool._fetch_and_deliver = real_fetch
        pool._dispatch_chunk = real_dispatch
    assert not any(thread.is_alive() for thread in threads)
    assert len(riders) == 2 and len(waited) == 1
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and profiling.POOL_WAIT_WORK not in stub.names:
        time.sleep(0.005)  # the worker parks once its last slot is free
    profiling._annotation = real_annotation
    yield SimpleNamespace(dev=dev, depth=depth, prefill_only=prefill_only[0],
                          seeded=seeded[0], waited=waited[0], riders=riders, stub=stub)
    dev.close()


def _records(dev, kind):
    return {r["dispatch_id"]: r for r in dev.timeline.records(limit=2000, kind=kind)}


def test_pool_chunks_carry_marks_cadence_and_depth(held_pool):
    chunks = list(_records(held_pool.dev, "decode_chunk").values())
    assert len(chunks) >= held_pool.depth
    for record in chunks:
        assert_marks_in_order(record)
        assert record["cadence_s"] is not None and record["cadence_s"] > 0
        assert 0 <= record["chunks_ahead"] < held_pool.depth
    # the held worker filled its pipeline one chunk at a time
    first = sorted(chunks, key=lambda r: r["dispatch_id"])[: held_pool.depth]
    assert [r["chunks_ahead"] for r in first] == list(range(held_pool.depth))
    assert held_pool.dev.decode_pool.chunks_in_flight == 0


def test_pool_chunks_count_the_kv_blocks_their_attention_had_to_read(held_pool):
    """Two slots of one 128-position block each (the tiny model's cache):
    a step reads one block for each row that rides it."""
    pool = held_pool.dev.decode_pool
    for record in _records(held_pool.dev, "decode_chunk").values():
        assert record["kv_blocks_held"] == pool.chunk * pool.n_slots
        assert record["kv_blocks_read"] == pool.chunk * record["batch_size"]
        assert record["state_bytes"] is None
    # the mask is what the last chunk was issued with: whoever rode it
    assert np.asarray(pool.cache["live"]).tolist() == list(pool._live_mask)
    assert 1 in pool._live_mask


def test_kv_blocks_read_follows_each_rows_length_over_the_steps():
    from gofr_tpu.tpu.decode_pool import DecodePool

    pool = SimpleNamespace(chunk=8, max_len=2048, _kv_block=128)
    rows = [(0, SimpleNamespace(cache_len=125)), (1, None), (2, SimpleNamespace(cache_len=2044))]
    # row 0 attends 126..133 keys: three steps of one block, five of two;
    # row 2 is full after four steps and stays at the cache's 16 blocks
    assert DecodePool._kv_blocks_read(pool, rows) == (3 * 1 + 5 * 2) + 8 * 16


def test_prefill_issued_behind_k_pool_chunks_records_k(held_pool):
    prefills = _records(held_pool.dev, "prefill")
    mine = [prefills[i] for i in held_pool.prefill_only["dispatch_ids"]]
    assert [r["chunks_ahead"] for r in mine] == [held_pool.depth]
    assert_marks_in_order(mine[0])
    assert held_pool.prefill_only["pool_admit_s"] is None  # never asked the pool


def test_refused_request_leaves_decode_solo_records_on_its_flight(held_pool):
    """The name is PR 25's; since PR 37 a full pool refuses nobody: the
    held pool's third request stands waiting with its prefilled row and is
    seated as a rider ends, and no solo program ever runs for it."""
    flight = held_pool.waited
    assert flight["status"] == "ok" and flight["tokens_out"] == 9
    assert flight["pool_reject_reason"] is None
    assert flight["pool_seat_wait_s"] > 0.02
    # the pool's answer is the seat: pool_admit_s now holds the wait
    assert flight["pool_admit_s"] >= flight["pool_seat_wait_s"]
    assert flight["state_insert_s"] is not None and flight["pool_cohort"] >= 1
    chunks = _records(held_pool.dev, "decode_chunk")
    solo = _records(held_pool.dev, "decode_solo")
    prefills = _records(held_pool.dev, "prefill")
    decodes = [i for i in flight["dispatch_ids"] if i not in prefills]
    # 8 tokens in chunks of 4, and the chunk already queued when the last came
    assert len(decodes) == 2 + held_pool.depth - 1 and set(decodes) <= set(chunks)
    assert not set(flight["dispatch_ids"]) & set(solo)
    for i in decodes:
        assert_marks_in_order(chunks[i])
    # seated before the worker's next dispatch once the riders' last chunk
    # was delivered: its first chunk is issued after theirs ended
    riders_last = max(i for rider in held_pool.riders for i in rider["dispatch_ids"])
    assert min(decodes) > riders_last
    for rider in held_pool.riders:  # seated at once: no wait, no refusal
        assert rider["pool_reject_reason"] is None and rider["pool_admit_s"] >= 0
        assert rider["pool_seat_wait_s"] is None
        assert not set(rider["dispatch_ids"]) & set(solo)


def test_seeded_request_leaves_decode_solo_records_on_its_flight(held_pool):
    """What still decodes solo (here a seeded request: the pool's key order
    depends on co-tenants) leaves one ``decode_solo`` record a chunk, each
    issued behind the pool's chunks in flight."""
    flight = held_pool.seeded
    assert flight["pool_reject_reason"] is None and flight["pool_admit_s"] is None
    assert flight["pool_seat_wait_s"] is None
    solo = _records(held_pool.dev, "decode_solo")
    mine = [solo[i] for i in flight["dispatch_ids"] if i in solo]
    assert len(mine) == 2  # 8 tokens after the first, chunks of 4
    for record in mine:
        assert_marks_in_order(record)
        assert record["batch_size"] == 1 and record["tokens"] == 4
        assert record["chunks_ahead"] == held_pool.depth
    # no solo record left running or abandoned behind the finished request
    assert all(r["status"] == "ok" for r in solo.values())


def test_metrics_count_tokens_and_export_no_utilisation(held_pool):
    """gofr_tpu_tokens_total moves by prompt tokens prefilled and by tokens
    the pool delivered (the two riders' 20 each and the 8 of the request
    that waited for a seat: not a chunk's tail, not the seeded request's
    solo tokens); no utilisation gauge and no cost-model family is
    registered at all."""
    text = held_pool.dev.metrics.expose()
    count = {
        op: float(next(
            ln for ln in text.splitlines()
            if ln.startswith(f'gofr_tpu_tokens_total{{model="tiny",op="{op}"}}')
        ).rsplit(" ", 1)[1])
        for op in ("prefill", "decode")
    }
    assert count == {"prefill": 2 * 5 + 3 + 3 + 4, "decode": 2 * 20 + 8}
    for family in ("gofr_tpu_mfu", "gofr_tpu_mbu",
                   "gofr_tpu_dispatch_residual_ratio",
                   "gofr_tpu_dispatch_anomalies_total"):
        assert family not in text


def test_delivery_held_back_shows_in_the_riders_deliver_gap(held_pool):
    """The riders' first tokens were out when the worker was held at its
    first fetch; their next delivery came once it was let go, after the
    prefill, the solo decode and the 20 ms a waiter stood: the gap is the
    pool's side of that silence, on a record that was never streamed."""
    for rider in held_pool.riders:
        assert rider["deliver_gap_max_s"] > 0.02
        assert rider["frames"] is None
    # one delivery only (a single token): no gap to speak of
    assert held_pool.prefill_only["deliver_gap_max_s"] is None


def test_pooled_stream_frames_each_token_of_each_burst(held_pool):
    """A stream over the pool, served as the HTTP server serves it (the
    responder's iterator pulled on an event loop, what the stream has ready
    taken in one pull, ``on_write`` after each pull's frames): the first
    token and two bursts of DECODE_CHUNK tokens make nine token frames, the
    terminal frame none, and a write for every pull that carried a token."""
    from gofr_tpu.http.responder import _sse_iter
    from gofr_tpu.http.response import Held, Stream

    recorder = FlightRecorder()
    record = recorder.start(model="tiny", endpoint="/t", stream=True)
    try:
        tokens = held_pool.dev.generate_stream([5, 3, 5, 8], 9)
    finally:
        activate_record(None)

    def events():
        for token in tokens:
            yield {"token": token}
        yield {"finish": True}

    async def serve():
        return [frame async for frame in _sse_iter(
            recorder.finish_stream(Stream(events(), ready=tokens.ready), record))]

    frames = asyncio.run(serve())
    flight = record.to_dict()
    assert flight["status"] == "ok" and flight["tokens_out"] == 9
    assert flight["frames"] == len(frames) - 1 == 9
    # a pull ends on the one frame that is not Held; the last pull is the
    # terminal frame's alone (the stream's end is no token: ready() is false)
    assert not isinstance(frames[-1], Held) and not isinstance(frames[-2], Held)
    assert flight["frame_writes"] == sum(not isinstance(f, Held) for f in frames[:-1])
    assert 1 <= flight["frame_writes"] <= 9
    assert flight["frame_lag_max_s"] >= flight["frame_lag_mean_s"] >= 0
    assert flight["frame_lag_max_s"] >= flight["first_frame_s"] > 0
    assert flight["deliver_gap_max_s"] > 0  # first token -> first burst -> second
    assert flight["pool_admit_s"] is not None  # it rode the pool


def test_solo_chunk_notes_only_the_tokens_before_its_stop(held_pool):
    """A seeded request decodes solo, DECODE_CHUNK tokens a fetch. With a
    stop token in the middle of a chunk the tokens noted as delivered are
    those handed on, not the chunk's."""
    dev = held_pool.dev

    def stream(stop_tokens):
        record = FlightRecorder().start(model="tiny", endpoint="/t", stream=True)
        try:
            tokens = list(dev.generate_stream(
                [6, 2, 8], 9, sampler=Sampler(temperature=1.0, seed=5),
                stop_tokens=stop_tokens))
        finally:
            activate_record(None)
        noted = record._head_left + sum(n for _, n in record._deliveries)
        return tokens, noted, record

    free, noted, record = stream(frozenset())
    assert len(free) == noted == 9 and record.pool_cohort == 0  # never rode the pool
    # a token first seen inside a chunk (the first token is the prefill's,
    # then chunks of 4: positions 1-4, 5-8), with more of the chunk behind it
    at = next(j for j in range(1, 9) if j % 4 and free[j] not in free[:j])
    stopped, noted, _ = stream(frozenset({free[at]}))
    assert stopped == free[:at]
    assert noted == at


def test_pool_and_solo_annotations_are_leaves(held_pool):
    assert not held_pool.stub.nested
    # the echo app's server, where this module's other fixture is alive,
    # ticks through the same stub
    # (and the tiny model's chunks of a few milliseconds are held only where
    # this CPU was slow enough to make them long: tests/test_pool_hold.py)
    sometimes = {profiling.HTTP_LOOP_TICK, profiling.POOL_HOLD}
    assert held_pool.stub.names - sometimes == ALL_NAMES - sometimes - {
        profiling.SSE_FIRST_FRAME}
