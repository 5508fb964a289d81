"""A burst of tokens travels through the transport as a burst: what a
stream has ready when the responder pulls it makes ONE trip between the
threads, one ``writer.write`` and one ``drain``, and the wire does not
change (one SSE frame, one ``id:``, one HTTP chunk an item). No model: a
fake stream fed by the test, the real ``_sse_iter`` and the real
``HTTPServer._write_response`` over a recording writer; the OpenAI routes'
own generators on the no-JAX echo model over HTTP."""

import asyncio
import json
import os
import queue
import socket
import threading
import time
from collections import deque
from types import SimpleNamespace

import pytest

from gofr_tpu.http.responder import respond
from gofr_tpu.http.response import Held, Stream
from gofr_tpu.http.router import Router
from gofr_tpu.http.server import HTTPServer
from gofr_tpu.telemetry import FlightRecorder


def chunk(text, finish=None):
    return json.dumps({"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m",
                       "choices": [{"text": text, "index": 0, "finish_reason": finish}]})


ITEMS = [chunk(t) for t in ("The", " quick", " brown", " fox", " jumps", " over", " the", " dög")]
TAIL = [chunk("", "length"), "[DONE]"]
# What the PARENT of this change (f3b92e2: one pull, one write and one drain
# a frame) wrote for ITEMS + TAIL under ids=True, a write an entry. Taken
# from it, not from this tree: the wire is the yardstick.
GOLDEN = [
    b'99\r\nid: 0\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": "The", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9c\r\nid: 1\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " quick", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9c\r\nid: 2\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " brown", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9a\r\nid: 3\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " fox", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9c\r\nid: 4\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " jumps", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9b\r\nid: 5\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " over", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9a\r\nid: 6\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " the", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9f\r\nid: 7\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": " d\\u00f6g", "index": 0, "finish_reason": null}]}\n\n\r\n',
    b'9a\r\nid: 8\ndata: {"id": "cmpl-g", "object": "text_completion", "created": 17, "model": "m", "choices": [{"text": "", "index": 0, "finish_reason": "length"}]}\n\n\r\n',
    b'14\r\nid: 9\ndata: [DONE]\n\n\r\n',
    b'0\r\n\r\n',
]


class Source:
    """A stream the test feeds: ``events()`` waits for each item, ``ready()``
    says one is there (``TokenStream``'s rule: the end is not an item)."""

    END = object()

    def __init__(self):
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._taken: deque = deque()
        self.closed = False

    def feed(self, *items):
        for item in items:
            self._queue.put(item)

    def end(self):
        self._queue.put(self.END)

    def ready(self):
        while not self._queue.empty():
            self._taken.append(self._queue.get_nowait())
        return bool(self._taken) and self._taken[0] is not self.END

    def events(self):
        try:
            while True:
                item = self._taken.popleft() if self._taken else self._queue.get()
                if item is self.END:
                    return
                yield item
        finally:
            self.closed = True


class Writer:
    """Records what ``_write_response`` does to its ``StreamWriter``. The
    first write is the response's head. ``hold`` keeps a drain from
    returning until the test lets it; ``fail_at`` makes the n-th body write
    raise as a closed socket does."""

    def __init__(self, fail_at=None):
        self.writes: list[bytes] = []
        self.drains = 0
        self.aborted = 0
        self.hold = threading.Event()
        self.hold.set()
        self.fail_at = fail_at
        self.transport = SimpleNamespace(abort=self._abort)

    def _abort(self):
        self.aborted += 1

    def write(self, data):
        if self.fail_at is not None and len(self.writes) == self.fail_at:
            raise ConnectionResetError("the client went away")
        self.writes.append(bytes(data))

    async def drain(self):
        self.drains += 1
        while not self.hold.is_set():
            await asyncio.sleep(0.001)

    @property
    def body(self):
        return self.writes[1:]


def serve(stream, writer):
    """The server's own write of ``stream``, on a loop of its own in a
    thread: (thread, what it raised)."""
    raised: list[BaseException] = []

    def run():
        try:
            asyncio.run(HTTPServer(Router(), 0)._write_response(
                writer, respond(stream, None), keep_alive=False, head_only=False))
        except BaseException as exc:  # the test reads it
            raised.append(exc)

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    return thread, raised


def wait_for(predicate, what, seconds=10.0):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.002)
    raise AssertionError(f"timed out waiting for {what}")


def split_chunks(data):
    """The HTTP chunks of ``data``, each with its size line and CRLF."""
    chunks = []
    while data:
        size, _, rest = data.partition(b"\r\n")
        end = len(size) + 2 + int(size, 16) + 2
        chunks.append(data[:end])
        data = data[end:]
    return chunks


def test_eight_items_ready_together_leave_in_one_write_with_the_parents_bytes():
    source, writer = Source(), Writer()
    source.feed(*ITEMS)  # all there before the first pull
    thread, raised = serve(Stream(source.events(), ids=True, ready=source.ready), writer)
    wait_for(lambda: len(writer.body) == 1, "the burst's write")
    assert writer.drains == 1
    assert writer.body[0] == b"".join(GOLDEN[:8])  # eight frames, eight chunks, ids 0-7
    assert split_chunks(writer.body[0]) == GOLDEN[:8]
    source.feed(*TAIL)
    source.end()
    thread.join(10.0)
    assert not raised and source.closed
    assert b"".join(writer.body) == b"".join(GOLDEN)  # the parent's stream, byte for byte
    assert writer.aborted == 0


def test_a_stream_that_offers_no_ready_is_pulled_and_written_a_frame_at_a_time():
    writer = Writer()
    thread, raised = serve(Stream(iter(ITEMS + TAIL), ids=True), writer)
    thread.join(10.0)
    assert not raised
    assert writer.body == GOLDEN  # a write a frame, as the parent
    assert writer.drains == len(GOLDEN)


def test_an_item_ready_alone_leaves_at_once_and_the_pull_waits_for_no_second():
    source, writer = Source(), Writer()
    thread, raised = serve(Stream(source.events(), ids=True, ready=source.ready), writer)
    wait_for(lambda: len(writer.writes) == 1, "the head")
    source.feed(ITEMS[0])  # the first token: nothing behind it
    wait_for(lambda: len(writer.body) == 1, "the first frame, with no second to come")
    assert writer.body[0] == GOLDEN[0] and writer.drains == 1
    time.sleep(0.05)
    assert len(writer.body) == 1  # and nothing was made up meanwhile
    source.feed(*ITEMS[1:3])
    wait_for(lambda: len(writer.body) == 2, "the pair's write")
    assert writer.body[1] == GOLDEN[1] + GOLDEN[2] and writer.drains == 2
    source.end()
    thread.join(10.0)
    assert not raised and writer.body[-1] == b"0\r\n\r\n"


def test_items_that_arrive_while_a_write_drains_leave_in_the_next():
    source, writer = Source(), Writer()
    writer.hold.clear()  # the socket is slow: drains do not return
    thread, raised = serve(Stream(source.events(), ids=True, ready=source.ready), writer)
    source.feed(ITEMS[0])
    wait_for(lambda: writer.drains == 1, "the first write's drain")
    source.feed(*ITEMS[1:6])  # five arrive behind the back-pressure
    time.sleep(0.05)
    assert len(writer.body) == 1  # nothing is written past a drain that has not returned
    writer.hold.set()
    wait_for(lambda: len(writer.body) == 2, "the next write")
    assert writer.body[1] == b"".join(GOLDEN[1:6]) and writer.drains == 2
    source.end()
    thread.join(10.0)
    assert not raised


def test_a_failed_write_in_the_middle_of_a_stream_aborts_once_and_writes_no_more():
    source = Source()
    writer = Writer(fail_at=2)  # head, first burst, then the socket is gone
    aborts: list[int] = []
    written: list[int] = []
    stream = Stream(source.events(), ids=True, ready=source.ready,
                    on_abort=lambda: aborts.append(1), on_write=written.append)
    thread, raised = serve(stream, writer)
    source.feed(*ITEMS[:3])
    wait_for(lambda: len(writer.body) == 1, "the first burst")
    source.feed(*ITEMS[3:8])  # this burst's write fails
    thread.join(10.0)
    assert not raised
    assert aborts == [1] and writer.aborted == 1  # fired once, directly
    assert writer.body == [b"".join(GOLDEN[:3])]  # nothing after the failure, no terminal chunk
    assert written == [3]  # the frames of the failed write never left
    assert not source.closed  # the hook is the teardown: the generator may be mid-next elsewhere


def test_a_body_that_fails_behind_held_frames_still_sends_them():
    """What wraps a body (devtools/chaos.py cuts one after n chunks) sees a
    frame a chunk as ever; the frames before its failure leave, then the abort."""
    source, writer = Source(), Writer()
    source.feed(*ITEMS)
    response = respond(Stream(source.events(), ids=True, ready=source.ready), None)
    inner = response.stream

    async def cut_after_three():
        sent = 0
        async for frame in inner:
            if sent == 3:
                raise ConnectionResetError("chaos: injected mid-stream disconnect")
            yield frame
            sent += 1

    async def run():
        response.stream = cut_after_three()
        await HTTPServer(Router(), 0)._write_response(writer, response, False, False)
        await inner.aclose()

    asyncio.run(run())
    assert writer.body == [b"".join(GOLDEN[:3])] and writer.aborted == 1
    assert source.closed


def test_every_token_frame_is_noted_with_its_own_delivery_and_a_write_a_pull():
    source, writer = Source(), Writer()
    recorder = FlightRecorder()
    record = recorder.start(model="m", endpoint="/t", stream=True, activate=False)
    noted: list[float] = []
    real_note_frame = record.__class__.note_frame

    def spy(self, now):
        before = self.frames, self._frame_lag_sum
        counted = real_note_frame(self, now)
        if counted:
            noted.append(now - (self._frame_lag_sum - before[1]))  # the delivery it was matched to
        return counted

    stream = recorder.finish_stream(
        Stream(source.events(), ids=True, ready=source.ready), record)
    t0 = time.perf_counter()
    try:
        record.__class__.note_frame = spy
        thread, raised = serve(stream, writer)
        record.mark_first_token()  # delivers one: the first token, alone
        source.feed(ITEMS[0])
        wait_for(lambda: record.frames == 1, "the first token's frame")
        assert record.frame_writes == 1 and record.t_first_frame is not None
        record.note_delivered(4, now=t0 + 100.0)  # two deliveries (their times told apart),
        record.note_delivered(3, now=t0 + 200.0)  # found together by one pull
        source.feed(*ITEMS[1:8])
        wait_for(lambda: record.frames == 8, "the burst's frames")
        source.feed(*TAIL)  # the terminal frames find no delivery left
        source.end()
        thread.join(10.0)
    finally:
        record.__class__.note_frame = real_note_frame
    assert not raised
    assert record.frames == 8 and record.frame_writes == 2  # frames are tokens, writes are pulls
    assert noted[0] == pytest.approx(record.t_first_token, abs=1e-6)
    assert noted[1:] == pytest.approx([t0 + 100.0] * 4 + [t0 + 200.0] * 3, abs=1e-6)
    flight = record.to_dict()
    assert flight["frames"] == 8 and flight["frame_writes"] == 2 and flight["status"] == "ok"
    assert recorder.transport()["frames_total"] == 8
    assert recorder.transport()["frame_writes_total"] == 2
    assert b"".join(writer.body) == b"".join(GOLDEN)
    quiet = recorder.start(model="m", endpoint="/t", stream=False, activate=False).to_dict()
    assert quiet["frames"] is None and quiet["frame_writes"] is None


def test_a_resumed_streams_ids_start_at_its_offset_and_run_on_across_bursts():
    source, writer = Source(), Writer()
    thread, raised = serve(
        Stream(source.events(), ids=True, id_offset=41, ready=source.ready), writer)
    source.feed(*ITEMS[:3])
    wait_for(lambda: len(writer.body) == 1, "the first burst")
    source.feed(*ITEMS[3:8])
    wait_for(lambda: len(writer.body) == 2, "the second burst")
    source.end()
    thread.join(10.0)
    assert not raised
    frames = split_chunks(writer.body[0]) + split_chunks(writer.body[1])
    assert len(split_chunks(writer.body[0])) == 3 and len(frames) == 8
    for i, (frame, golden) in enumerate(zip(frames, GOLDEN)):
        ids = frame.split(b"\r\n", 1)[1].split(b"\n", 1)[0]
        assert ids == b"id: %d" % (41 + i)
        # but for its id (and the size line that counts it) the frame is the parent's
        assert frame.split(b"\ndata: ", 1)[1] == golden.split(b"\ndata: ", 1)[1]


def test_only_the_last_frame_of_a_pull_is_not_held_and_non_sse_items_ride_too():
    source = Source()
    source.feed("a", b"b", {"c": 1})
    source.end()
    writes: list[int] = []

    async def frames():
        stream = Stream(source.events(), sse=False, ready=source.ready, on_write=writes.append)
        return [frame async for frame in respond(stream, None).stream]

    got = asyncio.run(frames())
    assert got == [b"a", b"b", b'{"c":1}']
    assert [isinstance(frame, Held) for frame in got] == [True, True, False]
    assert writes == [3]


def test_token_stream_says_a_token_is_there_and_never_that_the_end_is(monkeypatch):
    """``generate_stream``'s iterator over a stand-in generation: ``ready``
    holds while a token can be had without waiting, not for the end."""
    from gofr_tpu.tpu.device import TokenStream, TPUDevice

    gate = threading.Event()

    def generate(tokens, max_new_tokens, on_token=None, stop=None, **kw):
        for t in tokens[:3]:
            on_token(t)  # a burst: put in one go
        gate.wait(10.0)
        on_token(tokens[3])

    dev = SimpleNamespace(generate=generate)
    stream = TPUDevice._stream_iter(dev, [7, 8, 9, 10], 4, None, None, None, False)
    assert isinstance(stream, TokenStream)
    assert not stream.ready()  # not begun: nothing to have
    assert next(stream) == 7
    wait_for(stream.ready, "the rest of the burst")
    assert [next(stream), next(stream)] == [8, 9]
    assert not stream.ready()  # the producer is held: a next() would wait
    gate.set()
    wait_for(stream.ready, "the last token")
    assert next(stream) == 10
    time.sleep(0.05)
    assert not stream.ready()  # the end is there, and is no token
    assert list(stream) == []
    stream.close()


# -- the OpenAI routes' own generators: echo model over HTTP ---------------------

def boot_echo(tmp_path_factory, **more):
    import gofr_tpu
    from gofr_tpu.openai_compat import register_openai_routes

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {"HTTP_PORT": str(port), "LOG_LEVEL": "FATAL", "MODEL_NAME": "echo",
           "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "1", "FLIGHT_SLOW_MS": "60000", **more}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("transport_burst"))
    try:
        app = gofr_tpu.new()
    finally:
        os.chdir(cwd)
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    register_openai_routes(app)
    app.start()
    return app, port


@pytest.fixture(scope="module")
def echo_app(tmp_path_factory):
    app, port = boot_echo(tmp_path_factory, TOKENIZER="byte")
    yield app, port
    app.shutdown()


@pytest.fixture(scope="module")
def echo_ids_app(tmp_path_factory):
    """No tokenizer: prompts and frames carry token ids, and a frame depends
    on its own token alone, so ``X-Resume-From`` skips ahead."""
    app, port = boot_echo(tmp_path_factory)
    yield app, port
    app.shutdown()


def post_stream(port, path, body, headers=()):
    """(SSE frames as (id, data) pairs, the flight's trace id)."""
    data = json.dumps(body).encode()
    head = (f"POST {path} HTTP/1.1\r\nHost: t\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\n" + "".join(f"{k}: {v}\r\n" for k, v in headers) + "\r\n")
    with socket.create_connection(("127.0.0.1", port), timeout=30) as sock:
        sock.sendall(head.encode() + data)
        raw = b""
        while True:
            got = sock.recv(65536)
            if not got:
                break
            raw += got
    head_bytes, _, rest = raw.partition(b"\r\n\r\n")
    assert head_bytes.startswith(b"HTTP/1.1 200"), head_bytes
    trace = [ln.split(b": ", 1)[1].decode() for ln in head_bytes.split(b"\r\n")
             if ln.lower().startswith(b"x-correlation-id")][0]
    frames = []
    for http_chunk in split_chunks(rest)[:-1]:  # the last is the terminal 0-chunk
        payload = http_chunk.split(b"\r\n", 1)[1][:-2].decode()
        assert payload.endswith("\n\n") and payload.count("\n\n") == 1  # ONE frame a chunk
        id_line, data_line = payload[:-2].split("\n")
        frames.append((int(id_line.removeprefix("id: ")), data_line.removeprefix("data: ")))
    return frames, trace


def flight_of(app, trace_id):
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:  # a stream's record closes behind its last frame
        for record in app.container.telemetry.records(limit=200):
            if record["trace_id"] == trace_id and record["status"] == "ok":
                return record
        time.sleep(0.01)
    raise AssertionError(f"no finished flight record for {trace_id}")


def test_completions_stream_is_a_frame_a_token_with_fewer_writes_than_frames(echo_app):
    app, port = echo_app
    frames, trace = post_stream(port, "/v1/completions", {
        "model": "echo", "prompt": "burst of tokens", "max_tokens": 15, "stream": True})
    assert [i for i, _ in frames] == list(range(17))  # 15 tokens, the terminal chunk, [DONE]
    texts = [json.loads(d)["choices"][0]["text"] for _, d in frames[:-1]]
    assert "".join(texts) == "burst of tokens" and frames[-1][1] == "[DONE]"
    assert all(len(t) == 1 for t in texts[:15])  # no frame carries more than it carried
    flight = flight_of(app, trace)
    assert flight["frames"] == flight["tokens_out"] == 15
    # the echo runner puts its tokens as fast as a loop runs: they were ready together
    assert 1 <= flight["frame_writes"] < 15


def test_stop_string_stream_keeps_frames_matched_to_deliveries(echo_app):
    app, port = echo_app
    frames, trace = post_stream(port, "/v1/completions", {
        "model": "echo", "prompt": "abcdefghij", "max_tokens": 10, "stream": True,
        "stop": ["fg"]})
    datas = [d for _, d in frames]
    texts = [json.loads(d)["choices"][0]["text"] for d in datas[:-1]]
    assert "".join(texts) == "abcde" and datas[-1] == "[DONE]"
    assert json.loads(datas[-2])["choices"][0]["finish_reason"] == "stop"
    assert [i for i, _ in frames] == list(range(len(frames)))
    flight = flight_of(app, trace)
    # a frame for each token up to the one that completed the stop, the
    # terminal chunk and [DONE] counted as nobody's, tokens past the stop unframed
    token_frames = len(frames) - 2
    assert flight["frames"] == token_frames == 7
    assert 1 <= flight["frame_writes"] <= token_frames


def test_chat_token_that_decodes_to_no_text_leaves_no_frame_and_none_waits(echo_app):
    app, port = echo_app
    # the echo model repeats its prompt: the rendered chat prompt ends in
    # these bytes, and each two-byte letter's first byte decodes to nothing
    frames, trace = post_stream(port, "/v1/chat/completions", {
        "model": "echo", "messages": [{"role": "user", "content": "éè"}],
        "max_tokens": 64, "stream": True})
    deltas = [json.loads(d)["choices"][0]["delta"] for _, d in frames[:-1]]
    assert deltas[0] == {"role": "assistant"} and frames[-1][1] == "[DONE]"
    assert [i for i, _ in frames] == list(range(len(frames)))
    content = [d["content"] for d in deltas[1:] if "content" in d]
    assert "é" in content and "è" in content  # whole letters, a frame each
    flight = flight_of(app, trace)
    text = "".join(content)
    unframed = flight["tokens_out"] - len(text)  # one byte a token, one letter a frame
    assert unframed >= 2
    # the role, terminal and [DONE] frames are nobody's; a possible tail
    # frame (a letter cut by max_tokens) is not a token frame either
    assert flight["frames"] == len([c for c in content if c]) == flight["tokens_out"] - unframed
    assert 1 <= flight["frame_writes"] <= flight["frames"]


def test_resumed_completion_over_http_numbers_its_frames_from_the_offset(echo_ids_app):
    app, port = echo_ids_app
    body = {"model": "echo", "prompt": [11, 12, 13, 14, 15, 16, 17, 18, 19, 20],
            "max_tokens": 10, "stream": True, "temperature": 0}
    whole, _ = post_stream(port, "/v1/completions", body)
    assert [i for i, _ in whole] == list(range(12))  # ten tokens, the terminal chunk, [DONE]
    rest, trace = post_stream(port, "/v1/completions", body, headers=[("X-Resume-From", "4")])
    def told(frames):  # the completion's id is drawn anew: what a frame tells, by its id
        return [(i, json.loads(d)["choices"] if d != "[DONE]" else d) for i, d in frames]

    assert told(rest) == told(whole)[4:]  # ids 4.., each frame what the whole stream gave that id
    flight = flight_of(app, trace)
    assert flight["frames"] == 6 and 1 <= flight["frame_writes"] <= 6


def test_many_streams_at_once_keep_their_order_and_their_counts(tmp_path_factory):
    """More streams than cores through one loop, the interpreter's switch
    interval cut short so that producers, pullers and the loop interleave at
    every turn: each stream's ids run on without a hole, its text is its
    own, and its frames are its tokens."""
    import sys

    app, port = boot_echo(tmp_path_factory, TOKENIZER="byte", ECHO_STEP_MS="0.2")
    results: dict[int, tuple] = {}

    def one(i):
        prompt = f"stream {i:02d} says its piece; "
        results[i] = (prompt, *post_stream(port, "/v1/completions", {
            "model": "echo", "prompt": prompt, "max_tokens": 3 * len(prompt), "stream": True}))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(24)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(60.0)
        assert not any(thread.is_alive() for thread in threads)
    finally:
        sys.setswitchinterval(interval)
    try:
        assert len(results) == 24
        for prompt, frames, trace in results.values():
            assert [i for i, _ in frames] == list(range(len(frames)))
            text = "".join(json.loads(d)["choices"][0]["text"] for _, d in frames[:-1])
            assert text == 3 * prompt
            flight = flight_of(app, trace)
            assert flight["frames"] == flight["tokens_out"] == 3 * len(prompt)
            assert 1 <= flight["frame_writes"] <= flight["frames"]
    finally:
        app.shutdown()
