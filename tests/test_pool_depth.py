"""How many chunks the decode pool keeps in flight (tpu/decode_pool.py):
one running and one queued; what that means for a row seated while the
pipeline is full; and that the depth moves no token. The tiny transformer
on the CPU, one compiled bucket, the worker held at its fetch."""

import functools
import os
import threading
import time

import pytest

from gofr_tpu.config import DECLARED_KEYS, EnvConfig
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu import decode_pool
from gofr_tpu.tpu.decode_pool import DecodePool
from gofr_tpu.tpu.device import new_device

_TINY = {
    "MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
    "MODEL_BUCKETS": "64", "DECODE_SLOTS": "4", "DECODE_CHUNK": "4", "PREFIX_CACHE": "0",
    "SCHED_MAX_DEFER_MS": "50",  # a prefill behind a held pool waits this long
}


def _wait(what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if what():
            return
        time.sleep(0.002)
    raise AssertionError("timed out waiting")


class Steered:
    """A tiny device whose pool worker can be held at its fetch, counts its
    fetches, notes the fetch that first delivered tokens to each slot and the
    most chunks it ever had in flight."""

    def __init__(self, pipeline_depth=None):
        old = {k: os.environ.get(k) for k in _TINY}
        os.environ.update(_TINY)
        real_pool = decode_pool.DecodePool
        if pipeline_depth is not None:  # pinned through the constructor
            decode_pool.DecodePool = functools.partial(real_pool, pipeline_depth=pipeline_depth)
        try:
            self.dev = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
            self.dev.wait_ready(300.0)
        finally:
            decode_pool.DecodePool = real_pool
            for k, v in old.items():
                os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        self.pool = pool = self.dev.decode_pool
        self.gate = threading.Event()
        self.gate.set()
        self.reset()
        fetch, issue, deliver_one = pool._fetch_and_deliver, pool._dispatch_chunk, pool._deliver_one

        def held_fetch(in_flight, last_fetch_done):
            self.gate.wait(60.0)
            self.fetches += 1
            return fetch(in_flight, last_fetch_done)

        def counted_issue(in_flight):
            issue(in_flight)
            self.issues += 1
            self.deepest = max(self.deepest, pool.chunks_in_flight)

        def noted_deliver(index, req, *rest):
            delivered = deliver_one(index, req, *rest)
            if delivered:
                self.first_tokens.setdefault(index, self.fetches)
            return delivered

        pool._fetch_and_deliver, pool._dispatch_chunk = held_fetch, counted_issue
        pool._deliver_one = noted_deliver

    def reset(self):
        self.fetches = self.issues = self.deepest = 0
        self.first_tokens: dict[int, int] = {}
        self.mark = max((r["dispatch_id"] for r in self.dev.timeline.records(limit=1)), default=0)

    def chunks(self):
        """This test's ``decode_chunk`` records, oldest first."""
        records = self.dev.timeline.records(limit=2000, kind="decode_chunk")
        return sorted((r for r in records if r["dispatch_id"] > self.mark),
                      key=lambda r: r["dispatch_id"])

    def serve(self, prompts, n):
        """Greedy generations, all at once -> (threads, their token lists)."""
        out = [None] * len(prompts)

        def run(i):
            out[i] = self.dev.generate(prompts[i], max_new_tokens=n)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
        for thread in threads:
            thread.start()
        return threads, out

    def join(self, threads):
        self.gate.set()
        for thread in threads:
            thread.join(60.0)
        assert not any(thread.is_alive() for thread in threads)
        _wait(lambda: self.pool.chunks_in_flight == 0 and not self.pool._active)


@pytest.fixture(scope="module")
def as_deployed():
    steered = Steered()
    yield steered
    steered.dev.close()


@pytest.fixture(scope="module")
def pinned_at_three():
    steered = Steered(pipeline_depth=3)
    yield steered
    steered.dev.close()


@pytest.fixture
def steered(request):
    chosen = request.getfixturevalue(request.param)
    chosen.reset()
    return chosen


def test_a_depth_under_one_is_refused():
    with pytest.raises(ValueError, match="pipeline_depth"):
        DecodePool(None, None, None, n_slots=1, chunk=1, pipeline_depth=0)


def test_busy_pool_holds_two_chunks_and_no_chunk_is_issued_behind_two(as_deployed):
    h, pool = as_deployed, as_deployed.pool
    h.reset()
    assert pool.pipeline_depth == 2
    h.gate.clear()
    threads, out = h.serve([[3, 1, 4, 1, 5], [4, 1, 4, 1, 5]], 21)
    _wait(lambda: pool.chunks_in_flight == 2 and len(pool._active) == 2)
    time.sleep(0.05)  # full: the worker waits at its fetch and issues no third
    assert pool.chunks_in_flight == 2 and h.issues == 2
    h.join(threads)
    assert [len(tokens) for tokens in out] == [21, 21]
    ahead = [r["chunks_ahead"] for r in h.chunks()]
    assert len(ahead) >= 5 and set(ahead) == {0, 1} and ahead[:2] == [0, 1]
    assert h.deepest == 2
    assert pool.occupancy()["pipeline_depth"] == 2


@pytest.mark.parametrize("steered, depth", [("as_deployed", 2), ("pinned_at_three", 3)],
                         indirect=["steered"])
def test_row_seated_behind_a_full_pipeline_rides_the_next_chunk_issued(steered, depth):
    """The fetch is held with the pipeline full and a request is seated.
    Its row rides the chunk issued when the held fetch returns, which is
    fetched behind every chunk already in flight: at depth 2 the second
    fetch after the held one brings its first pooled tokens, at 3 the third."""
    h, pool = steered, steered.pool
    assert pool.pipeline_depth == depth
    h.gate.clear()
    threads, _ = h.serve([[3, 1, 4, 1, 5]], 61)
    _wait(lambda: pool.chunks_in_flight == depth and len(pool._active) == 1)
    rider = set(pool._active)
    held_at = h.fetches  # the held fetch will be number held_at + 1
    late, late_out = h.serve([[2, 7, 1, 8]], 9)
    _wait(lambda: len(pool._active) == 2)
    assert pool.chunks_in_flight == depth and h.fetches == held_at
    (seat,) = set(pool._active) - rider
    h.join(threads + late)
    assert len(late_out[0]) == 9
    assert h.first_tokens[seat] - (held_at + 1) == depth
    # the records say the same: the chunks in flight at the submit carried
    # one row, the next one issued carries two
    assert [r["batch_size"] for r in h.chunks()[: depth + 1]] == [1] * depth + [2]
    assert max(r["chunks_ahead"] for r in h.chunks()) == depth - 1
    assert pool.pipeline_depth == depth


def test_greedy_streams_are_the_same_at_depth_two_and_three(as_deployed, pinned_at_three):
    prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
    got = []
    for h in (as_deployed, pinned_at_three):
        h.reset()
        threads, out = h.serve(prompts, 19)
        h.join(threads)
        assert all(r["chunks_ahead"] < h.pool.pipeline_depth for r in h.chunks())
        got.append(out)
    assert got[0] == got[1] and [len(tokens) for tokens in got[0]] == [19] * 4
    assert (as_deployed.pool.pipeline_depth, pinned_at_three.pool.pipeline_depth) == (2, 3)


def test_depth_is_no_configuration_key(monkeypatch):
    """``DECODE_PIPELINE=0`` was refused at boot; now nothing reads it."""
    assert "DECODE_PIPELINE" not in DECLARED_KEYS
    monkeypatch.setenv("DECODE_PIPELINE", "0")
    monkeypatch.setenv("MODEL_NAME", "echo")
    monkeypatch.setenv("TIMEBASE_ENABLED", "off")
    device = new_device(EnvConfig(), MockLogger(Level.FATAL), Registry())
    try:
        device.wait_ready(30)
        assert device.generate([1, 2, 3], max_new_tokens=2) == [1, 2]
        assert not hasattr(device, "_pool_depth")
    finally:
        device.close()
