"""A prefilled request that finds the decode pool full waits for a seat
(``DecodePool.submit``) instead of decoding beside the pool in a program of
its own: arrival order, which chunk a freed row's successor rides, the three
ways a wait ends without a seat (client gone, deadline, pool closed), the
wait on the KV ledger, and the gate that holds requests past the standing
room before their prefill. The pool's worker is held at a fetch so that a
full pool stays full while the waiters line up. Tiny transformer, ONE
compiled bucket, two slots: a few seconds of CPU compiles a device."""

import contextlib
import os
import threading
import time

import pytest

from gofr_tpu.config import EnvConfig
from gofr_tpu.deadline import Deadline, activate_deadline
from gofr_tpu.errors import DeadlineExceeded
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.telemetry import FlightRecorder, activate_record
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import new_device

_TINY = {
    "MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
    "MODEL_BUCKETS": "64", "DECODE_SLOTS": "2", "DECODE_CHUNK": "4", "PREFIX_CACHE": "0",
}
RECORDER = FlightRecorder()


def _boot(**env):
    env = {**_TINY, **env}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        dev = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    dev.wait_ready(300.0)
    return dev


@pytest.fixture(scope="module")
def dev():
    device = _boot()
    yield device
    device.close()


class Served(threading.Thread):
    """One ``generate`` on a thread of its own, under a FlightRecord."""

    def __init__(self, dev, prompt, n, deadline=None, stop=None):
        super().__init__(daemon=True, name="test-served")
        self.dev, self.prompt, self.n = dev, prompt, n
        self.deadline, self.stop = deadline, stop
        self.out = self.error = self.flight = None
        self.start()

    def run(self):
        record = RECORDER.start(model="tiny", endpoint="/t")
        activate_deadline(self.deadline)
        try:
            self.out = self.dev.generate(self.prompt, max_new_tokens=self.n, stop=self.stop)
        except Exception as exc:
            self.error = exc
        finally:
            RECORDER.finish(record)
            activate_record(None)
            activate_deadline(None)
        self.flight = record


def until(cond, what, timeout=30.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end and not cond():
        time.sleep(0.002)
    assert cond(), what() if callable(what) else what


@contextlib.contextmanager
def held(pool):
    """Hold the pool's worker at its next fetch (outside the pool's lock)
    until the yielded event is set, or the pool is closed."""
    # not at the fetch of a chunk left in flight behind the last request
    until(lambda: pool.chunks_in_flight == 0 and not pool._active, "the pool is idle")
    gate = threading.Event()
    real = pool._fetch_and_deliver

    def held_fetch(in_flight, last_fetch_done):
        while not gate.wait(0.01) and not pool._closed:
            pass
        return real(in_flight, last_fetch_done)

    pool._fetch_and_deliver = held_fetch
    try:
        yield gate
    finally:
        gate.set()
        pool._fetch_and_deliver = real


def fill(dev, lengths=(9, 9)):
    """A rider a slot, seated and riding a full pipeline of held chunks."""
    pool = dev.decode_pool
    riders = [Served(dev, [3 + i, 1, 4, 1, 5], n) for i, n in enumerate(lengths)]
    until(lambda: pool.chunks_in_flight == pool.pipeline_depth
          and len(pool._active) == pool.n_slots,
          lambda: f"the riders fill the held pool: {pool.occupancy()}, {pool.chunks_in_flight} in flight")
    return riders


def wait_in_line(dev, prompt, n, place, **kw):
    served = Served(dev, prompt, n, **kw)
    until(lambda: dev.decode_pool.occupancy()["waiting"] == place,
          f"request {prompt} stands waiting at place {place}")
    return served


def join(*served):
    for one in served:
        one.join(60.0)
    assert not any(one.is_alive() for one in served)


def chunks_of(dev, served):
    chunks = {r["dispatch_id"] for r in dev.timeline.records(limit=2000, kind="decode_chunk")}
    return [i for i in served.flight.dispatch_ids if i in chunks]


def reference(dev, prompt, n):
    """The same request with the pool to itself (greedy: the pool's rows
    do not see each other)."""
    return dev.generate(prompt, max_new_tokens=n)


def test_waiters_are_seated_in_arrival_order_and_ride_the_second_chunk(dev):
    """Two slots, riders of one chunk and of three: the first waiter takes
    the slot the short rider frees, the second is seated no earlier; a row freed when
    chunk k is delivered is written before the worker's next dispatch and
    rides k+2 (k+1 was queued already), not k+3. Nothing decodes solo and
    nothing is counted as refused."""
    pool = dev.decode_pool
    want = [reference(dev, p, 9) for p in ([2, 7, 1, 8], [2, 8, 1, 8])]
    with held(pool) as gate:
        short, long_ = fill(dev, lengths=(5, 13))
        first = wait_in_line(dev, [2, 7, 1, 8], 9, place=1)
        second = wait_in_line(dev, [2, 8, 1, 8], 9, place=2)
        time.sleep(0.02)  # a wait the clock can see
        gate.set()
        join(short, long_, first, second)
    assert [first.out, second.out] == want and not (first.error or second.error)
    for waiter in (first, second):
        flight = waiter.flight.to_dict()
        assert flight["pool_reject_reason"] is None
        assert flight["pool_seat_wait_s"] > 0.02
        assert flight["pool_admit_s"] >= flight["pool_seat_wait_s"]
    for rider in (short, long_):
        assert rider.flight.to_dict()["pool_seat_wait_s"] is None
    assert first.flight.t_state_insert < second.flight.t_state_insert
    every = sorted(r["dispatch_id"] for r in dev.timeline.records(limit=2000, kind="decode_chunk"))
    # the short rider's four tokens came with chunk k; it rode k+1 too,
    # which was queued before k was fetched
    k, k1 = chunks_of(dev, short)
    assert chunks_of(dev, first)[0] == every[every.index(k) + 2] == every[every.index(k1) + 1]
    assert chunks_of(dev, second)[0] >= chunks_of(dev, first)[0]
    assert not dev.timeline.records(limit=2000, kind="decode_solo")
    rejects = dev.metrics.counter("gofr_tpu_pool_reject_total", labels=("reason",))
    assert rejects.value(reason="no_free_slots") == 0
    assert 'gofr_tpu_pool_seat_wait_seconds_count{model="tiny"} 2' in dev.metrics.expose()
    assert pool.occupancy()["waiting"] == 0 and len(pool._free) == pool.n_slots


def test_a_waiter_holds_its_own_row_and_not_the_prefill_batch(dev):
    pool = dev.decode_pool
    with held(pool) as gate:
        riders = fill(dev)
        waiter = wait_in_line(dev, [2, 7, 1, 8], 5, place=1)
        with pool._work:
            row = pool._waiters[0].row_cache
        rows = {name: leaf.shape[0 if leaf.ndim == 1 else 1] for name, leaf in row.items()}
        gate.set()
        join(waiter, *riders)
    assert set(rows.values()) == {1}, rows  # BATCH_MAX_SIZE is 2: a prefill makes two


def test_a_waiter_whose_client_has_gone_leaves_and_the_next_is_seated(dev):
    pool = dev.decode_pool
    want = reference(dev, [2, 8, 1, 8], 9)
    gone = threading.Event()
    with held(pool) as gate:
        riders = fill(dev)
        leaver = wait_in_line(dev, [2, 7, 1, 8], 9, place=1, stop=gone)
        stayer = wait_in_line(dev, [2, 8, 1, 8], 9, place=2)
        gone.set()
        leaver.join(10.0)
        assert not leaver.is_alive() and leaver.error is None
        assert len(leaver.out) == 1  # its first token, streamed before the wait
        assert pool.occupancy()["waiting"] == 1 and stayer.is_alive()
        gate.set()
        join(stayer, *riders)
    assert stayer.out == want
    assert leaver.flight.to_dict()["pool_seat_wait_s"] > 0
    assert not chunks_of(dev, leaver)  # it never took a seat
    assert len(pool._free) == pool.n_slots


def test_a_waiter_whose_deadline_runs_out_is_shed_with_the_deadline_accounting(dev):
    pool = dev.decode_pool
    shed = dev.metrics.counter("gofr_tpu_deadline_exceeded_total", labels=("stage",))
    before = shed.value(stage="admission")
    budget = Deadline(30.0)
    with held(pool) as gate:
        riders = fill(dev)
        waiter = wait_in_line(dev, [2, 7, 1, 8], 9, place=1, deadline=budget)
        budget.t_deadline = 0.0  # spent, deterministically: the waiter's next look sees it
        waiter.join(10.0)
        assert not waiter.is_alive()
        assert pool.occupancy()["waiting"] == 0
        gate.set()
        join(*riders)
    assert isinstance(waiter.error, DeadlineExceeded) and waiter.error.stage == "admission"
    assert waiter.flight.pool_reject_reason == "deadline"
    assert waiter.flight.shed_stage == "admission"
    assert shed.value(stage="admission") == before + 1
    assert all(rider.error is None and len(rider.out) == 9 for rider in riders)


def test_requests_past_the_standing_room_wait_before_their_prefill(dev):
    """Two slots and BATCH_MAX_SIZE 2: four places. The fifth and the sixth
    request hold nothing on the device: no prefill is dispatched for them
    until a place opens; places are handed on in arrival order, and then
    they are served like any other."""
    pool = dev.decode_pool
    assert pool.standing_room == 2
    want = [reference(dev, [4, 4, 4 + i], 9) for i in range(2)]

    def prefills():
        return len(dev.timeline.records(limit=2000, kind="prefill"))

    with held(pool) as gate:
        riders = fill(dev, lengths=(5, 13))  # their places open two chunks apart
        waiters = [wait_in_line(dev, [2, 7 + i, 1, 8], 9, place=1 + i) for i in range(2)]
        seen = prefills()
        late = []
        for i in range(2):
            late.append(Served(dev, [4, 4, 4 + i], 9))
            until(lambda: len(pool._gate_line) == i + 1, "it queues at the gate")
        time.sleep(0.25)
        assert prefills() == seen and all(one.is_alive() and one.flight is None for one in late)
        assert pool.occupancy()["waiting"] == 2
        gate.set()
        join(*late, *waiters, *riders)
    assert [one.out for one in late] == want
    # the first place went to who came first (one dispatch may hold both
    # prefills if the second place opened within the batcher's millisecond)
    assert late[0].flight.dispatch_ids[0] <= late[1].flight.dispatch_ids[0]
    assert prefills() - seen in (1, 2)
    assert all(w.error is None and len(w.out) == 9 for w in waiters)
    assert pool._places_free == pool.n_slots + pool.standing_room and not pool._gate_line


def test_closing_the_pool_wakes_its_waiters_into_the_closed_pool_path():
    """What a submit to a closed pool does: counted ``closed``, and the
    request decodes solo (``RuntimeError`` from ``submit``)."""
    dev = _boot()
    try:
        pool = dev.decode_pool
        want = reference(dev, [2, 7, 1, 8], 9)
        with held(pool):
            riders = fill(dev, lengths=(41, 41))
            waiter = wait_in_line(dev, [2, 7, 1, 8], 9, place=1)
            pool.close()
            join(waiter, *riders)
        assert waiter.error is None and waiter.out == want
        assert waiter.flight.pool_reject_reason == "closed"
        solo = {r["dispatch_id"] for r in dev.timeline.records(limit=2000, kind="decode_solo")}
        assert solo & set(waiter.flight.dispatch_ids)
        rejects = dev.metrics.counter("gofr_tpu_pool_reject_total", labels=("reason",))
        assert rejects.value(reason="closed") >= 1
        for rider in riders:  # mid-stream when the pool closed: an error, never a short "ok"
            assert isinstance(rider.error, RuntimeError) and "closed" in str(rider.error)
    finally:
        dev.close()


def test_a_reservation_the_ledger_cannot_cover_waits_for_a_pooled_row_to_finish():
    """Four slots, a ledger of eight blocks of 16 tokens: two requests of
    four blocks take it whole, the third finds a slot free and the ledger
    spent on pooled rows, so it waits (the BlockPool counts the failed
    reservation: the fleet prober's saturation signal) and is seated when
    one of them finishes; it is never refused."""
    dev = _boot(DECODE_SLOTS="4", KV_BLOCKS="8", KV_BLOCK_TOKENS="16", PREFIX_CACHE="3")
    try:
        pool = dev.decode_pool
        assert dev.kv_pool is not None
        want = reference(dev, [2, 7, 1, 8], 9)
        with held(pool) as gate:
            riders = [Served(dev, [3 + i, 1, 4, 1, 5], 58) for i in range(2)]  # 5 + 1 + 57 = 63 tokens
            until(lambda: len(pool._active) == 2 and pool.chunks_in_flight == pool.pipeline_depth,
                  "two riders hold the whole ledger")
            assert dev.kv_pool.stats()["reserved"] == 8
            waiter = wait_in_line(dev, [2, 7, 1, 8], 9, place=1)
            assert len(pool._free) == 2  # a seat is free: it is the ledger it waits for
            assert dev.kv_pool.stats()["kv_exhausted_rejects"] >= 1
            gate.set()
            join(waiter, *riders)
        assert waiter.error is None and waiter.out == want
        assert waiter.flight.pool_reject_reason == "" and waiter.flight.kv_blocks > 0
        assert waiter.flight.to_dict()["pool_seat_wait_s"] > 0
        assert dev.kv_pool.stats()["reserved"] == 0
        rejects = dev.metrics.counter("gofr_tpu_pool_reject_total", labels=("reason",))
        assert rejects.value(reason="kv_exhausted") == 0
    finally:
        dev.close()
