"""When the decode pool issues the chunk behind the one that is running
(tpu/decode_pool.py::_hold) and who goes ahead of it meanwhile
(tpu/scheduler.py, the guard). The tiny transformer on the CPU behind a
pretend device that runs one program at a time and takes ``run_s`` a chunk:
the tiny model's own chunks take a few milliseconds, which the pool never
holds for."""

import threading
import time

import numpy as np
import pytest

from gofr_tpu.tpu.scheduler import InterferenceScheduler
from tests.test_pool_depth import Steered, _wait

RUN_S, LEAD_S = 0.12, 0.03
PREFILL = ("prefill", 64, 1)  # the program of one row's prefill at the tiny bucket


class _Late:
    """A chunk's tokens as the pretend device has them: ready at a time."""

    def __init__(self, array, ready_at):
        self.array, self.ready_at = array, ready_at

    def is_ready(self):
        return time.perf_counter() >= self.ready_at

    def copy_to_host_async(self):
        self.array.copy_to_host_async()

    def __array__(self, *args, **kwargs):
        time.sleep(max(self.ready_at - time.perf_counter(), 0.0))
        return np.asarray(self.array)


class Paced(Steered):
    """A steered pool on a pretend device: programs run in the order they
    were issued, a chunk takes ``run_s`` (0: as fast as the CPU), a prefill
    ``prefill_s`` (0: it is not on the pretend device at all, so its row is
    there to seat at once). Notes when each chunk was issued and fetched."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.run_s = self.prefill_s = self.free_at = 0.0
        pool, runner = self.pool, self.dev.runner
        run, prefill = pool._run_executable, runner._prefill
        issue, fetch = pool._dispatch_chunk, pool._fetch_and_deliver

        def take(seconds):
            self.free_at = max(self.free_at, time.perf_counter()) + seconds
            return self.free_at

        def paced_run(records):
            toks, *rest = run(records)
            return (_Late(toks, take(self.run_s)), *rest)

        def paced_prefill(*args):
            logits, next_ids, cache = prefill(*args)
            ready_at = take(self.prefill_s) if self.prefill_s else 0.0
            return logits, _Late(next_ids, ready_at), cache

        def timed_issue(in_flight):
            issue(in_flight)
            self.issued_at.append(time.perf_counter())

        def timed_fetch(in_flight, last_fetch_done):
            done = fetch(in_flight, last_fetch_done)
            self.fetched_at.append(done)
            return done

        pool._run_executable, runner._prefill = paced_run, paced_prefill
        pool._dispatch_chunk, pool._fetch_and_deliver = timed_issue, timed_fetch

    def reset(self):
        super().reset()
        self.issued_at, self.fetched_at = [], []

    def pace(self, monkeypatch, run_s=RUN_S, terms=(RUN_S, LEAD_S), prefill_s=0.0):
        """Chunks of ``run_s`` until the test ends; the pool's two estimates
        pinned at ``terms`` (None: its own)."""
        monkeypatch.setattr(self, "run_s", run_s)
        monkeypatch.setattr(self, "prefill_s", prefill_s)
        if terms is not None:
            monkeypatch.setattr(self.pool, "_hold_terms", lambda: terms)

    def at_a_hold(self):
        """Return as a hold begins."""
        _wait(lambda: not self.pool.holding)
        _wait(lambda: self.pool.holding)

    def newest(self, kind):
        (record,) = self.dev.timeline.records(limit=1, kind=kind)
        return record


@pytest.fixture(scope="module")
def paced_device():
    paced = Paced()
    yield paced
    paced.dev.close()


@pytest.fixture
def paced(paced_device):
    paced_device.reset()
    yield paced_device
    paced_device.join([])
    paced_device.dev.scheduler._prefill_runs.clear()


def test_second_chunk_is_issued_when_it_is_due_and_not_before(paced, monkeypatch):
    h, pool = paced, paced.pool
    h.pace(monkeypatch)
    held_before = pool.held_issues
    threads, out = h.serve([[3, 1, 4, 1, 5]], 33)
    h.join(threads)
    assert len(out[0]) == 33
    chunks = h.chunks()
    assert len(chunks) >= 8 and h.deepest == 2
    # with nothing in flight a chunk is issued at once; every other one was
    # held: from its due time, RUN_S - LEAD_S after the fetch before the
    # running chunk's, and on the queue within a bound whatever else ran
    assert chunks[0]["chunks_ahead"] == 0 and chunks[0]["held_s"] is None
    assert [r["chunks_ahead"] for r in chunks[1:]] == [1] * (len(chunks) - 1)
    assert pool.held_issues - held_before == len(chunks) - 1
    for k in range(2, len(chunks)):
        after_fetch = h.issued_at[k] - h.fetched_at[k - 2]
        assert RUN_S - LEAD_S - 0.005 <= after_fetch <= RUN_S - LEAD_S + 0.05, (k, after_fetch)
        assert 0.0 <= chunks[k]["held_late_s"] <= 0.05
        assert chunks[k]["held_s"] >= RUN_S - LEAD_S - 0.04
    # the second chunk of all has the first one's issue to count from
    assert h.issued_at[1] - h.issued_at[0] >= RUN_S - LEAD_S - 0.005
    # the device never waited: a chunk came every RUN_S
    assert pool.occupancy()["held_issues_late"] == 0
    gaps = np.diff(h.fetched_at)
    assert gaps.max() < RUN_S + 0.04 and gaps.min() > RUN_S - 0.04


def test_pool_learns_both_estimates_and_a_prefills_run_from_its_own_stamps(paced, monkeypatch):
    h, pool, sched = paced, paced.pool, paced.dev.scheduler
    h.pace(monkeypatch, terms=None, prefill_s=0.04)
    assert pool._hold_terms() is None and pool.occupancy()["chunk_run_s"] == 0.0
    threads, _ = h.serve([[3, 1, 4, 1, 5]], 61)
    _wait(lambda: pool.held_issues_late + pool.held_issues >= 3)
    for prompt in ([2, 7, 1, 8], [1, 6, 1, 8], [1, 4, 1, 4]):  # one at a time: a prefill an interval
        h.at_a_hold()
        late, _ = h.serve([prompt], 5)
        for thread in late:
            thread.join(60.0)
    seen = pool.occupancy()
    h.join(threads)
    assert RUN_S - 0.03 < seen["chunk_run_s"] < RUN_S + 0.03
    assert 0.005 <= seen["issue_lead_s"] < RUN_S / 4
    assert seen["held_issues"] >= 6 and seen["held_issues_late"] <= 1
    # a prefill's run: what ONE prefill added to a delivery interval
    assert 0.02 < sched.expected_run_s(PREFILL) < 0.07
    assert sched.expected_run_s(("prefill", 64, 2)) is None
    # drained: the rows that come next make another chunk
    assert pool._hold_terms() is None and pool.occupancy()["chunk_run_s"] == 0.0


@pytest.mark.parametrize("expected_s, ahead", [(0.01, True), (1.0, False), (None, False)],
                         ids=["short", "long", "untimed"])
def test_prefill_admitted_during_a_hold_goes_ahead_of_the_held_chunk_if_short(
        paced, monkeypatch, expected_s, ahead):
    h, pool, sched = paced, paced.pool, paced.dev.scheduler
    h.pace(monkeypatch)
    if expected_s is not None:
        sched.note_interval([PREFILL], expected_s)
    before = dict(sched.stats)
    threads, _ = h.serve([[3, 1, 4, 1, 5]], 61)
    h.at_a_hold()
    issues, riders = h.issues, h.newest("prefill")["dispatch_id"]
    late, late_out = h.serve([[2, 7, 1, 8]], 9)
    _wait(lambda: h.newest("prefill")["dispatch_id"] > riders
          and h.newest("prefill")["chunks_ahead"] is not None)
    prefill = h.newest("prefill")
    if ahead:
        # issued at once, behind the running chunk alone; the pool still holds
        assert (prefill["chunks_ahead"], prefill["ahead_of_held"]) == (1, True)
        assert sched.stats["prefills_ahead_of_held"] == before["prefills_ahead_of_held"] + 1
        assert sched.stats["prefills_kept_behind"] == before["prefills_kept_behind"]
    else:
        # the pool issued its held chunk first: the order without a hold
        assert (prefill["chunks_ahead"], prefill["ahead_of_held"]) == (2, None)
        assert sched.stats["prefills_kept_behind"] == before["prefills_kept_behind"] + 1
        assert sched.stats["prefills_ahead_of_held"] == before["prefills_ahead_of_held"]
        cut_short = h.chunks()[issues]
        assert cut_short["held_late_s"] is None and cut_short["held_s"] < RUN_S - LEAD_S
    assert pool.occupancy()["prefills_kept_behind"] == sched.stats["prefills_kept_behind"]
    assert pool.occupancy()["prefills_ahead_of_held"] == sched.stats["prefills_ahead_of_held"]
    h.join(threads + late)
    assert len(late_out[0]) == 9


def test_row_seated_during_a_hold_rides_the_held_chunk(paced, monkeypatch):
    """Its first pooled tokens come with the second fetch after its seat;
    behind a full pipeline they come with the third
    (test_pool_depth.py::test_row_seated_behind_a_full_pipeline_rides_the_next_chunk_issued)."""
    h, pool = paced, paced.pool
    h.pace(monkeypatch, run_s=0.3, terms=(0.3, LEAD_S))  # a hold to prefill in, on this CPU
    paced.dev.scheduler.note_interval([PREFILL], 0.01)  # a prefill that does not end the hold
    threads, _ = h.serve([[3, 1, 4, 1, 5]], 29)
    _wait(lambda: len(pool._active) == 1)
    rider = set(pool._active)
    h.at_a_hold()
    fetched, issued = h.fetches, h.issues
    late, late_out = h.serve([[2, 7, 1, 8]], 9)
    _wait(lambda: len(pool._active) == 2)
    assert pool.holding and (h.fetches, h.issues) == (fetched, issued)
    (seat,) = set(pool._active) - rider
    h.join(threads + late)
    assert len(late_out[0]) == 9
    assert h.first_tokens[seat] - fetched == 2
    assert [r["batch_size"] for r in h.chunks()[issued - 1: issued + 1]] == [1, 2]


def test_no_hold_without_an_estimate_for_short_chunks_or_at_depth_one(paced, monkeypatch):
    h, pool = paced, paced.pool
    assert pool._hold_terms() is None  # drained: nothing is known
    lead = pool.occupancy()["issue_lead_s"]
    pool._run_samples.extend([3.9 * lead] * 5)  # a chunk under four leads
    assert pool._hold_terms() is None
    pool._run_samples.extend([4.1 * lead] * 5)
    assert pool._hold_terms() == (pytest.approx(4.1 * lead), pytest.approx(lead))
    pool._run_samples.clear()
    pool._run_samples.extend([0.3, 0.2, 0.25])  # the device needs the next no sooner than the shortest
    assert pool._hold_terms()[0] == pool.occupancy()["chunk_run_s"] == 0.2
    pool._run_samples.clear()
    # speculation's depth: the one chunk in flight is never held, whatever is known
    h.pace(monkeypatch)
    monkeypatch.setattr(pool, "pipeline_depth", 1)
    held_before = pool.held_issues
    threads, out = h.serve([[3, 1, 4, 1, 5]], 13)
    h.join(threads)
    assert len(out[0]) == 13 and h.deepest == 1 and pool.held_issues == held_before
    assert all(r["held_s"] is None and r["chunks_ahead"] == 0 for r in h.chunks())


def test_greedy_streams_are_the_same_with_and_without_the_hold(paced, monkeypatch):
    h, pool = paced, paced.pool
    prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
    threads, plain = h.serve(prompts, 19)
    h.join(threads)
    assert all(r["held_s"] is None for r in h.chunks())
    h.reset()
    h.pace(monkeypatch)
    held_before = pool.held_issues
    threads, held = h.serve(prompts, 19)
    h.join(threads)
    assert pool.held_issues > held_before
    assert held == plain and [len(tokens) for tokens in held] == [19] * 4


def test_close_during_a_hold_returns_at_once_and_fails_the_rows():
    h = Paced()
    pool = h.pool
    try:
        h.run_s = 30.0
        pool._hold_terms = lambda: (30.0, LEAD_S)
        errors = []

        def run():
            try:
                h.dev.generate([3, 1, 4, 1, 5], max_new_tokens=40)
            except Exception as exc:
                errors.append(exc)

        thread = threading.Thread(target=run)
        thread.start()
        _wait(lambda: pool.holding)
        start = time.monotonic()
        pool.close()
        assert time.monotonic() - start < 2.0 and not pool._thread.is_alive()
        assert not pool.holding
        thread.join(30.0)
        assert not thread.is_alive()
        assert len(errors) == 1 and "closed" in str(errors[0])
        with pytest.raises(RuntimeError, match="closed"):
            pool.submit(None, 1, 1, 1, None)
    finally:
        h.run_s = 0.0
        h.dev.close()


# -- the guard alone (no device) ------------------------------------------------

def _held_scheduler(run_s=0.1, **kw):
    sched = InterferenceScheduler(policy=kw.pop("policy", "fair"), max_defer_ms=2000, **kw)
    released = []

    def release():
        released.append(time.perf_counter())
        # the pool issues its held chunk
        threading.Timer(0.05, sched.note_decode_chunk, args=(2,)).start()

    sched.note_decode_chunk(2)
    sched.note_decode_chunk(2)
    sched.note_hold(run_s, release)
    return sched, released


def test_guard_lets_a_short_prefill_ahead_and_asks_for_nothing():
    sched, released = _held_scheduler()
    for run in (0.02, 0.03, 0.5):  # the median shrugs off the stray reading
        sched.note_interval(["p"], run)
    assert sched.expected_run_s("p") == pytest.approx(0.03)
    assert sched.admit_prefill(64, program="p") < 0.02
    assert not released and sched.stats["prefills_ahead_of_held"] == 1
    assert sched.note_decode_chunk(2) == ["p"]  # what ran between the two chunks


@pytest.mark.parametrize("readings", [(), (0.2,), (0.1,)], ids=["untimed", "longer", "as-long"])
def test_guard_makes_the_pool_issue_first_for_a_long_or_untimed_prefill(readings):
    sched, released = _held_scheduler()
    for run in readings:
        sched.note_interval(["p"], run)
    waited = sched.admit_prefill(64, program="p")
    assert len(released) == 1 and 0.03 < waited < 1.0  # until the held chunk was issued
    assert sched.stats["prefills_kept_behind"] == 1 and sched.stats["prefills_ahead_of_held"] == 0
    # it is admitted in the interval AFTER the held chunk's issue
    assert sched.note_decode_chunk(2) == ["p"]


def test_second_prefill_of_an_interval_still_waits_for_the_held_issue():
    sched, released = _held_scheduler()
    sched.note_interval(["p"], 0.01)
    assert sched.admit_prefill(64, program="p") < 0.02  # ahead of the held chunk
    issue = threading.Timer(0.15, sched.note_decode_chunk, args=(2,))
    issue.start()
    waited = sched.admit_prefill(64, program="p")  # short too, and not its turn
    issue.join()
    assert 0.1 < waited < 1.0 and not released
    assert sched.stats["prefills_ahead_of_held"] == 1 and sched.stats["deferred_chunks"] == 1


def test_two_prefills_waiting_for_one_chunk_are_admitted_a_chunk_apart():
    """One prefill an interval, however many wait for the same issue."""
    sched = InterferenceScheduler(policy="fair", max_defer_ms=5000)
    sched.note_decode_chunk(2)
    assert sched.admit_prefill(64) < 0.02  # this interval's one
    admitted = []

    def admit(name):
        sched.admit_prefill(64, program=name)
        admitted.append((name, sched._decode_seq))

    waiting = [threading.Thread(target=admit, args=(name,)) for name in ("a", "b")]
    for thread in waiting:
        thread.start()
    time.sleep(0.1)
    assert not admitted
    sched.note_decode_chunk(2)
    _wait(lambda: len(admitted) == 1)
    time.sleep(0.1)
    assert len(admitted) == 1 and admitted[0][1] == 2
    sched.note_decode_chunk(2)
    for thread in waiting:
        thread.join(5.0)
    assert sorted(seq for _, seq in admitted) == [2, 3]


def test_whoever_comes_while_a_prefill_is_kept_behind_waits_for_the_chunk_after():
    sched, released = _held_scheduler()
    order = []

    def admit(name):
        sched.admit_prefill(64, program=name)
        order.append((name, sched._decode_seq))

    first = threading.Thread(target=admit, args=("long",))
    first.start()
    _wait(lambda: released)
    second = threading.Thread(target=admit, args=("other",))
    second.start()
    first.join(5.0)
    time.sleep(0.05)
    assert order == [("long", 3)] and second.is_alive()  # one prefill an interval
    sched.note_decode_chunk(2)
    second.join(5.0)
    assert order == [("long", 3), ("other", 4)]


def test_outside_a_hold_and_under_prefill_first_nothing_is_decided():
    sched, released = _held_scheduler()
    sched.note_decode_chunk(2)  # the held chunk is issued: the hold is over
    assert sched.admit_prefill(64, program="p") < 0.02
    first, released = _held_scheduler(policy="prefill-first")
    assert first.admit_prefill(64, program="p") < 0.02 and not released
    for s in (sched, first):
        assert s.stats["prefills_kept_behind"] == s.stats["prefills_ahead_of_held"] == 0
    # readings are of ONE prefill in an interval; a drained pool ends a hold
    sched.note_interval(["p", "q"], 0.2)
    sched.note_interval([], 0.2)
    assert sched.expected_run_s("p") is None and sched.expected_run_s("q") is None
    sched.note_hold(0.1, lambda: released.append(0.0))
    sched.note_decode_idle()
    assert sched.admit_prefill(64, program="p") < 0.02 and not released
