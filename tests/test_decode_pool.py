"""Continuous-batching decode pool: correctness vs solo decode, slot
reuse, saturation fallback, cancellation."""

import os
import threading

import pytest

from gofr_tpu.config import EnvConfig
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.ops.sampling import Sampler
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.device import new_device

# XLA-compile-dominated module: deselect with -m 'not slow' for the
# fast developer loop (CI runs everything; CONTRIBUTING.md)
pytestmark = pytest.mark.slow


def _device(**env):
    defaults = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "1"}
    defaults.update(env)
    old = {k: os.environ.get(k) for k in defaults}
    os.environ.update(defaults)
    try:
        return new_device(EnvConfig(), MockLogger(Level.INFO), Registry()), old
    except BaseException:
        _restore(old)  # a failed boot must not leak env into later tests
        raise


def _restore(old):
    for k, v in old.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


@pytest.fixture(scope="module")
def pooled():
    dev, old = _device(DECODE_POOL="on", DECODE_SLOTS="4", DECODE_CHUNK="4")
    yield dev
    dev.close()
    _restore(old)


@pytest.fixture(scope="module")
def solo():
    dev, old = _device(DECODE_POOL="off", DECODE_CHUNK="4")
    yield dev
    dev.close()
    _restore(old)


def test_pool_enabled_by_default():
    dev, old = _device()
    try:
        assert dev.decode_pool is not None
    finally:
        dev.close()
        _restore(old)


def test_submit_rejection_reason_is_counted():
    """A solo-decode fallback must be diagnosable from the metrics:
    the reject reason lands on
    gofr_tpu_pool_reject_total{reason=...}. DECODE_POOL_PENALTIES=off
    rejects penalized submits deterministically."""
    dev, old = _device(DECODE_POOL_PENALTIES="off")
    try:
        out = dev.generate(
            [3, 1, 4, 1, 5], max_new_tokens=6, sampler=Sampler(presence_penalty=0.5)
        )
        assert len(out) == 6  # the solo fallback still served the request
        counter = dev.metrics.counter(
            "gofr_tpu_pool_reject_total", labels=("reason",)
        )
        assert counter.value(reason="penalties_off") >= 1
    finally:
        dev.close()
        _restore(old)


def test_pooled_greedy_matches_solo(pooled, solo):
    for prompt, n in (([1, 2, 3], 11), ([7] * 30, 6), ([42], 1), ([5, 6], 4)):
        assert pooled.generate(prompt, max_new_tokens=n) == \
            solo.generate(prompt, max_new_tokens=n), (prompt, n)


def test_concurrent_streams_share_the_pool(pooled, solo):
    prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
    want = [solo.generate(p, max_new_tokens=9) for p in prompts]
    got = [None] * 4

    def run(i):
        got[i] = pooled.generate(prompts[i], max_new_tokens=9)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


def test_slots_recycle_across_many_requests(pooled, solo):
    # 12 sequential requests through 4 slots: reuse must not leak state
    for i in range(12):
        prompt = [(i % 5) + 1, 2, 3]
        assert pooled.generate(prompt, max_new_tokens=5) == \
            solo.generate(prompt, max_new_tokens=5), i


def test_pool_saturation_falls_back_to_solo(pooled, solo):
    """The name is older than the behaviour: since PR 37 nothing falls back.
    8 concurrent streams over 4 slots, the worker held at a fetch so that
    the pool is full when the last four arrive, one after another: they
    wait for a seat, are seated in arrival order as the first four end, and
    every stream reads as the solo reference does. No solo program runs and
    no ``no_free_slots`` is counted. (tests/test_pool_seat_wait.py holds the
    ways a wait ends without a seat.)"""
    import time

    from gofr_tpu.telemetry import FlightRecorder, activate_record

    pool = pooled.decode_pool
    prompts = [[i + 1, 9, 9] for i in range(8)]
    want = [solo.generate(p, max_new_tokens=7) for p in prompts]
    got = [None] * 8
    flights = [None] * 8
    recorder = FlightRecorder()
    solo_before = len(pooled.timeline.records(limit=5000, kind="decode_solo"))

    def run(i):
        record = recorder.start(model="tiny", endpoint="/t")
        try:
            got[i] = pooled.generate(prompts[i], max_new_tokens=7)
        finally:
            recorder.finish(record)
            activate_record(None)
        flights[i] = record

    def until(cond):
        end = time.monotonic() + 30.0
        while time.monotonic() < end and not cond():
            time.sleep(0.002)
        assert cond()

    until(lambda: pool.chunks_in_flight == 0 and not pool._active)
    gate = threading.Event()
    real_fetch = pool._fetch_and_deliver

    def held_fetch(in_flight, last_fetch_done):
        gate.wait(60.0)
        return real_fetch(in_flight, last_fetch_done)

    pool._fetch_and_deliver = held_fetch
    threads = [threading.Thread(target=run, args=(i,)) for i in range(8)]
    try:
        for t in threads[:4]:
            t.start()
        until(lambda: len(pool._active) == 4 and pool.chunks_in_flight == pool.pipeline_depth)
        for place, t in enumerate(threads[4:], start=1):
            t.start()
            until(lambda: pool.occupancy()["waiting"] == place)
        time.sleep(0.02)
    finally:
        gate.set()
        for t in threads:
            t.join(60.0)
        pool._fetch_and_deliver = real_fetch
    assert not any(t.is_alive() for t in threads)
    assert got == want
    for flight in flights:
        assert flight.pool_reject_reason == ""
    assert all(f.to_dict()["pool_seat_wait_s"] is None for f in flights[:4])
    assert all(f.to_dict()["pool_seat_wait_s"] > 0.02 for f in flights[4:])
    seated = [f.t_state_insert for f in flights[4:]]
    assert seated == sorted(seated)  # in arrival order
    assert len(pooled.timeline.records(limit=5000, kind="decode_solo")) == solo_before
    counter = pooled.metrics.counter("gofr_tpu_pool_reject_total", labels=("reason",))
    assert counter.value(reason="no_free_slots") == 0
    assert pool.occupancy()["waiting"] == 0


def test_seeded_requests_bypass_pool(pooled):
    s = Sampler(temperature=1.0, seed=5)
    s2 = Sampler(temperature=1.0, seed=5)
    a = pooled.generate([1, 2, 3], max_new_tokens=8, sampler=s)
    b = pooled.generate([1, 2, 3], max_new_tokens=8, sampler=s2)
    assert a == b  # exact reproducibility preserved


def test_pooled_sampling_respects_top_k(pooled):
    # temperature>0 unseeded goes through the pool with per-row params;
    # top_k=1 must reduce to greedy
    greedy = pooled.generate([4, 5, 6], max_new_tokens=6)
    via_pool = pooled.generate(
        [4, 5, 6], max_new_tokens=6, sampler=Sampler(temperature=5.0, top_k=1)
    )
    assert via_pool == greedy


def test_pooled_cancellation_frees_slot(pooled):
    stop = threading.Event()
    seen = []

    def on_token(t):
        seen.append(t)
        if len(seen) >= 2:
            stop.set()

    out = pooled.generate([1, 2, 3], max_new_tokens=200, on_token=on_token, stop=stop)
    assert len(out) < 200
    # slot must be free again: another full round completes
    assert len(pooled.generate([1, 2, 3], max_new_tokens=5)) == 5


def test_pool_deadline_admission_reject_accounting(pooled):
    """The submit-time deadline gate: a spent budget rejects with the
    ``deadline`` pool-reject reason stamped on the FlightRecord, the
    ``admission`` stage on the shared counter, and a DeadlineExceeded
    raise (NO solo fallback — solo is slower, not faster)."""
    import time as _time

    from gofr_tpu.deadline import Deadline, activate_deadline
    from gofr_tpu.errors import DeadlineExceeded
    from gofr_tpu.telemetry import FlightRecorder, activate_record

    pool = pooled.decode_pool
    recorder = FlightRecorder()
    record = recorder.start(model="tiny", endpoint="/test")
    expired = Deadline(0.001)
    _time.sleep(0.005)
    try:
        with pool._work:
            with pytest.raises(DeadlineExceeded) as err:
                pool._admit_deadline(expired)
        assert err.value.stage == "admission"
        assert record.pool_reject_reason == "deadline"
        assert record.shed_stage == "admission"
        # a live-but-insufficient budget rejects too once a cadence is
        # observed (cannot cover even one chunk) — but only while rows
        # are DECODING: on an idle pool the cadence is stale (a single
        # anomalous chunk must not wedge the gate into rejecting
        # everything forever) and the chunk runs immediately anyway
        pool._chunk_ema_s = max(pool._chunk_ema_s, 0.05)
        thin = Deadline(0.01)
        with pool._work:
            assert not pool._active
            pool._admit_deadline(thin)  # idle: stale cadence bypassed
            pool._active[0] = pool._slots[0]
            try:
                with pytest.raises(DeadlineExceeded):
                    pool._admit_deadline(Deadline(0.01))
            finally:
                del pool._active[0]
        # a roomy budget admits
        with pool._work:
            pool._admit_deadline(Deadline(30.0))
    finally:
        activate_record(None)
        recorder.finish(record)


def test_pool_deadline_expiry_mid_stream_frees_slot(pooled):
    """Per-chunk row expiry: a deadline that expires mid-generation
    ends the pooled stream with DeadlineExceeded (stage decode), and
    the slot + KV budget are free for the next request."""
    from gofr_tpu.deadline import Deadline, activate_deadline
    from gofr_tpu.errors import DeadlineExceeded

    d = Deadline(30.0)
    seen = []

    def on_token(t):
        seen.append(t)
        if len(seen) == 2:
            # force expiry mid-stream, deterministically (no sleeps):
            # the worker's next per-chunk check sees it
            d.t_deadline = 0.0

    activate_deadline(d)
    try:
        with pytest.raises(DeadlineExceeded) as err:
            pooled.generate([1, 2, 3], max_new_tokens=200,
                            on_token=on_token)
        assert err.value.stage == "decode"
    finally:
        activate_deadline(None)
    assert 0 < len(seen) < 200
    # slot must be free again: another full round completes
    assert len(pooled.generate([1, 2, 3], max_new_tokens=5)) == 5


def test_solo_deadline_expiry_mid_decode(solo):
    """The SOLO path honors the per-chunk decode expiry too: a request
    that fell out of the pool (or a pool-off deployment) must not
    decode unmetered past its budget."""
    from gofr_tpu.deadline import Deadline, activate_deadline
    from gofr_tpu.errors import DeadlineExceeded

    d = Deadline(30.0)
    seen = []

    def on_token(t):
        seen.append(t)
        if len(seen) == 2:
            d.t_deadline = 0.0

    activate_deadline(d)
    try:
        with pytest.raises(DeadlineExceeded) as err:
            solo.generate([1, 2, 3], max_new_tokens=200,
                          on_token=on_token)
        assert err.value.stage == "decode"
    finally:
        activate_deadline(None)
    assert 0 < len(seen) < 200
    # the device serves the next request normally
    assert len(solo.generate([1, 2, 3], max_new_tokens=5)) == 5


def test_cache_bound_in_pool(pooled, solo):
    # tiny max_seq=128; prompt 100 -> at most 28-ish decodes
    out = pooled.generate(list(range(1, 100)), max_new_tokens=300)
    want = solo.generate(list(range(1, 100)), max_new_tokens=300)
    assert out == want
    assert len(out) <= 30


def test_submissions_during_fetch_window_join_next_chunk(pooled, solo):
    # hammer the race: stagger many submissions so some land while the
    # worker is mid-fetch; every stream must still match solo exactly
    import time

    prompts = [[(i % 7) + 1, 3, 9] for i in range(16)]
    want = [solo.generate(p, max_new_tokens=9) for p in prompts]
    got = [None] * len(prompts)

    def run(i):
        time.sleep(0.003 * i)  # staggered arrivals hit fetch windows
        got[i] = pooled.generate(prompts[i], max_new_tokens=9)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want


def test_worker_death_fails_requests_not_hangs():
    dev, old = _device(DECODE_POOL="on", DECODE_SLOTS="2", DECODE_CHUNK="2")
    try:
        pool = dev.decode_pool

        def boom(*a, **k):
            raise RuntimeError("device fell off")

        pool._decode = boom
        with pytest.raises(RuntimeError, match="device fell off"):
            dev.generate([1, 2, 3], max_new_tokens=8)
        # pool is closed; later requests fall back to solo and still work
        out = dev.generate([1, 2, 3], max_new_tokens=4)
        assert len(out) == 4
    finally:
        dev.close()
        _restore(old)


def test_stop_tokens_solo_and_pooled_agree(pooled, solo):
    # pick a token the greedy continuation actually emits, use it as stop
    full = solo.generate([1, 2, 3], max_new_tokens=10)
    assert len(full) == 10
    stop_tok = full[5]
    want = full[: full.index(stop_tok)]
    for dev in (solo, pooled):
        got = dev.generate([1, 2, 3], max_new_tokens=10, stop_tokens=[stop_tok])
        assert got == want, (dev is pooled, got, want)


def test_stop_token_on_first_token(pooled, solo):
    first = solo.generate([1, 2, 3], max_new_tokens=1)[0]
    for dev in (solo, pooled):
        assert dev.generate([1, 2, 3], max_new_tokens=10, stop_tokens=[first]) == []


def test_stop_tokens_in_stream(pooled):
    full = pooled.generate([1, 2, 3], max_new_tokens=10)
    stop_tok = full[4]
    got = list(pooled.generate_stream([1, 2, 3], max_new_tokens=10,
                                      stop_tokens=[stop_tok]))
    assert got == full[: full.index(stop_tok)]


def test_pooled_decode_counts_delivered_tokens_only(pooled):
    """gofr_tpu_tokens_total{op="decode"} moves by the tokens the pool put
    on a request's queue: not the first token (the prefill's), not the
    tail of a chunk past max_new_tokens, not a stop token or what the
    chunk computed after it."""
    def decoded():
        line = next(
            (ln for ln in pooled.metrics.expose().splitlines()
             if ln.startswith('gofr_tpu_tokens_total{model="tiny",op="decode"}')),
            None,
        )
        return float(line.rsplit(" ", 1)[1]) if line else 0.0

    chunk = pooled.decode_pool.chunk
    before = decoded()
    full = pooled.generate([1, 2, 3], max_new_tokens=chunk + 3)
    assert len(full) == chunk + 3
    assert decoded() - before == chunk + 2  # two chunks ran, 2 x chunk steps
    before = decoded()
    stop_tok = full[3]
    got = pooled.generate([1, 2, 3], max_new_tokens=chunk + 3,
                          stop_tokens=[stop_tok])
    assert got == full[: full.index(stop_tok)]
    assert decoded() - before == max(len(got) - 1, 0)


def test_slot_sampling_knobs_reset_on_free(pooled):
    # a finished sampled request must not leave its temperature on the
    # slot: stale temps defeat the all-greedy lax.cond fast path in
    # sample_logits_rows for every later chunk
    pooled.generate([2, 4, 6], max_new_tokens=4,
                    sampler=Sampler(temperature=0.9, top_k=7, top_p=0.5))
    pool = pooled.decode_pool
    with pool._work:  # settle: delivery runs under this lock
        assert all(t == 0.0 for t in pool._temps), list(pool._temps)
        assert all(k == 0 for k in pool._top_ks), list(pool._top_ks)
        assert all(p == 1.0 for p in pool._top_ps), list(pool._top_ps)


def test_pool_close_mid_stream_raises_not_truncates():
    dev, old = _device(DECODE_POOL="on", DECODE_SLOTS="2", DECODE_CHUNK="2")
    try:
        import time

        results = []

        def run():
            try:
                results.append(("ok", dev.generate([1, 2, 3], max_new_tokens=10_000)))
            except RuntimeError as exc:
                results.append(("err", str(exc)))

        t = threading.Thread(target=run)
        t.start()
        time.sleep(0.4)  # mid-stream (tiny max_seq keeps it bounded; chunk=2 is slow)
        dev.decode_pool.close()
        t.join(timeout=10)
        assert results, "generation thread hung"
        kind, value = results[0]
        # either it finished before the close (cache bound) or it errored —
        # never a silently truncated 'ok' shorter than the cache allows
        if kind == "ok":
            assert len(value) >= 100  # ran to the tiny cache bound
        else:
            assert "closed" in value
    finally:
        dev.close()
        _restore(old)


def test_pooled_logprobs_match_solo(pooled, solo):
    """logprobs requests ride the pool (the chosen tokens' log-softmax
    comes back with every chunk): tokens equal the solo path exactly,
    logprobs to float tolerance (the [slots]-batch executable may
    schedule the matmuls differently than the [1]-batch one)."""
    import numpy as np

    for prompt, n in (([1, 2, 3], 11), ([5, 6], 4)):
        pt, plp = pooled.generate(prompt, max_new_tokens=n, logprobs=True)
        st, slp = solo.generate(prompt, max_new_tokens=n, logprobs=True)
        assert pt == st, (prompt, n)
        np.testing.assert_allclose(plp, slp, rtol=1e-4, atol=1e-4)
    # streaming consumers receive (token, logprob) pairs from the pool
    got = []
    out = pooled.generate([1, 2, 3], max_new_tokens=6, logprobs=True,
                          on_token=got.append)
    assert [t for t, _ in got] == out[0]
    assert [lp for _, lp in got] == out[1]


def test_pooled_penalized_logprobs(pooled, solo):
    """Penalties + logprobs pool together; the logprobs stay RAW model
    values (unpenalized log-softmax), matching the solo convention."""
    import numpy as np

    s = dict(presence_penalty=1.5, frequency_penalty=0.5)
    pt, plp = pooled.generate([1, 2, 3], max_new_tokens=8, logprobs=True,
                              sampler=Sampler(**s))
    st, slp = solo.generate([1, 2, 3], max_new_tokens=8, logprobs=True,
                            sampler=Sampler(**s))
    assert pt == st
    np.testing.assert_allclose(plp, slp, rtol=1e-4, atol=1e-4)


def test_top_logprobs_pooled_and_solo(pooled, solo):
    """top_logprobs=True returns the TOP_LOGPROBS alternatives per
    position, best first; greedy's chosen token IS the top-1 entry, and
    the pooled and solo paths agree."""
    import numpy as np

    from gofr_tpu.models.transformer import TOP_LOGPROBS

    for dev in (pooled, solo):
        out, lps, tops = dev.generate([1, 2, 3], max_new_tokens=6,
                                      logprobs=True, top_logprobs=True)
        assert len(out) == len(lps) == len(tops) == 6
        for i, alts in enumerate(tops):
            assert len(alts) == TOP_LOGPROBS
            vals = [v for _, v in alts]
            assert vals == sorted(vals, reverse=True)
            assert alts[0][0] == out[i]  # greedy picks the argmax
            np.testing.assert_allclose(alts[0][1], lps[i], rtol=1e-4,
                                       atol=1e-4)
    p = pooled.generate([1, 2, 3], max_new_tokens=6, logprobs=True,
                        top_logprobs=True)
    s = solo.generate([1, 2, 3], max_new_tokens=6, logprobs=True,
                      top_logprobs=True)
    assert p[0] == s[0]
    assert [[i for i, _ in alts] for alts in p[2]] == \
        [[i for i, _ in alts] for alts in s[2]]


# -- paged KV (KV_PAGED, tpu/kv_blocks.py) ------------------------------------


def _deactivate():
    """Drop the contextvar a recorder.start() activated — a leaked
    active record would bleed into unrelated tests in the same worker."""
    from gofr_tpu.telemetry import activate_record

    activate_record(None)


def test_kv_exhausted_reject_reason_and_solo_fallback():
    """Block starvation is observable at the flight-record level like
    every other reject: with the shared KV ledger pre-claimed, submit
    rejects with reason=kv_exhausted (distinct from slot rejects), the
    request decodes solo and still completes, and releasing the budget
    re-admits pooled requests — continuous admission, no drain wait."""
    from gofr_tpu.telemetry import FlightRecorder

    # tiny max_seq=128, 16-token blocks -> 8 blocks per full sequence
    dev, old = _device(DECODE_POOL="on", DECODE_SLOTS="2", DECODE_CHUNK="2",
                       KV_BLOCKS="8", KV_BLOCK_TOKENS="16")
    try:
        assert dev.kv_pool is not None
        claimed = dev.kv_pool.reserve_ledger(128)  # the whole ledger
        recorder = FlightRecorder()
        rec = recorder.start(model="tiny", endpoint="/t")
        try:
            out = dev.generate([1, 2, 3], max_new_tokens=6)
        finally:
            recorder.finish(rec)
            _deactivate()
        assert len(out) == 6  # solo fallback served it
        assert rec.pool_reject_reason == "kv_exhausted"
        counter = dev.metrics.counter(
            "gofr_tpu_pool_reject_total", labels=("reason",)
        )
        assert counter.value(reason="kv_exhausted") >= 1
        # freed budget admits the next request immediately
        dev.kv_pool.release_ledger(claimed)
        rec2 = recorder.start(model="tiny", endpoint="/t")
        try:
            out2 = dev.generate([1, 2, 3], max_new_tokens=6)
        finally:
            recorder.finish(rec2)
            _deactivate()
        assert out2 == out  # pooled and solo agree (bit-identity)
        assert rec2.pool_reject_reason == ""
        assert rec2.kv_blocks > 0  # pooled admission reserved blocks
        assert dev.kv_pool.stats()["reserved"] == 0  # released at finish
    finally:
        dev.close()
        _restore(old)


def test_paged_pooled_outputs_match_unpaged(pooled, solo):
    """The paged device (block-table prefix cache + ledger admission)
    produces bit-identical pooled output to the unpaged slot model —
    across prefix hits, partial hits, and conversation stores."""
    dev, old = _device(DECODE_POOL="on", DECODE_SLOTS="4", DECODE_CHUNK="4",
                       PREFIX_CACHE="3", PREFIX_LCP_MIN="4",
                       KV_BLOCK_TOKENS="16")
    try:
        assert dev.kv_pool is not None  # paging actually on
        system = [7, 3, 9, 2, 11, 5]
        prompts = [[1, 2, 3], [1, 2, 3], system + [21, 22],
                   system + [31, 32, 33], [5, 6]]
        for p in prompts:
            assert dev.generate(p, max_new_tokens=8) == \
                solo.generate(p, max_new_tokens=8), p
        # multi-turn conversation reuse through the paged store
        reply = dev.generate(system + [41], max_new_tokens=6)
        follow = system + [41] + reply + [42]
        assert dev.generate(follow, max_new_tokens=5) == \
            solo.generate(follow, max_new_tokens=5)
        st = dev.kv_pool.stats()
        assert st["reserved"] == 0  # every reservation released
        assert dev.decode_pool.occupancy()["kv"]["total"] == st["total"]
    finally:
        dev.close()
        _restore(old)
