"""TPU datasource tests on the CPU backend (the reference's
sqlmock/miniredis strategy: SURVEY.md §4 — CPU PJRT is the fake)."""

import asyncio
import threading
import time

import numpy as np
import pytest

from gofr_tpu.config import EnvConfig
from gofr_tpu.errors import TooManyRequestsError
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.testutil import MockLogger
from gofr_tpu.tpu.batcher import DynamicBatcher, next_pow2, pad_rows
from gofr_tpu.tpu.device import new_device

# XLA-compile-dominated module: deselect with -m 'not slow' for the
# fast developer loop (CI runs everything; CONTRIBUTING.md)
pytestmark = pytest.mark.slow


# -- batcher -----------------------------------------------------------------

def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]


def test_batcher_coalesces_concurrent_requests():
    batches = []

    def run(payloads):
        batches.append(len(payloads))
        return [p * 2 for p in payloads]

    b = DynamicBatcher(run, max_batch=8, timeout_ms=50)
    futures = [b.submit(i) for i in range(6)]
    results = [f.result(timeout=5) for f in futures]
    assert results == [0, 2, 4, 6, 8, 10]
    assert max(batches) > 1  # actually batched
    b.close()


def test_batcher_deadline_flush_bounds_latency():
    def run(payloads):
        return payloads

    b = DynamicBatcher(run, max_batch=64, timeout_ms=30)
    start = time.perf_counter()
    b.infer("solo", timeout=5)
    elapsed = time.perf_counter() - start
    assert elapsed < 1.0  # flushed by deadline, not stuck waiting for 64
    b.close()


def test_batcher_overflow_sheds_load():
    release = threading.Event()

    def run(payloads):
        release.wait(5)
        return payloads

    b = DynamicBatcher(run, max_batch=1, timeout_ms=1, max_queue=2)
    futures = [b.submit(i) for i in range(2)]
    time.sleep(0.05)
    with pytest.raises(TooManyRequestsError):
        for i in range(8):  # queue of 2 + in-flight; must overflow
            b.submit(i)
    release.set()
    for f in futures:
        f.result(timeout=5)
    b.close()


def test_batcher_propagates_errors():
    def run(payloads):
        raise RuntimeError("device on fire")

    b = DynamicBatcher(run, max_batch=4, timeout_ms=1)
    with pytest.raises(RuntimeError, match="device on fire"):
        b.infer("x", timeout=5)
    b.close()


def test_batcher_async_api():
    def run(payloads):
        return [p + 1 for p in payloads]

    b = DynamicBatcher(run, max_batch=4, timeout_ms=1)

    async def main():
        return await b.infer_async(41)

    assert asyncio.run(main()) == 42
    b.close()


def test_pad_rows():
    rows = [np.ones(3), np.zeros(3)]
    out = pad_rows(rows, 4)
    assert out.shape == (4, 3)
    np.testing.assert_array_equal(out[2], out[1])  # repeats last row


# -- device: MLP -------------------------------------------------------------

@pytest.fixture(scope="module")
def mlp_device(tmp_path_factory):
    import os

    env = {"MODEL_NAME": "mlp", "BATCH_MAX_SIZE": "8", "BATCH_TIMEOUT_MS": "2"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    device = new_device(EnvConfig(), MockLogger(Level.DEBUG), Registry())
    yield device
    device.close()
    for k, v in old.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_mlp_infer(mlp_device):
    out = mlp_device.infer([0.5] * 64)
    assert out.shape == (16,)
    assert np.isfinite(out).all()


def test_mlp_infer_batched_concurrently(mlp_device):
    results = [None] * 6
    threads = [
        threading.Thread(target=lambda i=i: results.__setitem__(
            i, mlp_device.infer([float(i)] * 64)))
        for i in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None and r.shape == (16,) for r in results)
    # identical inputs give identical outputs regardless of batch packing
    a = mlp_device.infer([1.0] * 64)
    bq = mlp_device.infer([1.0] * 64)
    np.testing.assert_allclose(a, bq, rtol=1e-5)


def test_mlp_invalid_input(mlp_device):
    from gofr_tpu.errors import InvalidParamError

    with pytest.raises(InvalidParamError):
        mlp_device.infer([1.0, 2.0])


def test_device_health_and_metrics(mlp_device):
    h = mlp_device.health_check()
    assert h.status == "UP"
    assert h.details["device_count"] >= 1
    assert "platform" in h.details
    mlp_device.infer([0.0] * 64)
    text = mlp_device.metrics.expose()
    assert "gofr_tpu_requests_total" in text
    assert "gofr_tpu_batch_size" in text
    assert "gofr_tpu_ttft_seconds" in text
    assert "mlp" in mlp_device.describe()


def test_unknown_model_name(monkeypatch):
    monkeypatch.setenv("MODEL_NAME", "gpt-17")
    with pytest.raises(ValueError, match="unknown MODEL_NAME"):
        new_device(EnvConfig(), MockLogger(), Registry())


# -- device: transformer generation ------------------------------------------

@pytest.fixture(scope="module")
def tiny_device():
    import os

    # DECODE_CHUNK=1: token-granular stop/stream semantics for the
    # cancellation tests (chunked decode is covered by
    # test_chunked_decode_matches_stepwise)
    env = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "2",
           "DECODE_CHUNK": "1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    device = new_device(EnvConfig(), MockLogger(Level.DEBUG), Registry())
    yield device
    device.close()
    for k, v in old.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_generate_deterministic_and_streams(tiny_device):
    streamed = []
    out = tiny_device.generate([1, 2, 3], max_new_tokens=5, on_token=streamed.append)
    assert len(out) == 5
    assert out == streamed
    assert all(0 <= t < 256 for t in out)
    again = tiny_device.generate([1, 2, 3], max_new_tokens=5)
    assert again == out  # greedy decode is deterministic


def test_generate_respects_cache_bound(tiny_device):
    # max_seq=128: a long generation stops at the cache bound, no crash
    out = tiny_device.generate(list(range(1, 60)), max_new_tokens=500)
    assert len(out) <= 128


def test_infer_returns_prefill_state(tiny_device):
    state = tiny_device.infer({"tokens": [1, 2, 3, 4]})
    assert state["logits"].shape[-1] == 256
    assert state["length"] == 4


def test_generate_stream_yields_and_completes(tiny_device):
    toks = list(tiny_device.generate_stream([1, 2, 3], max_new_tokens=5))
    assert toks == tiny_device.generate([1, 2, 3], max_new_tokens=5)


def test_generate_stream_close_cancels_decode(tiny_device, monkeypatch):
    # closing the iterator must halt the BACKGROUND decode, observed on the
    # actual closed stream: slow each token down, close after two, then
    # assert production stops (not just that a fresh pre-set event stops)
    import time

    produced = []
    real_generate = tiny_device.generate

    def spy(tokens, max_new_tokens=32, on_token=None, stop=None, **kw):
        def slow_token(t):
            produced.append(t)
            on_token(t)
            time.sleep(0.02)

        return real_generate(
            tokens, max_new_tokens, on_token=slow_token, stop=stop, **kw
        )

    monkeypatch.setattr(tiny_device, "generate", spy)
    it = tiny_device.generate_stream([1, 2, 3], max_new_tokens=100)
    next(it)
    next(it)
    it.close()
    # decode halts at the next step boundary; allow a few in-flight steps
    time.sleep(0.3)
    n_after_close = len(produced)
    assert n_after_close < 20, "decode kept running after the stream closed"
    time.sleep(0.3)
    assert len(produced) == n_after_close, "tokens still being produced after close"


def test_generate_with_preset_stop_event(tiny_device):
    ev = threading.Event()
    ev.set()
    out = tiny_device.generate([1, 2, 3], max_new_tokens=64, stop=ev)
    assert len(out) == 1  # prefill token only; decode loop never entered


def test_stop_event_mid_decode(tiny_device):
    ev = threading.Event()
    seen = []

    def on_token(t):
        seen.append(t)
        if len(seen) == 3:
            ev.set()

    out = tiny_device.generate([1, 2, 3], max_new_tokens=64, on_token=on_token, stop=ev)
    assert len(out) == 3  # stopped at the next step boundary


# -- tokenizer wiring ---------------------------------------------------------

@pytest.fixture(scope="module")
def text_device():
    import os

    env = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "2",
           "TOKENIZER": "byte"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    device = new_device(EnvConfig(), MockLogger(Level.DEBUG), Registry())
    yield device
    device.close()
    for k, v in old.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_text_payload_infer(text_device):
    state = text_device.infer({"text": "hello"})
    assert state["length"] == 5  # byte-level: one id per byte
    assert "tokenizer=" in text_device.describe()


def test_text_generate_matches_ids(text_device):
    by_text = text_device.generate("hi", max_new_tokens=4)
    by_ids = text_device.generate([ord("h"), ord("i")], max_new_tokens=4)
    assert by_text == by_ids


def test_text_without_tokenizer_rejected(tiny_device):
    from gofr_tpu.errors import InvalidParamError

    with pytest.raises(InvalidParamError, match="tokenizer"):
        tiny_device.infer({"text": "hello"})


def test_out_of_range_ids_rejected(tiny_device):
    from gofr_tpu.errors import InvalidParamError

    with pytest.raises(InvalidParamError, match="token ids"):
        tiny_device.infer({"tokens": [1, 2, 999999]})


def test_chunked_decode_matches_stepwise(tiny_device):
    # the default chunked decode (N steps per dispatch) must emit the same
    # greedy sequence as token-at-a-time decode
    import os

    env = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "2",
           "DECODE_CHUNK": "8"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        chunked = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            for prompt, n in (([1, 2, 3], 13), ([9] * 20, 8), ([4], 1)):
                assert chunked.generate(prompt, max_new_tokens=n) == \
                    tiny_device.generate(prompt, max_new_tokens=n), (prompt, n)
        finally:
            chunked.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_reinit_rebuilds_working_stack(tiny_device):
    before = tiny_device.generate([1, 2, 3], max_new_tokens=5)
    # wedge the stack the way device loss presents: runner calls fail
    tiny_device.runner.run_batch = lambda payloads: (_ for _ in ()).throw(
        RuntimeError("device lost")
    )
    tiny_device.batcher.close()
    tiny_device.reinit()
    after = tiny_device.generate([1, 2, 3], max_new_tokens=5)
    assert after == before  # fresh stack, same params seed
    h = tiny_device.health_check()
    assert h.status == "UP"


def test_auto_reinit_rate_limited(tiny_device):
    import time as time_mod

    tiny_device._last_reinit = time_mod.monotonic()
    assert tiny_device._maybe_auto_reinit() is False  # within the 30s window


def test_model_buckets_limits_warmup_compiles():
    import os

    env = {"MODEL_NAME": "tiny", "MODEL_BUCKETS": "64", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device.runner.buckets == [64]
            out = device.generate([1, 2, 3], max_new_tokens=4)
            assert len(out) == 4
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_token_counter_and_param_count(tiny_device):
    tiny_device.infer({"tokens": [1, 2, 3, 4, 5]})
    text = tiny_device.metrics.expose()
    assert 'gofr_tpu_tokens_total{model="tiny",op="prefill"}' in text
    from gofr_tpu.tpu.flops import transformer_param_count

    # analytic count matches the materialized tree
    import jax

    n_leaf = sum(
        int(np.prod(x.shape))
        for x in jax.tree.leaves(tiny_device.runner.params)
    )
    assert transformer_param_count(tiny_device.runner.cfg) == n_leaf


def test_background_boot_and_readiness():
    import os

    env = {"MODEL_NAME": "tiny", "TPU_BOOT": "background", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            # health is UP (alive) even before ready; requests block until
            # warm instead of crashing
            assert device.health_check().status == "UP"
            out = device.generate([1, 2, 3], max_new_tokens=4)
            assert len(out) == 4
            assert device.ready()
            assert device.boot_status["state"] == "ready"
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_failed_background_boot_recovers(monkeypatch):
    """A transient init failure in a background boot is not terminal: the
    health check's rate-limited rebuild path recovers the stack and flips
    readiness back."""
    import os

    import gofr_tpu.tpu.device as device_mod

    calls = {"n": 0}
    orig = device_mod._build_runner

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient init failure")
        return orig(*a, **k)

    monkeypatch.setattr(device_mod, "_build_runner", flaky)
    env = {"MODEL_NAME": "tiny", "TPU_BOOT": "background", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1", "DECODE_POOL": "off"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device._ready.wait(30)
            assert not device.ready()
            assert device.boot_status["state"] == "failed"
            device._last_reinit = -1e9  # bypass the 30s rate limit for the test
            h = device.health_check()
            assert h.status == "UP" and h.details.get("reinitialized")
            assert device.ready()
            assert len(device.generate([1, 2, 3], max_new_tokens=3)) == 3
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_wedged_device_probe_does_not_block_construction(monkeypatch):
    """jax.devices() can hang when the device runtime does not answer;
    with TPU_BOOT=background the constructor must return immediately and
    readiness must report the probing stage (a hang before the server
    listens emits no diagnostics at all)."""
    import os

    import gofr_tpu.tpu.device as device_mod

    release = threading.Event()
    real_devices = device_mod.jax.devices

    def blocking_devices(*a, **k):
        release.wait(30)
        return real_devices(*a, **k)

    monkeypatch.setattr(device_mod.jax, "devices", blocking_devices)
    env = {"MODEL_NAME": "tiny", "TPU_BOOT": "background", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1", "DECODE_POOL": "off"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        start = time.perf_counter()
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        construction = time.perf_counter() - start
        try:
            assert construction < 5.0  # not blocked on the wedged probe
            assert not device.ready()
            # poll: the boot thread may not have been scheduled yet
            deadline = time.perf_counter() + 10
            while (
                device.boot_status["detail"] != "probing device runtime"
                and time.perf_counter() < deadline
            ):
                time.sleep(0.01)
            assert device.boot_status["detail"] == "probing device runtime"
            assert device.health_check().status == "UP"  # alive, not ready
            release.set()
            device.wait_ready(60)
            assert len(device.generate([1, 2, 3], max_new_tokens=3)) == 3
        finally:
            release.set()
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_model_max_seq_bounds_cache():
    import os

    env = {"MODEL_NAME": "tiny", "MODEL_MAX_SEQ": "64", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1", "MODEL_QUANT": "int8"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device.runner.cfg.max_seq == 64
            assert device.runner.buckets[-1] <= 64
            out = device.generate(list(range(1, 50)), max_new_tokens=100)
            assert len(out) <= 64 - 49 + 1  # bounded by the reduced cache
            assert "quant=int8" in device.describe()
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_int4_serving_generates():
    """MODEL_QUANT=int4 boots and serves; packed int4 leaves in the runner
    tree; generation runs through prefill + pooled decode."""
    import os

    import jax.numpy as jnp

    env = {"MODEL_NAME": "tiny", "MODEL_QUANT": "int4", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1", "DECODE_CHUNK": "4"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device.runner.params["layers"]["wq"]["q4"].dtype == jnp.int4
            out = device.generate([1, 2, 3], max_new_tokens=6)
            assert len(out) == 6
            assert all(0 <= t < device.runner.cfg.vocab_size for t in out)
            assert "quant=int4" in device.describe()
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_chunked_prefill_matches_full_bucket():
    """A prompt longer than the largest compiled bucket prefills through
    bucket-sized chunks into one cache row — generation must equal a
    device whose ladder covers the prompt in a single shot (no
    truncation), and the batched /infer path keeps the recency clip."""
    import os

    base = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1"}
    old = {k: os.environ.get(k) for k in {**base, "MODEL_BUCKETS": None}}
    prompt = [(i % 11) + 1 for i in range(100)]
    try:
        os.environ.update(base)
        os.environ["MODEL_BUCKETS"] = "128"
        full = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            want = full.generate(prompt, max_new_tokens=8)
        finally:
            full.close()
        os.environ["MODEL_BUCKETS"] = "32"
        small = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert small.runner.buckets == [32]
            got = small.generate(prompt, max_new_tokens=8)
            # same tokens from 4 chunked prefills as from one 128-bucket
            assert got == want, (got, want)
            # /infer (batched path) still clips to the top bucket
            clipped = small.infer({"tokens": prompt})
            assert clipped["next_token"] == small.infer(
                {"tokens": prompt[-32:]}
            )["next_token"]
        finally:
            small.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_prefill_chunk_budget_bit_identical_and_bounded():
    """PREFILL_CHUNK_TOKENS: a prompt whose bucket exceeds the budget
    prefills in >= 2 bounded chunks through the warmed budget bucket —
    and the output tokens are BIT-IDENTICAL to the unbudgeted path (the
    chunk-resume contract in models/transformer.py::prefill)."""
    import os

    base = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
            "MODEL_BUCKETS": "16,32,64"}
    old = {k: os.environ.get(k)
           for k in {**base, "PREFILL_CHUNK_TOKENS": None}}
    prompt = [(i % 9) + 1 for i in range(40)]  # the 64 bucket, > 2x budget
    try:
        os.environ.update(base)
        os.environ.pop("PREFILL_CHUNK_TOKENS", None)
        plain = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert plain.runner.prefill_chunk_bucket is None
            want = plain.generate(prompt, max_new_tokens=8)
        finally:
            plain.close()
        os.environ["PREFILL_CHUNK_TOKENS"] = "16"
        registry = Registry()
        budget = new_device(EnvConfig(), MockLogger(Level.INFO), registry)
        try:
            assert budget.runner.prefill_chunk_bucket == 16
            chunks = registry.counter(
                "gofr_tpu_prefill_chunks_total", labels=("model",)
            )
            before = chunks.value(model="tiny")
            got = budget.generate(prompt, max_new_tokens=8)
            assert got == want, (got, want)  # bit-identical to unchunked
            # 40 tokens through a 16-wide budget = 3 bounded dispatches
            assert chunks.value(model="tiny") - before >= 3
        finally:
            budget.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_budgeted_prefill_alongside_pooled_stream():
    """A >1-bucket prompt admitted while a pooled stream decodes: the
    prefill lands in bounded chunks (scheduler-admitted), both requests
    finish with their exact interference-free outputs, and the pool's
    cadence notes flowed through the shared scheduler. (The bounded
    inter-chunk gap itself is asserted deterministically in
    tests/test_scheduler.py — dispatch-order interleaving.)"""
    import os
    import threading

    env = {"MODEL_NAME": "tiny", "BATCH_MAX_SIZE": "2", "BATCH_TIMEOUT_MS": "1",
           "MODEL_BUCKETS": "16,32,64", "PREFILL_CHUNK_TOKENS": "16",
           "DECODE_CHUNK": "1", "DECODE_SLOTS": "2"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    prompt = [(i % 9) + 1 for i in range(40)]
    try:
        registry = Registry()
        dev = new_device(EnvConfig(), MockLogger(Level.INFO), registry)
        try:
            assert dev.decode_pool is not None
            stream_prompt = [5, 6, 7]
            stream_out: list[int] = []
            first = threading.Event()

            def on_token(t):
                stream_out.append(t)
                first.set()

            worker = threading.Thread(
                target=dev.generate,
                args=(stream_prompt,),
                kwargs={"max_new_tokens": 80, "on_token": on_token},
            )
            worker.start()
            assert first.wait(60)  # the pooled stream is live
            chunks = registry.counter(
                "gofr_tpu_prefill_chunks_total", labels=("model",)
            )
            before = chunks.value(model="tiny")
            got = dev.generate(prompt, max_new_tokens=4)
            worker.join(timeout=120)
            assert not worker.is_alive()
            # the long prefill went through in bounded chunks mid-traffic
            assert chunks.value(model="tiny") - before >= 3
            assert dev.scheduler.stats["decode_chunks"] >= 1
            # neither request perturbed the other: greedy outputs equal
            # their interference-free reruns exactly
            assert got == dev.generate(prompt, max_new_tokens=4)
            assert stream_out == dev.generate(stream_prompt, max_new_tokens=80)
        finally:
            dev.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_attn_impl_override():
    import os

    env = {"MODEL_NAME": "tiny", "MODEL_ATTN_IMPL": "xla", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device.runner.cfg.attn_impl == "xla"
            assert len(device.generate([1, 2, 3], max_new_tokens=4)) == 4
        finally:
            device.close()
        os.environ["MODEL_ATTN_IMPL"] = "nope"
        with pytest.raises(ValueError, match="MODEL_ATTN_IMPL"):
            new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_bad_model_quant_fails_fast():
    import os

    env = {"MODEL_NAME": "tiny", "MODEL_QUANT": "fp4"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        with pytest.raises(ValueError, match="int8, int4, or w8a8"):
            new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_flops_helpers():
    from gofr_tpu.tpu.flops import device_peak_flops, mfu, train_mfu

    assert device_peak_flops("TPU v5 lite", "tpu") == 197e12
    assert device_peak_flops("TPU v4", "tpu") == 275e12
    assert device_peak_flops("unknown", "cpu") == 0.0  # no peak off-TPU
    assert mfu(100, 10, 0.0, 1e3) == 0.0  # degenerate inputs never divide by 0
    assert train_mfu(100, 10, 1.0, 1e12) == pytest.approx(3 * mfu(100, 10, 1.0, 1e12))


def test_seq_bucket_ladder_covers_full_context():
    """The bucket ladder must reach the model family's max context: a
    ladder capped short silently truncates long prompts to its top
    bucket (prepare keeps the LAST tokens, so the user would see answers
    computed from a suffix with no error)."""
    from gofr_tpu.models.llama import LLAMA3_8B
    from gofr_tpu.tpu.device import _TransformerRunner

    assert _TransformerRunner.SEQ_BUCKETS[-1] >= LLAMA3_8B.max_seq


def test_f8_kv_cache_serving():
    """MODEL_KV_DTYPE=f8 stores the cache in float8 (2x tokens per HBM
    byte): serving and the pooled decode run end-to-end on it."""
    import os

    import jax.numpy as jnp

    env = {"MODEL_NAME": "tiny", "MODEL_KV_DTYPE": "f8", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1", "DECODE_SLOTS": "2"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device.runner.cfg.cache_dtype == jnp.float8_e4m3fn
            assert device.runner._zero_cache(2)["k"].dtype == jnp.float8_e4m3fn
            assert device.decode_pool is not None  # pool cache is f8 too
            assert device.decode_pool.cache["k"].dtype == jnp.float8_e4m3fn
            out = device.generate([1, 2, 3, 4], max_new_tokens=8)
            assert len(out) == 8 and all(0 <= t < 256 for t in out)
            again = device.generate([1, 2, 3, 4], max_new_tokens=8)
            assert again == out  # still deterministic under greedy
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_bad_kv_dtype_rejected(monkeypatch):
    monkeypatch.setenv("MODEL_NAME", "tiny")
    monkeypatch.setenv("MODEL_KV_DTYPE", "int4")
    with pytest.raises(ValueError, match="MODEL_KV_DTYPE"):
        new_device(EnvConfig(), MockLogger(), Registry())


def test_bert_param_count_matches_tree():
    import jax

    from gofr_tpu.models.bert import BertConfig, init_bert
    from gofr_tpu.tpu.flops import bert_param_count

    cfg = BertConfig(vocab_size=512, dim=64, n_layers=2, n_heads=2,
                     hidden_dim=128, max_seq=64)
    tree = init_bert(jax.random.key(0), cfg)
    n_leaf = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert bert_param_count(cfg) == n_leaf


def test_bert_serving_counts_tokens(monkeypatch):
    monkeypatch.setenv("MODEL_NAME", "bert-tiny")
    monkeypatch.setenv("BATCH_MAX_SIZE", "2")
    monkeypatch.setenv("BATCH_TIMEOUT_MS", "1")
    device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
    try:
        out = device.infer({"tokens": [1, 2, 3]})
        assert np.isfinite(np.asarray(out)).all()
        text = device.metrics.expose()
        assert 'gofr_tpu_tokens_total{model="bert-tiny",op="prefill"} 3' in text
    finally:
        device.close()


def test_w8a8_serving_generates():
    """MODEL_QUANT=w8a8 boots and serves: q8 packs in the runner tree
    (lm_head weight-only), generation through prefill + pooled decode."""
    import os

    import jax.numpy as jnp

    env = {"MODEL_NAME": "tiny", "MODEL_QUANT": "w8a8", "BATCH_MAX_SIZE": "2",
           "BATCH_TIMEOUT_MS": "1", "DECODE_CHUNK": "4"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        device = new_device(EnvConfig(), MockLogger(Level.INFO), Registry())
        try:
            assert device.runner.params["layers"]["wq"]["q8"].dtype == jnp.int8
            assert set(device.runner.params["lm_head"]) == {"q", "scale"}
            out = device.generate([1, 2, 3], max_new_tokens=6)
            assert len(out) == 6
            assert all(0 <= t < device.runner.cfg.vocab_size for t in out)
            assert "quant=w8a8" in device.describe()
        finally:
            device.close()
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_default_eos_stop(tmp_path):
    """Generation stops at the checkpoint's EOS by default: the ids come
    from generation_config.json (int or list) next to MODEL_PATH, else
    the tokenizer's eos; GEN_STOP_TOKENS overrides; GEN_STOP_EOS=off
    disables. (OpenAI semantics — a real instruct model must never run
    past <|eot_id|> to max_tokens.)"""
    import json

    from gofr_tpu.testutil import serving_device
    from gofr_tpu.tpu.device import _checkpoint_eos_ids

    # unit: generation_config parsing
    (tmp_path / "generation_config.json").write_text(
        json.dumps({"eos_token_id": [128001, 128009]})
    )
    assert _checkpoint_eos_ids(str(tmp_path / "model.safetensors"), None) \
        == {128001, 128009}
    (tmp_path / "generation_config.json").write_text(
        json.dumps({"eos_token_id": 7})
    )
    assert _checkpoint_eos_ids(str(tmp_path), None) == {7}
    assert _checkpoint_eos_ids(None, None) == set()

    # e2e: pick the plain greedy continuation's second token as the
    # "eos" via GEN_STOP_TOKENS — generation must end before emitting it
    with serving_device(DECODE_CHUNK="4", TOKENIZER="") as dev:
        free = dev.generate([1, 2, 3], max_new_tokens=6)
        assert dev.default_stop_ids == frozenset()  # no tokenizer/ckpt
    with serving_device(DECODE_CHUNK="4",
                        GEN_STOP_TOKENS=str(free[1])) as dev:
        assert dev.default_stop_ids == {free[1]}
        out = dev.generate([1, 2, 3], max_new_tokens=6)
        assert out == free[:1]  # stopped before the configured id
        # request stops COMPOSE with the default
        out2 = dev.generate([1, 2, 3], max_new_tokens=6,
                            stop_tokens=[free[0]])
        assert out2 == []
    with serving_device(DECODE_CHUNK="4", GEN_STOP_TOKENS=str(free[1]),
                        GEN_STOP_EOS="off") as dev:
        assert dev.default_stop_ids == frozenset()
        assert dev.generate([1, 2, 3], max_new_tokens=6) == free
