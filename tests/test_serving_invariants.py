"""Absolute invariants of the compile-free serving path: counts and byte
totals that hold whatever the machine's speed. Each case drives the real
component (paged-KV engine, echo device, two echo replicas over HTTP, the
container) and asserts a number that a regression would move: no timing."""

import contextlib
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from gofr_tpu.config import EnvConfig
from gofr_tpu.logging import Level
from gofr_tpu.metrics import Registry
from gofr_tpu.testutil import MockLogger


@contextlib.contextmanager
def _env(**overrides):
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def _echo_device(**env):
    from gofr_tpu.tpu.device import new_device

    with _env(MODEL_NAME="echo", BATCH_MAX_SIZE="4", BATCH_TIMEOUT_MS="1",
              TIMEBASE_ENABLED="off", **env):
        device = new_device(EnvConfig(), MockLogger(Level.FATAL), Registry())
    device.wait_ready(30)
    return device


def _copied_bytes_per_hit(copy_mode=False, shards=1, hits=40):
    """Seed one cached conversation, then admit exact repeats and LCP
    partial hits against it: bytes the pool copied per admission."""
    from gofr_tpu.tpu.kv_blocks import BlockPool, HostPagedKV, HostTokenArena

    prompt = (np.arange(512, dtype=np.int32) * 7) % 251 + 1
    follow = np.concatenate(  # shared prefix of 24 whole blocks, new tail
        [prompt[:384], (np.arange(64, dtype=np.int32) % 97) + 1]
    ).astype(np.int32)
    arena = HostTokenArena(2048, 16, shards=shards)
    pool = BlockPool(2048, 16, arena=arena, cache_entries=64)
    eng = HostPagedKV(pool, arena, lcp_min=16, copy_mode=copy_mode)
    eng.finish(eng.admit(prompt, 0))
    base = pool.stats()["copied_kv_bytes"]
    for i in range(hits):
        seq = eng.admit(prompt if i % 2 == 0 else follow, 8)
        assert seq.kind in ("hit", "partial_hit")
        eng.finish(seq, store=False)
    return (pool.stats()["copied_kv_bytes"] - base) / hits


def _paged_copies_less_than_the_slot_model():
    paged = _copied_bytes_per_hit(copy_mode=False)
    copied = _copied_bytes_per_hit(copy_mode=True)
    # the copy model materialises every hit's shared tokens (4 bytes each)
    assert copied >= 384 * 4
    assert paged < copied
    assert paged <= 64  # at most the one boundary block an LCP hit extends


def _host_mesh_adds_no_copies():
    single = _copied_bytes_per_hit(shards=1)
    meshed = _copied_bytes_per_hit(shards=2)
    assert meshed <= single + 64


def _pooled_speculation_carries_several_tokens_a_dispatch():
    streams, n_tok = 4, 64
    prompts = [[(5 * i + 13 * s) % 241 + 1 for i in range(48)]
               for s in range(streams)]
    device = _echo_device(SPEC_POOLED="on", SPEC_K_MAX="4")
    try:
        device.generate(prompts[0], max_new_tokens=2)
        before = dict(device.runner.spec_stats)
        got: dict[int, list] = {}
        threads = [
            threading.Thread(
                target=lambda s=s: got.__setitem__(
                    s, device.generate(prompts[s], max_new_tokens=n_tok)),
                name=f"spec-stream-{s}",
            )
            for s in range(streams)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert [len(got.get(s, ())) for s in range(streams)] == [n_tok] * streams
        with device.runner._spec_lock:
            after = dict(device.runner.spec_stats)
    finally:
        device.close()
    cycles = after["cycles"] - before["cycles"]
    drafted = after["drafted"] - before["drafted"]
    accepted = after["accepted"] - before["accepted"]
    assert drafted > 0 and accepted / drafted > 0.0
    # a verify that stops carrying several tokens has become plain decode
    assert streams * n_tok / cycles >= 1.5


def _every_kv_pull_takes_the_fast_path_at_a_bounded_wire_size():
    from gofr_tpu.devtools.chaos import chaos_fleet
    from gofr_tpu.fleet import kvwire

    prompt_tokens, block_tokens, rounds = 96, 16, 4
    env = {"ECHO_STEP_MS": "0", "KV_BLOCK_TOKENS": str(block_tokens),
           "KV_TRANSFER_TIMEOUT_S": "5", "WATCHDOG_DISPATCH_TIMEOUT_S": "30"}

    def generate(replica, tokens, donor=None):
        headers = {"Content-Type": "application/json"}
        if donor is not None:
            headers["X-KV-Donor"] = donor.address
        req = urllib.request.Request(
            replica.address + "/generate",
            data=json.dumps({"tokens": tokens, "max_new_tokens": 1}).encode(),
            headers=headers, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()

    with chaos_fleet(2, env=env) as (donor, receiver):
        prompts = [
            [(j % 251) + 1
             for j in range(i * prompt_tokens, (i + 1) * prompt_tokens)]
            for i in range(rounds)
        ]
        for prompt in prompts:  # fresh prompts: a warm one skips the pull
            generate(donor, prompt)
            generate(receiver, prompt, donor=donor)
        with urllib.request.urlopen(
            donor.address + f"/admin/kv/{kvwire.prompt_hash(prompts[0])}",
            timeout=10,
        ) as resp:
            wire_bytes = len(resp.read())
        with urllib.request.urlopen(
            receiver.address + "/admin/engine", timeout=10
        ) as resp:
            stats = json.loads(resp.read())["data"]["kv_transfer"]
    assert stats["ok"] == rounds and stats["fallback"] == 0
    # echo KV is token ids, 4 bytes a token; framing is a 12-byte head a
    # block, a 16-byte trailer, and an 8-byte prefix on a header of JSON
    payload = prompt_tokens * 4
    framing = 12 * (prompt_tokens // block_tokens) + 16 + 8
    assert payload + framing < wire_bytes <= payload + framing + 512


def _an_abandoned_stream_returns_its_blocks_with_the_stop():
    device = _echo_device(KV_BLOCKS="64", KV_BLOCK_TOKENS="4")
    try:
        prompt = [(3 * i) % 251 + 1 for i in range(24)]
        device.generate(prompt, max_new_tokens=2)  # the prompt's cache entry
        kv = device.kv_pool
        baseline = kv.stats()
        stop = threading.Event()
        seen: list[int] = []

        def on_token(token):
            seen.append(token)
            if len(seen) == 3:  # what the SSE abort hook does on a failed write
                stop.set()

        out = device.generate(prompt, max_new_tokens=40, on_token=on_token,
                              stop=stop)
        after = kv.stats()
    finally:
        device.close()
    assert len(out) == len(seen) == 3  # not one token past the stop
    # on return, not some time later: nothing held, nothing newly cached
    assert after["active"] == 0
    assert after["free"] == baseline["free"]
    assert after["cached"] == baseline["cached"]


def _the_anomaly_ring_is_sized_by_its_key_and_refuses_zero():
    from gofr_tpu.container import Container

    with _env(LOG_LEVEL="FATAL", TIMEBASE_ENABLED="off", ANOMALY_RING_SIZE="3"):
        container = Container(EnvConfig())
    try:
        ring = container.slo.ring
        for i in range(5):
            ring.record(kind="slo", cause="slo_fast_burn", objective=str(i))
        assert ring.capacity == 3 and len(ring.events()) == 3
        assert ring.total() == 5
    finally:
        container.close()
    with _env(LOG_LEVEL="FATAL", TIMEBASE_ENABLED="off", ANOMALY_RING_SIZE="0"):
        with pytest.raises(ValueError, match="ANOMALY_RING_SIZE must be >= 1"):
            Container(EnvConfig())


@pytest.mark.parametrize("check", [
    _paged_copies_less_than_the_slot_model,
    _host_mesh_adds_no_copies,
    _pooled_speculation_carries_several_tokens_a_dispatch,
    _every_kv_pull_takes_the_fast_path_at_a_bounded_wire_size,
    _an_abandoned_stream_returns_its_blocks_with_the_stop,
    _the_anomaly_ring_is_sized_by_its_key_and_refuses_zero,
], ids=lambda f: f.__name__.lstrip("_"))
def test_invariant(check):
    check()
