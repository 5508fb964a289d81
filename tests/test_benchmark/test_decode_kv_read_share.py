"""``kernel.decode_kv_read_share``: the share of the pool's K/V cache that
the window's decode chunks had to read, from the program's own counters."""

import types

import pytest

from benchmark import spec

NAME = "kernel.decode_kv_read_share"


def _run(*chunks):
    return types.SimpleNamespace(dispatches=[
        {"kind": "decode_chunk", "status": "ok", "batch_size": 2, **fields} for fields in chunks
    ] + [{"kind": "prefill", "status": "ok", "batch_size": 1}])


def test_read_share_is_blocks_read_over_blocks_held():
    read = spec.load_module("layer_metrics", NAME).read
    # 8 steps x 6 slots x 16 blocks held; two chunks of two live rows
    held = 8 * 6 * 16
    run = _run({"kv_blocks_read": 8 * (3 + 4), "kv_blocks_held": held},
               {"kv_blocks_read": 8 * (12 + 1), "kv_blocks_held": held})
    assert read(run) == pytest.approx(100.0 * 8 * 20 / (2 * held))
    # a chunk that failed, and one of a state (no such fields), count for nothing
    run.dispatches.append({"kind": "decode_chunk", "status": "error",
                           "kv_blocks_read": 1, "kv_blocks_held": 1})
    run.dispatches.append({"kind": "decode_chunk", "status": "ok", "state_bytes": 7,
                           "kv_blocks_read": None, "kv_blocks_held": None})
    assert read(run) == pytest.approx(100.0 * 8 * 20 / (2 * held))


def test_a_program_without_the_counters_reads_nothing():
    """The parent's records carry neither field: the reader gives None and
    the harness leaves the metric out of the line."""
    read = spec.load_module("layer_metrics", NAME).read
    assert read(_run({}, {})) is None
    assert read(_run()) is None
