"""The trace reduction on a hand-built trace (benchmark/fixtures/). Nothing
here describes a topology or loads a TPU library."""

import os

import pytest

from benchmark import spec, trace_reduce


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    path = os.path.join(spec.HERE, "fixtures", "trace_small.textproto")
    with open(path, encoding="utf-8") as fh:
        data = ProfileData.from_text_proto(fh.read())
    return trace_reduce.reduce_trace(data), data


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_busy_is_the_union_of_device_operations(reduced):
    out, _ = reduced
    assert out["n_devices"] == 1
    assert out["window_s"] == pytest.approx(12e-3)
    assert out["busy_s"] == pytest.approx(6e-3)
    assert out["idle_share"] == pytest.approx(0.5)


def test_device_time_per_program(reduced):
    out, _ = reduced
    assert out["programs"]["jit__prefill_fn(11)"] == {"seconds": pytest.approx(3e-3), "runs": 1}
    assert out["programs"]["jit__lambda_(22)"] == {"seconds": pytest.approx(3e-3), "runs": 2}


def test_a_blocked_wait_is_released_by_the_run_that_ended_it():
    """On the hand-built trace of a pool beside a prefill and a solo chunk
    (fixtures/trace_spans.textproto): each kind's waits end on its own
    program; the wait that did not block and the tiny lambda after the solo
    chunk are placed nowhere."""
    from jax.profiler import ProfileData

    path = os.path.join(spec.HERE, "fixtures", "trace_spans.textproto")
    with open(path, encoding="utf-8") as fh:
        out = trace_reduce.reduce_trace(ProfileData.from_text_proto(fh.read()))
    assert out["released"] == {
        "gofr.pool.fetch_wait": {"jit__lambda_(22)": 2},
        "gofr.prefill.fetch_wait": {"jit__prefill_fn(11)": 1},
        "gofr.solo.fetch_wait": {"jit__lambda_(33)": 1},
    }


def test_a_wait_no_run_ended_and_a_helper_run_release_nothing():
    runs = [(0.0, 0.010, "pool"), (0.010, 0.0104, "slice"), (0.020, 0.030, "solo")]
    waits = [("worker", [(0.004, 0.0106, "gofr.pool.fetch_wait"),  # ends on pool's run, not the helper's
                         (0.012, 0.015, "gofr.pool.fetch_wait"),  # no run ended inside it
                         (0.0299, 0.0301, "gofr.solo.fetch_wait"),  # did not block
                         (0.021, 0.0301, "gofr.solo.fetch_wait"),
                         (0.005, 0.0450, "gofr.pool.fetch_wait"),  # a stalled transfer: 15 ms after solo's run
                         (0.004, 0.0106, "gofr.pool.deliver")])]  # not a wait span
    assert trace_reduce.released_by(waits, runs) == {
        "gofr.pool.fetch_wait": {"pool": 1}, "gofr.solo.fetch_wait": {"solo": 1}}


def test_operation_labels_are_short_and_containers_left_out():
    long = "%copy.2 = bf16[8,128]{1,0:T(8,128)(2,1)} copy(bf16[8,128]{0,1} %p), metadata={}"
    assert trace_reduce.short_op(long) == ("copy.2 bf16[8,128] copy", "copy")
    loop = "%while.3 = (s32[]{:T(128)}, bf16[2,4]{1,0}) while((s32[]{:T(128)}, bf16[2,4]{1,0}) %t)"
    assert trace_reduce.short_op(loop) == ("while.3 while", "while")
    assert trace_reduce.short_op("fusion.1") == ("fusion.1", "")


def test_top_operations_by_time(reduced):
    out, _ = reduced
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(4e-3)]
    assert dict(out["device_ops"])["custom-call.7"] == pytest.approx(1e-3)


def test_gaps_are_attributed_to_the_host(reduced):
    out, _ = reduced
    gaps = dict(out["idle_gaps"])
    assert gaps["pool:fetch"] == pytest.approx(4e-3)
    assert gaps["pool:deliver"] == pytest.approx(2e-3)
    assert out["idle_gaps"][0][0] == "pool:fetch"


def test_a_trace_without_device_operations_is_refused():
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto('planes { id: 2 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(data)


def test_describe_lists_planes_and_lines(reduced):
    _, data = reduced
    text = "\n".join(trace_reduce.describe(data))
    assert "/device:TPU:0" in text and "XLA Ops" in text and "fusion.1" in text
