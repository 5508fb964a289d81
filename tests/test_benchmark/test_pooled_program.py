"""``readers.pooled_program``: which program of a reduced trace is the
pool's, told by whose runs end the pool's own waits, and the decode
rooflines and shares of the peak that divide by its time. On a hand-built
reduced trace and hand-built records. No chip."""

from types import SimpleNamespace

import pytest

from benchmark import readers, spec

SIZES = {"dim": 8, "layers": 2, "heads": 2, "kv_heads": 1, "head_dim": 4, "ffn": 16,
         "vocab": 32, "quant": "int8", "dtype": "bfloat16", "phi": 12}
PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
POOLED, SOLO, OTHER = "jit__lambda(7)", "jit__lambda(9)", "jit__prefill_fn(3)"
POOL_WAIT, SOLO_WAIT = "gofr.pool.fetch_wait", "gofr.solo.fetch_wait"


def _records(kind, n, **fields):
    return [dict({"kind": kind, "status": "ok", "batch_size": 1}, **fields) for _ in range(n)]


def _run(programs, released, dispatches=None):
    trace = None if programs is None else {"programs": programs}
    if released is not None:  # a trace reduced before PR 32 has no such key
        trace["released"] = released
    return SimpleNamespace(trace=trace, dispatches=dispatches or _records("decode_chunk", 14, batch_size=5),
                           w0=0.0, w1=51.0, records=[], sizes=SIZES, peaks=PEAKS,
                           server_env={"DECODE_CHUNK": "8"})


def _saturated(pool_waits=None):
    """Past the knee since PR 31: the solo fallback's lambda has more of the
    trace than the pool's (27 runs of 80.7 ms against 14 of 115.9); each
    ends the waits of its own span."""
    programs = {POOLED: {"seconds": 0.1159 * 14, "runs": 14},
                SOLO: {"seconds": 0.0807 * 27, "runs": 27},
                OTHER: {"seconds": 0.3, "runs": 6}}
    released = {POOL_WAIT: {POOLED: 13} if pool_waits is None else pool_waits,
                SOLO_WAIT: {SOLO: 26}, "gofr.prefill.fetch_wait": {OTHER: 6}}
    dispatches = (_records("decode_chunk", 14, batch_size=5) + _records("decode_solo", 27)
                  + _records("prefill", 6)
                  + [dict(_records("decode_chunk", 1)[0], status="error")])
    return _run(programs, released, dispatches)


def test_the_pooled_program_is_the_one_whose_runs_end_the_pools_waits():
    run = _saturated()
    programs = run.trace["programs"]
    assert programs[SOLO]["seconds"] > programs[POOLED]["seconds"]  # "most device time" misreads
    assert readers.pooled_program(run) == POOLED
    # one wait in ten placed on another program's run is within the rule
    assert readers.pooled_program(_saturated({POOLED: 18, SOLO: 2})) == POOLED


@pytest.mark.parametrize("name", ["kernel.steady.decode_step_roofline",
                                  "kernel.saturated.decode_step_roofline"])
def test_the_decode_roofline_divides_by_the_pooled_programs_time(name):
    run = _saturated()
    flops, nbytes = spec.load_module("kernels", "decode_step").work(run, 14)
    least = max(flops / PEAKS["bf16_flops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])
    read = spec.load_module("layer_metrics", name).read
    assert read(run) == pytest.approx(100.0 * least / (0.1159 * 14))
    # the solo program's time under the pooled sheet is what read 89.6% in PR 31's line
    assert read(run) != pytest.approx(100.0 * least / (0.0807 * 27))


@pytest.mark.parametrize("name,sheet", [
    ("kernel.steady.decode_step_mfu", "decode_step"),
    ("kernel.saturated.decode_step_mfu", "decode_step"),
    ("kernel.retention.decode_step_mfu", "retention_decode_step"),
])
def test_the_steps_share_of_the_peak_is_its_flops_over_the_pooled_programs_time(name, sheet):
    run = _saturated()
    flops, _ = spec.load_module("kernels", sheet).work(run, 14)
    read = spec.load_module("layer_metrics", name).read
    assert flops > 0
    assert read(run) == pytest.approx(100.0 * flops / (PEAKS["bf16_flops_per_s"] * 0.1159 * 14))
    assert read(_saturated({POOLED: 7, SOLO: 6})) is None and read(_run(None, None, run.dispatches)) is None


@pytest.mark.parametrize("name,sheet", [
    ("kernel.prefill_step_mfu", "prefill_step"),
    ("kernel.retention.prefill_step_mfu", "retention_prefill_step"),
])
def test_the_prefill_steps_share_of_the_peak(name, sheet):
    run = _saturated()
    for d in run.dispatches:  # what a prefill record carries: one row of a 16 bucket, 6 pads
        if d["kind"] == "prefill":
            d.update(bucket=16, padded_tokens=6, tokens=10)
    flops, _ = spec.load_module("kernels", sheet).work(run, 6)
    read = spec.load_module("layer_metrics", name).read
    assert read(run) == pytest.approx(100.0 * flops / (PEAKS["bf16_flops_per_s"] * 0.3))
    roofline = spec.load_module("layer_metrics", name.replace("_mfu", "_roofline")).read
    assert read(run) <= roofline(run)  # the roofline takes the larger of FLOPs and bytes
    run.trace["programs"].pop(OTHER)
    assert read(run) is None  # no prefill program in the trace: nothing, never 0


def test_the_retention_readers_take_the_same_program():
    run = _saturated()
    for d in run.dispatches:
        d["state_bytes"] = 4096
    run.trace["device_ops"] = [["retention_step.3 f32[2,6] custom-call", 0.5], ["fusion.1", 0.4]]
    read = spec.load_module("layer_metrics", "kernel.retention.step_roofline").read
    assert read(run) == pytest.approx(100.0 * 14 * 4096 / PEAKS["hbm_bytes_per_s"] / 0.5)  # 14 pooled runs
    roofline = spec.load_module("layer_metrics", "kernel.retention.decode_step_roofline").read
    undecided = _saturated({POOLED: 7, SOLO: 6})
    undecided.trace["device_ops"] = run.trace["device_ops"]
    assert roofline(run) is not None and roofline(undecided) is None and read(undecided) is None


@pytest.mark.parametrize("run,why", [
    (_saturated({POOLED: 7, SOLO: 6}), "the pool's waits end on two programs alike"),
    (_saturated({POOLED: 2}), "two waits placed are too few"),
    (_saturated({}), "no wait of the pool's was placed"),
    (_run({POOLED: {"seconds": 1.0, "runs": 9}}, {SOLO_WAIT: {POOLED: 9}}), "no wait of the pool's"),
    (_run({POOLED: {"seconds": 1.0, "runs": 9}}, None, None), "a trace reduced before PR 32"),
    (_run(None, None), "no trace"),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_nothing_is_read_where_the_rule_cannot_decide(run, why):
    assert readers.pooled_program(run) is None, why
    assert readers.decode_step_roofline(run) is None
    assert spec.load_module("layer_metrics", "kernel.retention.decode_step_roofline").read(run) is None


def test_one_program_alone_is_the_pooled_one_when_the_pools_waits_end_on_it():
    """A steady cell: the pool refuses nobody, so no solo program runs."""
    run = _run({POOLED: {"seconds": 4.9, "runs": 47}}, {POOL_WAIT: {POOLED: 46}})
    assert readers.pooled_program(run) == POOLED
