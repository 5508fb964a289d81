"""Shared arithmetic of the metric readers. A reader file under
``end_to_end/`` or ``layer_metrics/`` is one ``read(run)`` that picks its
source here; ``run`` is ``benchmark.run.Run``. A reader that finds nothing
to read returns None and the harness leaves the metric out of the line."""

from __future__ import annotations

from typing import Any, Callable, Optional

from benchmark import metrics as M
from benchmark import spec


def pct(values: list[float], q: float, scale: float = 1.0) -> Optional[float]:
    return M.percentile(values, q) * scale if values else None


def flights(run: Any, field: str) -> list[float]:
    """A numeric FlightRecord field over the window's finished requests."""
    return [r[field] or 0.0 for r in run.flights if r.get("status") == "ok"]


def dispatches(run: Any, kinds: tuple[str, ...]) -> list[dict]:
    return [d for d in run.dispatches if d["kind"] in kinds and d["status"] == "ok"]


def program_seconds(run: Any, match: Callable[[str], bool]) -> tuple[float, int]:
    """Device seconds and runs of the traced programs whose name matches."""
    if run.trace is None:
        return 0.0, 0
    hits = [v for name, v in run.trace["programs"].items() if match(name)]
    return sum(v["seconds"] for v in hits), int(sum(v["runs"] for v in hits))


def is_prefill(name: str) -> bool:
    """The trace's name for a prefill program (``jit__prefill_fn(<id>)``)."""
    return name.startswith("jit__prefill_fn")


def traced_work(run: Any, kernel: str, match: Callable[[str], bool]) -> Optional[tuple]:
    """(flops, bytes, device seconds) of the traced runs of the programs
    whose name matches; the work comes from
    ``kernels/<kernel>.py::work(run, runs) -> (flops, bytes)``."""
    seconds, runs = program_seconds(run, match)
    if not runs or seconds <= 0 or run.peaks is None:
        return None
    flops, nbytes = spec.load_module("kernels", kernel).work(run, runs)
    return flops, nbytes, seconds


def roofline_share(run: Any, kernel: str, match: Callable[[str], bool]) -> Optional[float]:
    """100 x (least time the chip could take for the traced runs of a
    program) / (device time the trace shows for them)."""
    work = traced_work(run, kernel, match)
    if work is None:
        return None
    flops, nbytes, seconds = work
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def mfu_share(run: Any, kernel: str, match: Callable[[str], bool]) -> Optional[float]:
    """100 x (the sheet's FLOPs for the traced runs of a program) / (what
    the chip's bf16 peak does in the device time the trace shows for them):
    the whole step's share of the chip's peak, whatever bounds it. It stands
    beside the step's roofline share and reads on when a kernel inside the
    step is replaced; a step whose sheet counts no FLOPs reads nothing."""
    work = traced_work(run, kernel, match)
    if work is None or work[0] <= 0:
        return None
    flops, _, seconds = work
    return 100.0 * flops / (run.peaks["bf16_flops_per_s"] * seconds)


# -- readers shared by the .steady / .saturated twins --------------------------

def chunk_rows_mean(run: Any) -> Optional[float]:
    rows = [d["batch_size"] or 0 for d in dispatches(run, ("decode_chunk",))]
    return sum(rows) / len(rows) if rows else None


def reject_share(run: Any) -> Optional[float]:
    done = [r for r in run.flights if r.get("status") == "ok"]
    if not done:
        return None
    return 100.0 * sum(1 for r in done if r.get("pool_reject_reason")) / len(done)


def decode_chunk_p50_ms(run: Any) -> Optional[float]:
    return pct([d["duration_s"] for d in dispatches(run, ("decode_chunk",))
                if d["duration_s"] is not None], 50, 1e3)


def stall_max_ms(run: Any) -> Optional[float]:
    """The longest stretch of the window with a request in flight and no
    token frame on any stream: one stall of a second or two is what a run
    that reads far off looks like (PERF.md, Findings)."""
    value = M.longest_silence_s(run.records, run.w0, run.w1)
    return None if value is None else 1e3 * value


def idle_share(run: Any) -> Optional[float]:
    return None if run.trace is None else 100.0 * run.trace["idle_share"]


def hbm_peak_gb(run: Any) -> Optional[float]:
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


POOL_WAIT = "gofr.pool.fetch_wait"  # the span in which the pool's worker fetches a chunk's tokens


def pooled_program(run: Any) -> Optional[str]:
    """The trace's name for the pool's jitted lambda, which the program
    names by nothing: the program whose runs end the pool's own waits
    (``trace_reduce.released_by``: the span ``gofr.pool.fetch_wait`` returns
    as its chunk's run ends on the device). Not the ``jit__lambda`` with
    most device time: past the knee the solo fallback's lambda has more of
    the trace than the pool's (PR 31), and its runs end
    ``gofr.solo.fetch_wait``. (Run counts against ``decode_chunk`` records
    cannot tell them apart: the reduced trace starts at its first device
    event, which the wall clock does not place.) Where fewer than three waits
    were placed, or under nine in ten of them on one program, nothing is
    decided and None comes back rather than a guess."""
    if run.trace is None:
        return None
    ended = run.trace.get("released", {}).get(POOL_WAIT, {})
    placed = sum(ended.values())
    best = max(ended, key=ended.get, default=None)
    return best if placed >= 3 and ended[best] >= 0.9 * placed else None


def of_pooled(run: Any, share: Callable, kernel: str) -> Optional[float]:
    """``share`` (``roofline_share`` or ``mfu_share``) of the sheet
    ``kernels/<kernel>.py`` over the device time of the pool's own program,
    found by ``pooled_program``."""
    pooled = pooled_program(run)
    return None if pooled is None else share(run, kernel, lambda name: name == pooled)


def decode_step_roofline(run: Any) -> Optional[float]:
    return of_pooled(run, roofline_share, "decode_step")


def decode_step_mfu(run: Any) -> Optional[float]:
    return of_pooled(run, mfu_share, "decode_step")
