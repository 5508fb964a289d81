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


def roofline_share(run: Any, kernel: str, match: Callable[[str], bool]) -> Optional[float]:
    """100 x (least time the chip could take for the traced runs of a
    program) / (device time the trace shows for them). The work comes from
    ``kernels/<kernel>.py::work(run, runs) -> (flops, bytes)``."""
    seconds, runs = program_seconds(run, match)
    if not runs or seconds <= 0 or run.peaks is None:
        return None
    flops, nbytes = spec.load_module("kernels", kernel).work(run, runs)
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


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


def decode_step_roofline(run: Any) -> Optional[float]:
    """The pool's jitted lambda is named by nothing, so it is found as the
    ``jit__lambda(<id>)`` module with the most device time."""
    if run.trace is None:
        return None
    lambdas = {n: v["seconds"] for n, v in run.trace["programs"].items()
               if n.startswith("jit__lambda(")}
    if not lambdas:
        return None
    biggest = max(lambdas, key=lambdas.get)
    return roofline_share(run, "decode_step", lambda name: name == biggest)
