"""Readers of the phase marks inside a dispatch and a request (PR 25):
``DispatchRecord`` ``issue_s`` / ``in_flight_s`` / ``fetch_wait_s`` /
``deliver_s`` / ``cadence_s`` / ``chunks_ahead`` and the kind
``decode_solo``; ``FlightRecord`` ``parse_s`` / ``first_frame_s`` /
``pool_admit_s`` / ``server_ttft_s``. Shared by the files under
``layer_metrics/`` as ``readers.py`` is by the older ones. A program that
does not stamp a field (any commit before PR 25) leaves it out of its
records: every reader here then finds nothing and returns None, and the
harness leaves the metric out of the line."""

from __future__ import annotations

from typing import Any, Optional

from benchmark.readers import dispatches, pct

PREFILL_KINDS = ("prefill", "prefill_chunk")


def _field(records: list[dict], name: str) -> list[float]:
    return [r[name] for r in records if r.get(name) is not None]


def _mean(values: list[float], scale: float = 1.0) -> Optional[float]:
    return scale * sum(values) / len(values) if values else None


def decode_chunk_cadence_p50_ms(run: Any) -> Optional[float]:
    """Median interval between the pool's deliveries: the program's own
    estimate of a pooled chunk's device time while the pipeline is full."""
    return pct(_field(dispatches(run, ("decode_chunk",)), "cadence_s"), 50, 1e3)


def prefill_chunks_ahead_mean(run: Any) -> Optional[float]:
    """Pool chunks issued and not yet fetched when a prefill was issued:
    what the prefill program queued behind on the device."""
    return _mean(_field(dispatches(run, PREFILL_KINDS), "chunks_ahead"))


def prefill_issue_p50_ms(run: Any) -> Optional[float]:
    """Median host time to prepare and enqueue a prefill program."""
    return pct(_field(dispatches(run, PREFILL_KINDS), "issue_s"), 50, 1e3)


def solo_chunk_p50_ms(run: Any) -> Optional[float]:
    """Median issue + fetch wait of a solo decode chunk (a request the
    pool refused decodes beside it, two chunks in flight)."""
    return pct([d["issue_s"] + d["fetch_wait_s"] for d in dispatches(run, ("decode_solo",))
                if d.get("issue_s") is not None and d.get("fetch_wait_s") is not None], 50, 1e3)


def pool_host_share(run: Any) -> Optional[float]:
    """100 x the pool worker's time issuing and delivering chunks over the
    window: its time neither blocked on the device nor parked."""
    chunks = [d for d in dispatches(run, ("decode_chunk",))
              if d.get("issue_s") is not None and d.get("deliver_s") is not None]
    if not chunks or run.seconds <= 0:
        return None
    return 100.0 * sum(d["issue_s"] + d["deliver_s"] for d in chunks) / run.seconds


def _finished(run: Any) -> list[dict]:
    return [r for r in run.flights if r.get("status") == "ok"]


def flight_p50_ms(run: Any, name: str) -> Optional[float]:
    return pct(_field(_finished(run), name), 50, 1e3)


def server_ttft_mean_ms(run: Any) -> Optional[float]:
    """Request accepted -> first token's frame handed to the socket, mean
    over the window's finished streams: the server's side of
    ``ttft_mean_ms``."""
    return _mean(_field(_finished(run), "server_ttft_s"), 1e3)
