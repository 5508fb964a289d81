"""Device profiling: JAX/XLA trace capture behind admin endpoints.

SURVEY.md §5: the reference has no continuous profiler (no pprof
endpoints); the TPU build adds device profiling via the runtime's profiler
hooks. ``jax.profiler.start_trace`` captures XLA device traces (HLO
timelines, memory viewer data) into a TensorBoard-compatible directory;
the admin endpoints (handler.py: POST /admin/profiler/start|stop, GET
/admin/profiler) drive it on a live serving process, so a production TTFT
regression can be traced without redeploying.

What is always on and cheap is the records: a ``DispatchRecord`` a
device dispatch and a ``FlightRecord`` a request, each with its
``perf_counter`` marks (``cadence_s`` on a pooled chunk is the in-program
estimate of the interval between two deliveries, whatever the device ran
in it; the pool's ``chunk_run_s`` on ``GET /admin/engine`` is the second,
of a chunk's own device time); utilisation is read from a
trace against the benchmark's work sheets and from nowhere in here. Full
traces are the on-demand deep dive.

``phase`` puts the program's own phase boundaries on both clocks at one
line of code: a ``jax.profiler.TraceAnnotation`` (the ``/host:CPU`` plane
of the same ``.xplane.pb`` the device's ``XLA Ops`` land in) and the
``perf_counter`` marks of the DispatchRecord / FlightRecord that the
phase belongs to. The names are LEAVES — no ``phase`` encloses another —
because a trace reducer that attributes a device gap to the host event
covering most of it would hand every gap to an enclosing span.
``instant`` is the same annotation with no extent and no record: the HTTP
server's event loop ticks one every 50 ms (``gofr.http.loop_tick``), so a
hole in them on the loop's thread is a loop that did not run, on the
device trace's clock.
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time
from typing import Any, Optional

# the phase vocabulary (PERF.md section 3 says which record field and
# which benchmark metric reads each)
BATCHER_COLLECT = "gofr.batcher.collect"
PREFILL_ISSUE = "gofr.prefill.issue"
PREFILL_FETCH_WAIT = "gofr.prefill.fetch_wait"
POOL_ISSUE = "gofr.pool.issue"
POOL_FETCH_WAIT = "gofr.pool.fetch_wait"
POOL_DELIVER = "gofr.pool.deliver"
POOL_WAIT_WORK = "gofr.pool.wait_work"
POOL_HOLD = "gofr.pool.hold"
POOL_STATE_INSERT = "gofr.pool.state_insert"
POOL_SEAT_WAIT = "gofr.pool.seat_wait"
SOLO_ISSUE = "gofr.solo.issue"
SOLO_FETCH_WAIT = "gofr.solo.fetch_wait"
SSE_FIRST_FRAME = "gofr.sse.first_frame"
HTTP_LOOP_TICK = "gofr.http.loop_tick"


def _annotation(name: str, dispatch_id: Optional[int]) -> Any:
    """The profiler's host-plane event for one phase. With no profiler
    session its enter/exit are a flag check inside jaxlib."""
    from jax.profiler import TraceAnnotation  # not at import: jax is heavy

    if dispatch_id is None:
        return TraceAnnotation(name)
    return TraceAnnotation(name, dispatch_id=dispatch_id)


class phase:
    """``with phase(NAME, record, start="t_x", end="t_y"):`` — one phase
    of a dispatch or a request. Opens the profiler annotation ``NAME``
    (tagged with the record's ``dispatch_id`` when it has one) and stamps
    the record's set-once ``perf_counter`` marks at the same two program
    points, so the record's split and the trace's spans cannot drift
    apart. ``record`` may be None (no timeline wired): annotation only."""

    __slots__ = ("_record", "_end", "_ann")

    def __init__(self, name: str, record: Any = None,
                 start: Optional[str] = None, end: Optional[str] = None):
        self._record = record
        self._end = end
        self._ann = _annotation(name, getattr(record, "dispatch_id", None))
        if start is not None:
            _stamp(record, start)

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        if self._end is not None:
            _stamp(self._record, self._end)
        return False


def instant(name: str) -> None:
    """An event of no extent on the calling thread's line of the host
    plane. A process that never imported jax (an app serving no model)
    has no profiler to write to, and is not made to import one."""
    if "jax" in sys.modules:
        with _annotation(name, None):
            pass


def between(a: Optional[float], b: Optional[float]) -> Optional[float]:
    """Seconds from mark ``a`` to mark ``b``; None while either is unset."""
    return None if a is None or b is None else b - a


def _stamp(record: Any, mark: str) -> None:
    if record is not None and getattr(record, mark) is None:
        setattr(record, mark, time.perf_counter())


class Profiler:
    """Thread-safe wrapper around one active jax.profiler trace session."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._dir: Optional[str] = None
        self._started_at: Optional[float] = None

    def start(self, log_dir: Optional[str] = None) -> dict[str, Any]:
        import jax

        from gofr_tpu.config import get_env

        with self._lock:
            if self._dir is not None:
                raise RuntimeError(f"profiler already tracing into {self._dir}")
            log_dir = log_dir or get_env("PROFILE_DIR") or tempfile.mkdtemp(
                prefix="gofr-profile-"
            )
            os.makedirs(log_dir, exist_ok=True)
            jax.profiler.start_trace(log_dir)
            self._dir = log_dir
            self._started_at = time.monotonic()
            return {"state": "tracing", "dir": log_dir}

    def stop(self) -> dict[str, Any]:
        import jax

        with self._lock:
            if self._dir is None:
                raise RuntimeError("profiler is not tracing")
            # clear state BEFORE stop_trace: if collection fails the
            # profiler must not wedge in "tracing" forever (the endpoint
            # exists to debug live processes; restarting defeats it)
            log_dir, self._dir = self._dir, None
            elapsed = time.monotonic() - (self._started_at or time.monotonic())
            self._started_at = None
            jax.profiler.stop_trace()
        files = []
        for root, _, names in os.walk(log_dir):
            files.extend(os.path.relpath(os.path.join(root, n), log_dir) for n in names)
        return {
            "state": "stopped", "dir": log_dir,
            "seconds": round(elapsed, 2), "artifacts": sorted(files),
        }

    def status(self) -> dict[str, Any]:
        with self._lock:
            if self._dir is None:
                return {"state": "idle"}
            return {
                "state": "tracing", "dir": self._dir,
                "seconds": round(time.monotonic() - (self._started_at or 0), 2),
            }


_PROFILER = Profiler()


def profiler() -> Profiler:
    """Process-wide profiler (the device runtime is process-wide too)."""
    return _PROFILER
