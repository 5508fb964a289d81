"""Engine introspection: dispatch timeline, engine state machine, and the
device stall watchdog — the device-level mirror of the request flight
recorder (telemetry.py), one layer down.

The flight recorder answers "what happened to THIS request"; nothing
answered "what is the DEVICE doing". A device runtime that stops
answering shows as a silent `jax.devices()`/dispatch hang unless an
in-process component can detect it, time-bound it, and explain it.
This module gives the serving engine that layer:

- ``DispatchTimeline``: every device dispatch (batched prefill, chunked
  prefill slice, pooled decode chunk, warmup compile, device probe) gets
  a monotonic ``dispatch_id`` and a ``DispatchRecord`` — kind, bucket,
  batch size, padded tokens, queued/running/done marks and the split of
  running -> done into issue / in flight / fetch wait / deliver — in a
  bounded ring exposed at ``GET /admin/dispatches``.
  FlightRecords carry the dispatch ids they rode
  (``FlightRecord.note_dispatch_id``), so a slow request in
  ``/admin/requests`` links directly to the dispatches that made it slow.
- ``EngineState``: an explicit state machine
  (booting → warming → serving → degraded → wedged → recovering, plus
  failed/closed) surfaced on ``GET /admin/engine`` and
  ``/.well-known/ready`` (which returns 503 with the state while
  degraded/wedged/recovering) and mirrored into the
  ``gofr_tpu_engine_state{state}`` gauge. ``wedged`` is no longer
  terminal: the recovery supervisor (tpu/recovery.py) quarantines the
  stuck dispatch and rebuilds the stack back to ``serving``.
- ``StallWatchdog``: a heartbeat thread that wraps every dispatch with a
  deadline (``WATCHDOG_DISPATCH_TIMEOUT_S``; armed automatically on TPU
  platforms). A dispatch exceeding it increments
  ``gofr_tpu_device_stalls_total{kind}``, dumps the stuck thread's stack
  to the log, and flips the engine to ``degraded`` (then ``wedged`` once
  the stall outlives ``timeout x wedge_factor``); the dispatch finally
  completing flips it back. A stalled device runtime becomes a
  diagnosed, observable condition instead of a silent hang.

Everything here is exercisable compile-free under ``MODEL_NAME=echo``
(the echo runner exposes an injectable ``stall_hook``), so the whole
layer is covered by the fast tier (tests/test_engine_obs.py).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Iterator, Optional

from gofr_tpu.profiling import between

DISPATCH_KINDS = (
    "prefill",          # one batched prefill dispatch (DynamicBatcher)
    "prefill_chunk",    # one bounded-compute chunked-prefill slice
    "decode_chunk",     # one pooled decode chunk (DecodePool)
    "decode_solo",      # one chunk of a solo decode beside the pool
    "warmup_compile",   # one boot-time warmup compile stage
    "device_probe",     # the first jax.devices() touch of the runtime
)

ENGINE_STATES = (
    "booting",     # constructed; runtime not probed yet
    "warming",     # probe done / warmup compiles running
    "serving",     # ready; dispatches completing inside their deadline
    "degraded",    # >=1 dispatch past WATCHDOG_DISPATCH_TIMEOUT_S
    "wedged",      # a stalled dispatch outlived timeout x wedge_factor
    "recovering",  # recovery supervisor quarantining/rebuilding the stack
    "failed",      # boot/recovery failed terminally (reinit may still fix)
    "closed",      # device closed
)

# the contextvar lets device code deep below a dispatcher (e.g. the
# device's run_batch under the batcher's dispatch thread) decorate the
# CURRENT dispatch record with values only it knows (the tokens it ran)
_current_dispatch: contextvars.ContextVar[Optional["DispatchRecord"]] = (
    contextvars.ContextVar("gofr_dispatch_record", default=None)
)


def current_dispatch() -> Optional["DispatchRecord"]:
    """The dispatch record of the dispatch executing on this thread."""
    return _current_dispatch.get()


def activate_dispatch(record: Optional["DispatchRecord"]) -> Any:
    """Bind ``record`` as the thread's current dispatch (None clears —
    dispatch pool threads are reused, a leak would mislabel later work)."""
    return _current_dispatch.set(record)


class DispatchRecord:
    """One device dispatch's flight data. Single-writer (the dispatching
    thread); readers see monotonic set-once fields."""

    __slots__ = (
        "dispatch_id", "kind", "bucket", "batch_size", "padded_tokens",
        "tokens", "detail", "status", "wall_start", "t_queued", "t_running",
        "t_done", "t_issued", "t_fetch", "t_fetched", "cadence_s",
        "chunks_ahead", "state_bytes", "kv_blocks_read", "kv_blocks_held",
        "carried", "expert_tokens", "experts_read", "expert_tokens_max",
        "identity_tokens", "absent_tokens", "latent_bytes", "shared_tokens",
        "held_s", "held_late_s", "ahead_of_held",
    )

    def __init__(
        self,
        dispatch_id: int,
        kind: str,
        bucket: int = 0,
        batch_size: int = 0,
        padded_tokens: int = 0,
        tokens: int = 0,
        detail: str = "",
        queued_at: Optional[float] = None,
    ):
        self.dispatch_id = dispatch_id
        self.kind = kind
        self.bucket = bucket
        self.batch_size = batch_size
        self.padded_tokens = padded_tokens
        self.tokens = tokens
        self.detail = detail
        self.status = "running"
        # gofrlint: wall-clock — /admin/dispatches display ts (durations use t_*)
        self.wall_start = time.time()
        now = time.perf_counter()
        self.t_queued = queued_at if queued_at is not None else now
        # no external queue mark -> execution starts now (dispatchers
        # with a real queue phase pass queued_at and mark_running later)
        self.t_running: Optional[float] = None if queued_at is not None else now
        self.t_done: Optional[float] = None
        # where the running -> done time went (set-once marks stamped by
        # profiling.phase at the lines that do the work): the jitted
        # call returned, the host began to fetch the result, the fetch
        # returned. With t_running and t_done they split duration into
        # issue / in_flight / fetch_wait / deliver.
        self.t_issued: Optional[float] = None
        self.t_fetch: Optional[float] = None
        self.t_fetched: Optional[float] = None
        # decode chunks: the pool's inter-delivery interval, its own
        # estimate of one chunk's device time with the pipeline full
        # (it holds other programs' time between two deliveries too)
        self.cadence_s: Optional[float] = None
        # pool dispatches issued and not yet fetched when this one was
        # issued: what a prefill queued behind on the device, or the
        # pipeline depth a decode chunk actually reached
        self.chunks_ahead: Optional[int] = None
        # a decode chunk of a model whose cache is a state per row: the
        # bytes of state its live rows had to read and write (rows x
        # layers x 2 x one row's bytes x steps); None for a K/V cache
        self.state_bytes: Optional[int] = None
        # a decode chunk over a K/V cache: the blocks of 128 positions its
        # attention had to read (steps x sum over live rows of the blocks
        # up to the row's length) and the blocks the pool's cache holds
        # (steps x slots x max_seq / 128); None for a state
        self.kv_blocks_read: Optional[int] = None
        self.kv_blocks_held: Optional[int] = None
        # a prefill chunk: whether it began from what an earlier chunk of
        # the same prompt left in the cache
        self.carried: Optional[bool] = None
        # an expert model's dispatch (decode_chunk, prefill, prefill_chunk),
        # summed over its steps and layers: tokens routed to an expert,
        # experts that got at least one (what a step had to read), and the
        # fullest expert's tokens (the straggler); None for a dense model
        self.expert_tokens: Optional[int] = None
        self.experts_read: Optional[int] = None
        self.expert_tokens_max: Optional[int] = None
        # under a top-k router over a deployment's experts, of which this
        # chip holds some, the three above count (token, expert) PAIRS that
        # landed on an expert held here; these two count the pairs that
        # chose an identity expert and those that chose another chip's.
        # The three pair counts add up to top-k x real tokens x layers
        self.identity_tokens: Optional[int] = None
        self.absent_tokens: Optional[int] = None
        # a model with a latent cache: the bytes of latent (and shared
        # rotated key) the dispatch's attention had to read, by its rows'
        # lengths over all its places; None for any other cache
        self.latent_bytes: Optional[int] = None
        # a model with shared experts, which every token takes beside its
        # routed ones: real tokens x expert layers (x steps); None without
        self.shared_tokens: Optional[int] = None
        # a decode chunk the pool held back until the device was about to
        # need it (tpu/decode_pool.py::_hold): how long, and how far after
        # its due time it was on the device's queue (None: the scheduler
        # ended the hold for a prefill that went behind the chunk); any
        # other dispatch: it was issued during such a hold, so it runs
        # ahead of the held chunk
        self.held_s: Optional[float] = None
        self.held_late_s: Optional[float] = None
        self.ahead_of_held: Optional[bool] = None

    def note_routing(self, counts: Any, held: int = 0, top_k: int = 1, shared: int = 0) -> None:
        """``counts`` [..., layers, experts]: the tokens each expert of each
        layer got in each step of this dispatch (a numpy array); where it
        is two columns wider than the ``held`` experts, those are the
        identity pairs and the absent ones. ``shared``: the model's shared
        experts (0: none): every real token of an expert layer drew ``top_k``
        pairs and took the shared expert once."""
        if shared:
            self.shared_tokens = int(counts.sum()) // top_k
        if held and counts.shape[-1] == held + 2:
            self.identity_tokens = int(counts[..., held].sum())
            self.absent_tokens = int(counts[..., held + 1].sum())
            counts = counts[..., :held]
        self.expert_tokens = int(counts.sum())
        self.experts_read = int((counts > 0).sum())
        self.expert_tokens_max = int(counts.max(axis=-1).sum())

    def mark_running(self) -> None:
        """Device execution begins (after any scheduler-interleave wait)."""
        if self.t_running is None:
            self.t_running = time.perf_counter()

    @property
    def queue_wait(self) -> Optional[float]:
        if self.t_running is None:
            return None
        return self.t_running - self.t_queued

    @property
    def duration(self) -> Optional[float]:
        if self.t_done is None:
            return None
        return self.t_done - (self.t_running or self.t_queued)

    @property
    def phase(self) -> str:
        """Which part of its life the dispatch is in, by its marks."""
        if self.t_done is not None:
            return "done"
        if self.t_running is None:
            return "queued"
        if self.t_issued is None:
            return "issue"
        if self.t_fetch is None:
            return "in_flight"
        return "fetch_wait" if self.t_fetched is None else "deliver"

    def to_dict(self) -> dict[str, Any]:
        return {
            "dispatch_id": self.dispatch_id,
            "kind": self.kind,
            "status": self.status,
            "bucket": self.bucket or None,
            "batch_size": self.batch_size or None,
            "padded_tokens": self.padded_tokens,
            "tokens": self.tokens,
            "detail": self.detail or None,
            "start_ts": self.wall_start,
            "queue_wait_s": self.queue_wait,
            "duration_s": self.duration,
            "issue_s": between(self.t_running, self.t_issued),
            "in_flight_s": between(self.t_issued, self.t_fetch),
            "fetch_wait_s": between(self.t_fetch, self.t_fetched),
            "deliver_s": between(self.t_fetched, self.t_done),
            "cadence_s": self.cadence_s,
            "chunks_ahead": self.chunks_ahead,
            "state_bytes": self.state_bytes,
            "kv_blocks_read": self.kv_blocks_read,
            "kv_blocks_held": self.kv_blocks_held,
            "carried": self.carried,
            "expert_tokens": self.expert_tokens,
            "experts_read": self.experts_read,
            "expert_tokens_max": self.expert_tokens_max,
            "identity_tokens": self.identity_tokens,
            "absent_tokens": self.absent_tokens,
            "latent_bytes": self.latent_bytes,
            "shared_tokens": self.shared_tokens,
            "held_s": self.held_s,
            "held_late_s": self.held_late_s,
            "ahead_of_held": self.ahead_of_held,
        }


class DispatchTimeline:
    """Bounded, thread-safe ring of DispatchRecords with monotonic ids.

    Records land in the ring at ``begin`` (status "running"), so an
    in-flight — including a WEDGED — dispatch is visible on
    ``/admin/dispatches`` while it hangs; ``finish`` stamps the terminal
    mark in place and is idempotent (error paths and success paths may
    both reach it)."""

    def __init__(self, capacity: int = 512, metrics: Any = None):
        self._ids = itertools.count(1)
        self._ring: "deque[DispatchRecord]" = deque(maxlen=max(1, capacity))
        self._lock = threading.Lock()
        self._by_kind: dict[str, int] = {}
        self._in_flight: dict[int, DispatchRecord] = {}
        if metrics is not None:
            self._count = metrics.counter(
                "gofr_tpu_dispatches_total",
                "device dispatches by kind (prefill, prefill_chunk, "
                "decode_chunk, warmup_compile, device_probe)",
                labels=("kind",),
            )
            self._dur = metrics.histogram(
                "gofr_tpu_dispatch_seconds",
                "device dispatch duration (running -> done)",
                labels=("kind",),
            )
        else:
            self._count = self._dur = None

    def begin(
        self,
        kind: str,
        bucket: int = 0,
        batch_size: int = 0,
        padded_tokens: int = 0,
        tokens: int = 0,
        detail: str = "",
        queued_at: Optional[float] = None,
    ) -> DispatchRecord:
        record = DispatchRecord(
            next(self._ids), kind, bucket=bucket, batch_size=batch_size,
            padded_tokens=padded_tokens, tokens=tokens, detail=detail,
            queued_at=queued_at,
        )
        with self._lock:
            self._ring.append(record)
            self._by_kind[kind] = self._by_kind.get(kind, 0) + 1
            self._in_flight[record.dispatch_id] = record
        if self._count is not None:
            self._count.inc(kind=kind)
        return record

    def finish(self, record: DispatchRecord, status: str = "ok") -> None:
        if record.t_done is not None:
            return  # idempotent: first finish wins
        record.mark_running()  # a dispatch that never ran still closes
        record.t_done = time.perf_counter()
        record.status = status
        with self._lock:
            self._in_flight.pop(record.dispatch_id, None)
        if self._dur is not None:
            self._dur.observe(record.duration or 0.0, kind=record.kind)

    # -- read side (admin API) ------------------------------------------------
    def records(
        self, limit: int = 100, kind: Optional[str] = None
    ) -> list[dict[str, Any]]:
        """Most-recent-first record dicts, optionally filtered by kind."""
        with self._lock:
            snapshot = list(self._ring)
        out: list[dict[str, Any]] = []
        for record in reversed(snapshot):
            if kind is not None and record.kind != kind:
                continue
            out.append(record.to_dict())
            if len(out) >= limit:
                break
        return out

    def running(self) -> list[dict[str, Any]]:
        """The dispatches in flight right now, oldest first, each with the
        phase it is in: what the device's threads were doing when
        something else (the HTTP loop's late tick) asks."""
        with self._lock:
            records = list(self._in_flight.values())
        return [
            {"dispatch_id": r.dispatch_id, "kind": r.kind, "phase": r.phase}
            for r in records
        ]

    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {
                "total": sum(self._by_kind.values()),
                "by_kind": dict(self._by_kind),
                "in_flight": len(self._in_flight),
            }


class EngineState:
    """Explicit engine state machine, mirrored into the
    ``gofr_tpu_engine_state{state}`` gauge (1 for the current state) and
    a bounded transition history for ``/admin/engine``."""

    def __init__(self, metrics: Any = None, logger: Any = None):
        self._lock = threading.Lock()
        self.state = "booting"
        self._detail = ""
        # gofrlint: wall-clock — /admin/engine "since"/history ts (display)
        self._since = time.time()
        self._history: "deque[dict[str, Any]]" = deque(maxlen=64)
        self._logger = logger
        self._listeners: list[Any] = []
        self._gauge = (
            metrics.gauge(
                "gofr_tpu_engine_state",
                "engine state machine (1 for the current state): booting, "
                "warming, serving, degraded, wedged, recovering, failed, "
                "closed",
                labels=("state",),
            )
            if metrics is not None else None
        )
        self._history.append(
            {"state": "booting", "ts": self._since, "detail": ""}
        )
        self._set_gauge("booting")

    def _set_gauge(self, state: str) -> None:
        if self._gauge is None:
            return
        for s in ENGINE_STATES:
            self._gauge.set(1.0 if s == state else 0.0, state=s)

    def add_listener(self, fn: Any) -> None:
        """Register ``fn(state, detail)``, called AFTER every completed
        transition, outside the engine lock. Listeners must be quick and
        non-blocking — the postmortem trigger, for example, hands the
        actual bundle write to its own thread. A raising listener is
        swallowed (observers must never wedge the state machine)."""
        with self._lock:
            self._listeners.append(fn)

    def transition(self, state: str, detail: str = "") -> None:
        if state not in ENGINE_STATES:
            raise ValueError(
                f"engine state '{state}' unknown — one of {ENGINE_STATES}"
            )
        with self._lock:
            if state == self.state:
                self._detail = detail or self._detail
                return
            self.state = state
            self._detail = detail
            # gofrlint: wall-clock — /admin/engine "since"/history ts (display)
            self._since = time.time()
            self._history.append(
                {"state": state, "ts": self._since, "detail": detail}
            )
            # inside the lock: two racing transitions must not interleave
            # their per-state gauge writes (the metric lock is a leaf)
            self._set_gauge(state)
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(state, detail)
            except Exception as exc:
                # observers must never wedge the state machine — but a
                # broken one must be visible, not silently dropped
                if self._logger is not None:
                    self._logger.warnf(
                        "engine state listener failed on -> %s: %r",
                        state, exc,
                    )
        if self._logger is not None:
            log = (
                self._logger.warnf if state in ("degraded", "wedged", "failed")
                else self._logger.infof
            )
            log("engine state -> %s%s", state, f" ({detail})" if detail else "")

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "state": self.state,
                "detail": self._detail or None,
                "since": self._since,
                "history": list(self._history),
            }


class _Watch:
    __slots__ = ("kind", "dispatch_id", "thread_ident", "thread_name",
                 "started", "flagged", "wedged")

    def __init__(self, kind: str, dispatch_id: int):
        self.kind = kind
        self.dispatch_id = dispatch_id
        thread = threading.current_thread()
        self.thread_ident = thread.ident
        self.thread_name = thread.name
        self.started = time.perf_counter()
        self.flagged = False
        self.wedged = False


class StallWatchdog:
    """Deadline heartbeat over in-flight dispatches.

    Dispatchers wrap device work in ``watch(kind, dispatch_id)``; a
    daemon thread scans the registered entries every ``poll`` interval.
    Past ``timeout_s`` a dispatch is a STALL: the stall counter
    increments, the stuck thread's stack is dumped to the log (the data
    that says where the runtime stopped answering), and the engine flips to
    ``degraded`` — then ``wedged`` once the stall outlives
    ``timeout_s x wedge_factor``. The dispatch completing (however late)
    flips the engine back to the state it held before the stall.

    ``timeout_s <= 0`` disables: ``watch`` degrades to a no-op context
    manager and no thread runs. ``arm`` enables later (the device arms
    automatically after probing a TPU platform when the operator set no
    explicit ``WATCHDOG_DISPATCH_TIMEOUT_S``)."""

    def __init__(
        self,
        engine: EngineState,
        metrics: Any = None,
        logger: Any = None,
        timeout_s: float = 0.0,
        wedge_factor: float = 3.0,
    ):
        if wedge_factor < 1.0:
            raise ValueError("wedge_factor must be >= 1.0")
        self.engine = engine
        self.logger = logger
        self.timeout_s = float(timeout_s)
        self.wedge_factor = wedge_factor
        self._entries: dict[int, _Watch] = {}
        # the last recovery incident's quarantined (forgotten) stalled
        # entries — evidence that outlives the quarantine
        self._quarantined: list[dict[str, Any]] = []
        self._tokens = itertools.count(1)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pre_stall_state = "serving"
        # plain counts next to the Prometheus counter so snapshots and
        # tests read stall history without scraping the registry
        self.stall_counts: dict[str, int] = {}
        self._stalls = (
            metrics.counter(
                "gofr_tpu_device_stalls_total",
                "dispatches that exceeded WATCHDOG_DISPATCH_TIMEOUT_S "
                "(the engine degrades/wedges while one is in flight)",
                labels=("kind",),
            )
            if metrics is not None else None
        )
        if self.timeout_s > 0:
            self._start()

    # -- lifecycle ------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self.timeout_s > 0 and not self._stop.is_set()

    def _poll_interval(self) -> float:
        return max(0.01, min(self.timeout_s / 4.0, 1.0))

    def _start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="gofr-watchdog", daemon=True
        )
        self._thread.start()

    def arm(self, timeout_s: float) -> None:
        """Enable (or retune) the deadline; idempotent."""
        if timeout_s <= 0:
            return
        self.timeout_s = float(timeout_s)
        self._start()

    def close(self) -> None:
        self._stop.set()

    # -- dispatch side --------------------------------------------------------
    @contextlib.contextmanager
    def watch(self, kind: str, dispatch_id: int = 0) -> Iterator[None]:
        """Register the calling thread's dispatch for deadline scanning
        for the duration of the with-block."""
        if not self.enabled:
            yield
            return
        entry = _Watch(kind, dispatch_id)
        token = next(self._tokens)
        with self._lock:
            self._entries[token] = entry
        try:
            yield
        finally:
            self._unwatch(token, entry)

    def _unwatch(self, token: int, entry: _Watch) -> None:
        # pop, flag-check, AND the recovery transition all under the
        # watchdog lock: the scanner serializes on the same lock before
        # flagging, so a completing dispatch either wins the pop (never
        # flagged) or observes its flag here and recovers the engine —
        # no interleaving can strand the engine in degraded. Lock order
        # is watchdog -> engine; the engine lock is a leaf.
        elapsed = time.perf_counter() - entry.started
        recovered = False
        with self._lock:
            self._entries.pop(token, None)
            if entry.flagged:
                recovered = True
                still_stalled = any(
                    e.flagged for e in self._entries.values()
                )
                if not still_stalled and self.engine.state in (
                    "degraded", "wedged"
                ):
                    self.engine.transition(
                        self._pre_stall_state,
                        f"{entry.kind} dispatch {entry.dispatch_id} "
                        f"recovered after {elapsed:.1f}s",
                    )
        if recovered and self.logger is not None:
            self.logger.warnf(
                "watchdog: %s dispatch %d recovered after %.1fs",
                entry.kind, entry.dispatch_id, elapsed,
            )

    def quarantine(self) -> list[dict[str, Any]]:
        """Recovery-supervisor entry: forget every currently-flagged
        (stalled/wedged) watch entry and return their descriptions.

        The stuck thread is unreachable — it may never return from its
        device call — but its watch entry must not keep poisoning the
        engine state machine after the stack around it is rebuilt: a
        LATER dispatch completing its own recovery checks
        ``any(e.flagged ...)`` over the live entries, and a permanently
        wedged ghost would hold the engine degraded forever. The ghost
        thread's eventual ``_unwatch`` pops a token that is already
        gone (harmless) and only transitions the engine when it still
        reads degraded/wedged — never after recovery reached serving."""
        quarantined: list[dict[str, Any]] = []
        with self._lock:
            for token, entry in list(self._entries.items()):
                if entry.flagged:
                    quarantined.append({
                        "kind": entry.kind,
                        "dispatch_id": entry.dispatch_id,
                        "thread": entry.thread_name,
                        "elapsed_s": round(
                            time.perf_counter() - entry.started, 3
                        ),
                    })
                    self._entries.pop(token, None)
            # evidence survives the quarantine: snapshot() keeps serving
            # the LAST incident's stuck dispatches on /admin/engine and
            # in postmortem bundles written after the rebuild
            if quarantined:
                self._quarantined = quarantined
        return quarantined

    # -- heartbeat ------------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.wait(self._poll_interval()):
            self._scan()

    def _scan(self) -> None:
        now = time.perf_counter()
        with self._lock:
            entries = list(self._entries.items())
            timeout = self.timeout_s
        for token, entry in entries:
            elapsed = now - entry.started
            if not entry.flagged and elapsed > timeout:
                if self._flag_stall(token, entry, elapsed):
                    self._log_stall(entry, elapsed)
            elif (
                entry.flagged and not entry.wedged
                and elapsed > timeout * self.wedge_factor
            ):
                self._flag_wedge(token, entry, elapsed, timeout)

    def _flag_stall(self, token: int, entry: _Watch, elapsed: float) -> bool:
        """Flag one overdue entry. The membership re-check and the
        engine transition happen under the watchdog lock: a dispatch
        that completed since the scan snapshot was popped by _unwatch
        (membership fails, nothing flagged) — flagging a finished
        dispatch would degrade the engine with nothing left to recover
        it. Returns True when the stall was recorded."""
        with self._lock:
            if self._entries.get(token) is not entry:
                return False  # completed between snapshot and flag
            entry.flagged = True
            self.stall_counts[entry.kind] = (
                self.stall_counts.get(entry.kind, 0) + 1
            )
            if self.engine.state not in ("degraded", "wedged"):
                self._pre_stall_state = self.engine.state
            self.engine.transition(
                "degraded",
                f"{entry.kind} dispatch {entry.dispatch_id} stalled "
                f"{elapsed:.1f}s (deadline {self.timeout_s:.1f}s)",
            )
        if self._stalls is not None:
            self._stalls.inc(kind=entry.kind)
        return True

    def _flag_wedge(
        self, token: int, entry: _Watch, elapsed: float, timeout: float
    ) -> None:
        with self._lock:
            if self._entries.get(token) is not entry:
                return  # completed: _unwatch already recovered the engine
            entry.wedged = True
            self.engine.transition(
                "wedged",
                f"{entry.kind} dispatch {entry.dispatch_id} stalled "
                f"{elapsed:.1f}s (> {self.wedge_factor:.0f}x the "
                f"{timeout:.1f}s deadline)",
            )

    def _log_stall(self, entry: _Watch, elapsed: float) -> None:
        """The stuck thread's stack — outside the lock (formatting a
        deep stack is not watchdog-critical-path work)."""
        if self.logger is None:
            return
        self.logger.errorf(
            "watchdog: %s dispatch %d stalled %.1fs on thread %s:\n%s",
            entry.kind, entry.dispatch_id, elapsed, entry.thread_name,
            self._stack_of(entry.thread_ident),
        )

    @staticmethod
    def _stack_of(thread_ident: Optional[int]) -> str:
        """The stuck thread's current stack — what turns 'it hangs' into
        'it hangs inside THIS call'."""
        frame = sys._current_frames().get(thread_ident or -1)
        if frame is None:
            return "<thread gone>"
        return "".join(traceback.format_stack(frame))

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            watching = [
                {
                    "kind": e.kind,
                    "dispatch_id": e.dispatch_id,
                    "elapsed_s": round(time.perf_counter() - e.started, 3),
                    "stalled": e.flagged,
                }
                for e in self._entries.values()
            ]
            counts = dict(self.stall_counts)
            quarantined = list(self._quarantined)
        return {
            "enabled": self.enabled,
            "timeout_s": self.timeout_s if self.enabled else None,
            "wedge_factor": self.wedge_factor,
            "stalls": counts,
            "watching": watching,
            # the last recovery incident's quarantined dispatches
            # (empty until a recovery has run)
            "quarantined": quarantined,
        }
