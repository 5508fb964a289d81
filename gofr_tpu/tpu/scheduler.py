"""Prefill/decode interference scheduler: the policy between the two
dispatchers that share one device.

Without it the prefill ``DynamicBatcher`` and the continuous-batching
``DecodePool`` dispatch independently: a long-prompt prefill batch
occupies the device for its full duration and every pooled decode chunk
behind it waits, so one 4k-token prompt spikes TPOT for all co-tenants.
The fix is two-sided:

- **bounded prefill compute**: prefills larger than ``PREFILL_CHUNK_TOKENS``
  are split into bucket-sized chunks (device.py ``_chunked_prefill``), so
  no single prefill dispatch occupies the device much longer than one
  decode chunk;
- **an interleaver** (this module): both dispatchers consult ONE
  ``InterferenceScheduler``. Decode is never throttled: the pool *notes*
  each chunk dispatch, and says so while it HOLDS the chunk behind the
  one that is running (``note_hold``: the pool issues that chunk when the
  device is about to need it, tpu/decode_pool.py). Prefill chunks call
  ``admit_prefill``, which under load defers until decode has taken its
  turn, so the device stream alternates decode-chunk / prefill-chunk
  instead of running a prefill train;
- **who goes next during a hold** (this module, the guard): a prefill
  admitted while the pool holds a chunk back is issued at once, AHEAD of
  the held chunk, if it is expected to run shorter than a chunk does;
  one expected to run longer, or of a program not yet timed, makes the
  pool issue its held chunk first and goes behind it, which is the order
  without a hold. Shortest first, from two observed times: the pool's
  ``run_s`` and this module's median of what each prefill program added
  to a delivery interval (``note_interval``).

Why dispatch-order interleaving is enough: a single device executes its
stream roughly in dispatch order (JAX async dispatch keeps the host
ahead, not the device reordered), so admitting at most one
bounded-compute prefill chunk per decode-chunk interval bounds the gap
between two decode chunks at ~one prefill chunk's compute — the decode
cadence a pooled stream observes degrades by at most that bound, never
by a whole prompt's prefill.

Policies (``SCHED_POLICY``):

- ``fair`` (default): at most one prefill chunk per decode-chunk
  interval while decode is busy — prefills make steady progress, pooled
  streams keep their cadence.
- ``decode-first``: one prefill chunk per TWO decode-chunk intervals —
  stronger TPOT protection for decode-heavy deployments, prefill
  (TTFT) pays.
- ``prefill-first``: never defer (the pre-scheduler behavior; TTFT
  wins, co-tenant TPOT pays).

Every wait is bounded by ``SCHED_MAX_DEFER_MS`` per chunk and by a
decode-idleness horizon, so a stalled or finished pool can never starve
prefill: the scheduler degrades to a no-op when decode goes quiet.

Telemetry: ``gofr_tpu_prefill_chunks_total`` counts admitted
bounded-compute prefill dispatches, ``gofr_tpu_sched_defer_seconds``
observes how long each chunk waited for its turn. Callers stamp the
per-request FlightRecord themselves (they hold it; this module stays
request-agnostic).
"""

from __future__ import annotations

import statistics
import threading
import time
from collections import deque
from typing import Any, Callable, Hashable, Optional

POLICIES = ("decode-first", "prefill-first", "fair")
# readings kept of a program's run (a prefill program here, the pooled chunk
# in tpu/decode_pool.py): the median of so few shrugs off the one that was
# booked to the wrong interval (a prefill admitted before a chunk's issue
# and on the device after it, a program nobody admitted) and follows a
# program whose cost drifts
RUN_SAMPLES = 5


class InterferenceScheduler:
    """The small shared object both dispatchers consult.

    Decode side: ``note_decode_chunk(active)`` per pool dispatch,
    ``note_hold(run_s)`` while the pool holds its next chunk back,
    ``note_interval`` per delivery interval it could time (and
    ``note_decode_idle()`` when the pool drains) — cheap, never blocks.
    Prefill side: ``admit_prefill()`` before each bounded prefill
    dispatch — blocks (bounded) for a decode turn under load and
    returns the seconds deferred.
    """

    def __init__(
        self,
        policy: str = "fair",
        metrics: Any = None,
        model: str = "",
        max_defer_ms: float = 1000.0,
        idle_after_s: float = 0.5,
    ):
        if policy not in POLICIES:
            raise ValueError(
                f"scheduler policy '{policy}' not supported — use one of "
                f"{POLICIES}"
            )
        if max_defer_ms <= 0:
            raise ValueError("max_defer_ms must be > 0")
        self.policy = policy
        self.model = model
        self._max_defer_s = max_defer_ms / 1000.0
        self._idle_after_s = idle_after_s
        self._cond = threading.Condition()
        self._decode_seq = 0  # decode chunk dispatches seen
        self._decode_active = 0  # active pool slots at the last note
        self._last_decode_t = 0.0
        self._last_admit_seq = 0  # decode seq at the last admitted prefill
        self._interval_ema = 0.0  # smoothed decode chunk cadence
        # the pool holds its next chunk back (tpu/decode_pool.py): (what a
        # chunk runs by the pool's estimate, how an admitted prefill makes
        # the pool issue that chunk now); None outside a hold
        self._hold: Optional[tuple[float, Callable[[], None]]] = None
        # the prefill programs admitted since the last decode note, in
        # order: the pool takes them with the note and hands them back
        # with the interval they ran in (``note_interval``); one makes a
        # reading, so the last few are as good as all (a pool that notes
        # nothing must not make this grow)
        self._admitted: deque = deque(maxlen=4)
        # program -> the last few readings of what ONE prefill of it
        # added to a delivery interval; the median is its expected run
        self._prefill_runs: dict = {}
        # counters kept plain too so tests (and /admin debugging) can read
        # scheduling behavior without scraping the registry
        self.stats = {
            "prefill_chunks": 0,
            "deferred_chunks": 0,
            "decode_chunks": 0,
            # the guard's two answers during a hold
            "prefills_ahead_of_held": 0,
            "prefills_kept_behind": 0,
        }
        if metrics is not None:
            self._chunks_counter = metrics.counter(
                "gofr_tpu_prefill_chunks_total",
                "bounded-compute prefill dispatches admitted by the "
                "interference scheduler",
                labels=("model",),
            )
            self._defer_hist = metrics.histogram(
                "gofr_tpu_sched_defer_seconds",
                "time a prefill chunk waited for its decode-interleave turn",
                labels=("model",),
            )
        else:
            self._chunks_counter = self._defer_hist = None

    def snapshot(self) -> dict:
        """Point-in-time defer state for ``GET /admin/engine``: policy,
        bound, decode cadence, and the plain counters."""
        with self._cond:
            return {
                "policy": self.policy,
                "max_defer_ms": self._max_defer_s * 1000.0,
                "decode_active": self._decode_active,
                "decode_interval_ema_s": round(self._interval_ema, 6),
                **dict(self.stats),
            }

    # -- decode side (never blocks) ------------------------------------------
    def note_decode_chunk(self, active: int) -> list:
        """One pooled decode chunk dispatched with ``active`` live slots
        (a held one: the hold is over). Returns the prefill programs
        admitted since the note before, which is what the device runs
        between the two chunks."""
        now = time.perf_counter()
        with self._cond:
            self._decode_seq += 1
            self._hold = None
            admitted = list(self._admitted)
            self._admitted.clear()
            self.stats["decode_chunks"] += 1
            if self._last_decode_t:
                interval = now - self._last_decode_t
                self._interval_ema = (
                    interval if not self._interval_ema
                    else 0.8 * self._interval_ema + 0.2 * interval
                )
            self._last_decode_t = now
            self._decode_active = max(int(active), 0)
            self._cond.notify_all()
        return admitted

    def note_hold(self, run_s: float, release: Callable[[], None]) -> None:
        """The pool holds its next chunk back behind the one that runs,
        which by its estimate takes ``run_s``: until the next decode note
        a prefill is placed by the guard (``admit_prefill``), which calls
        ``release`` (under no lock of this module's) for one that is to
        go behind the held chunk."""
        with self._cond:
            self._hold = (run_s, release)

    def note_interval(self, admitted: list, extra_s: float) -> None:
        """A delivery interval the pool could time (two fetches in a row,
        the device busy throughout) ran ``extra_s`` longer than a chunk
        does, and ``admitted`` is what ``note_decode_chunk`` returned for
        it. Exactly one prefill: that is a reading of its program's run."""
        if len(admitted) != 1:
            return
        with self._cond:
            self._prefill_runs.setdefault(
                admitted[0], deque(maxlen=RUN_SAMPLES)
            ).append(max(extra_s, 0.0))

    def expected_run_s(self, program: Hashable) -> Optional[float]:
        """What one prefill of ``program`` is expected to hold the device
        for: the median of its readings, None before the first."""
        with self._cond:
            runs = self._prefill_runs.get(program)
            return statistics.median(runs) if runs else None

    def note_decode_idle(self) -> None:
        """The pool drained (or died): release any waiting prefill now."""
        with self._cond:
            self._decode_active = 0
            self._hold = None
            self._cond.notify_all()

    def _decode_busy(self, now: float) -> bool:
        """Under ``_cond``: is decode actively dispatching? Active slots
        alone are not enough — a wedged pool must not starve prefill, so
        a cadence older than the idleness horizon counts as quiet."""
        if self._decode_active <= 0:
            return False
        horizon = max(self._idle_after_s, 8.0 * self._interval_ema)
        return (now - self._last_decode_t) < horizon

    # -- prefill side ---------------------------------------------------------
    def admit_prefill(self, tokens: int = 0,
                      program: Optional[Hashable] = None) -> float:
        """Gate one bounded-compute prefill dispatch; returns the seconds
        this chunk was deferred waiting for its decode-interleave turn
        (0.0 when decode is idle or the policy never defers). ``program``
        names the compiled program the dispatch runs, whatever says which
        of them take the same time (``tokens``, the chunk's bucket width,
        where the caller gives none): during a hold of the pool the guard
        places the dispatch by that program's expected run."""
        start = time.perf_counter()
        program = tokens if program is None else program
        if self.policy != "prefill-first":
            need = 2 if self.policy == "decode-first" else 1
            deadline = start + self._max_defer_s
            with self._cond:
                self._await_turn(need, deadline)
                self._last_admit_seq = self._decode_seq
                release = self._kept_behind(program)
                if release is not None:
                    # the held chunk's interval is this prefill's: whoever
                    # comes meanwhile waits for the chunk after it
                    self._last_admit_seq += 1
            if release is not None:
                release()  # takes the pool's lock: not under ours
                with self._cond:
                    self._await_turn(0, deadline)
        deferred = time.perf_counter() - start
        with self._cond:
            self._admitted.append(program)
            self.stats["prefill_chunks"] += 1
            if deferred > 0.0005:
                self.stats["deferred_chunks"] += 1
        if self._chunks_counter is not None:
            self._chunks_counter.inc(model=self.model)
            self._defer_hist.observe(deferred, model=self.model)
        return deferred

    def _await_turn(self, chunks: int, deadline: float) -> None:
        """Under ``_cond``: wait until decode has issued ``chunks`` chunks
        since the last admitted prefill (whoever is admitted meanwhile
        moves that mark: one prefill an interval, however many wait), has
        gone quiet, or the defer bound is spent."""
        while self._decode_seq < self._last_admit_seq + chunks:
            now = time.perf_counter()
            if not self._decode_busy(now):
                break
            remaining = deadline - now
            if remaining <= 0:
                break  # defer bound: prefill must keep progressing
            # short poll cap: an idle transition without a
            # note_decode_idle (pool wedged) must still release us
            self._cond.wait(min(remaining, 0.05))

    def _kept_behind(self, program: Hashable) -> Optional[Callable[[], None]]:
        """Under ``_cond``, the guard: outside a hold nothing to decide.
        During one, a prefill expected to run shorter than a chunk goes
        ahead of the held chunk; a longer one, or one of a program with
        no reading yet, goes behind it: the order without a hold, so it
        costs the rows of the pool what it costs them now. Returns what
        makes the pool issue the held chunk for one kept behind."""
        if self._hold is None:
            return None
        run_s, release = self._hold
        expected = self.expected_run_s(program)
        behind = expected is None or expected >= run_s
        self.stats["prefills_kept_behind" if behind
                   else "prefills_ahead_of_held"] += 1
        return release if behind else None
