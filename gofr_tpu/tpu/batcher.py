"""Deadline-based dynamic batcher in front of device execution.

SURVEY.md §7 hard part (b): dynamic batching without destroying p50 TTFT.
Design:

- requests enqueue (payload, Future) on a bounded queue; overflow sheds load
  with 429 instead of growing latency unboundedly;
- a dedicated worker thread takes the first request, then drains more until
  ``max_batch`` or ``timeout_ms`` past the FIRST request's arrival —
  the first request never waits longer than the deadline;
- with a ``bucket_fn``, the drained batch is split into per-bucket
  COHORTS and the fullest cohort dispatches (bucket-homogeneous batches:
  a 48-token prompt no longer pads to a co-batched 4k prompt's bucket
  and burns its FLOPs); the rest stay pending and dispatch on their own
  already-running deadlines — cohort formation never blocks an item
  beyond the deadline it was already waiting out, it only reorders which
  dispatch an item rides;
- batches pad the batch dimension to the next power of two (bounded set of
  compiled shapes), excess rows are masked out on split;
- with a ``scheduler`` (tpu/scheduler.py), each dispatch first asks the
  prefill/decode interference scheduler for its turn, so a prefill burst
  cannot starve pooled decode chunks of the shared device;
- works from sync handlers (Future.result) and async handlers
  (asyncio.wrap_future) alike — no event-loop coupling.
"""

from __future__ import annotations

import asyncio
import contextlib
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Optional, Sequence

import numpy as np

from gofr_tpu.deadline import current_deadline, deadline_exceeded_counter
from gofr_tpu.errors import DeadlineExceeded, TooManyRequestsError
from gofr_tpu.profiling import BATCHER_COLLECT, phase
from gofr_tpu.telemetry import current_record
from gofr_tpu.tpu.introspect import activate_dispatch
from gofr_tpu.tracing import current_span, get_tracer


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class _Item:
    __slots__ = ("payload", "future", "arrival", "span", "record", "deadline")

    def __init__(self, payload: Any):
        self.payload = payload
        self.future: Future = Future()
        self.arrival = time.perf_counter()
        # trace continuity across the worker-thread boundary: the caller's
        # span and flight record ride the queue item, so the dispatch-side
        # tpu-batch span lands in the SAME trace as the HTTP server span
        # and the request's record learns its queue wait + batch cohort
        self.span = current_span()
        self.record = current_record()
        # the request's end-to-end deadline rides the item too: the
        # worker sheds an expired item at dequeue instead of dispatching
        # work nobody is waiting for
        self.deadline = current_deadline()
        if self.record is not None:
            self.record.mark_enqueue()


class DynamicBatcher:
    """Batches ``run_batch(list_of_payloads) -> list_of_results`` calls.

    ``run_batch`` receives between 1 and ``max_batch`` payloads and must
    return one result per payload (it handles padding internally so it can
    exploit pow2 bucketing).
    """

    def __init__(
        self,
        run_batch: Callable[[list[Any]], Sequence[Any]],
        max_batch: int = 8,
        timeout_ms: float = 5.0,
        max_queue: int = 256,
        metrics: Any = None,
        name: str = "default",
        pipeline_depth: int = 2,
        bucket_fn: Optional[Callable[[Any], int]] = None,
        scheduler: Any = None,
        cohort: bool = True,
        timeline: Any = None,
        watchdog: Any = None,
    ):
        self.run_batch = run_batch
        self.max_batch = max_batch
        self.timeout_s = timeout_ms / 1000.0
        # bucket_fn(payload) -> the compiled sequence bucket the payload
        # lands in; enables cohort formation AND padded-token accounting
        self.bucket_fn = bucket_fn
        self.scheduler = scheduler
        self.cohort = cohort
        # engine introspection (tpu/introspect.py): every dispatch gets a
        # DispatchRecord on the timeline and runs under the stall
        # watchdog's deadline; both optional (bare test batchers)
        self.timeline = timeline
        self.watchdog = watchdog
        # pipeline_depth > 1 overlaps device execute of batch N+1 with the
        # host-transfer/completion of batch N (the gain is unmeasured on
        # this machine — ROADMAP S2)
        from concurrent.futures import ThreadPoolExecutor

        self.pipeline_depth = max(1, pipeline_depth)
        self._dispatch_pool = ThreadPoolExecutor(
            max_workers=self.pipeline_depth, thread_name_prefix=f"gofr-dispatch-{name}"
        )
        self._queue: "queue.Queue[Optional[_Item]]" = queue.Queue(maxsize=max_queue)
        # items displaced by cohort formation wait here (worker-owned;
        # sized for the depth gauge so displaced requests stay counted)
        self._pending: "deque[_Item]" = deque()
        self._closed = False
        if metrics is not None:
            self._batch_hist = metrics.histogram(
                "gofr_tpu_batch_size", "dispatched batch sizes",
                labels=("model",), buckets=(1, 2, 4, 8, 16, 32, 64, 128),
            )
            self._queue_gauge = metrics.gauge(
                "gofr_tpu_queue_depth", "requests waiting for a batch", labels=("model",)
            )
            self._wait_hist = metrics.histogram(
                "gofr_tpu_queue_wait_seconds", "time from enqueue to dispatch",
                labels=("model",),
            )
            # the padding a dispatch burned: bucket width minus true
            # length, summed over the cohort — the FLOPs the compiled
            # shape spends on pad tokens. Bucket-homogeneous cohorts
            # exist to drive this toward zero.
            self._padded_counter = (
                metrics.counter(
                    "gofr_tpu_prefill_padded_tokens_total",
                    "pad tokens dispatched in prefill batches "
                    "(bucket width minus true length, summed per cohort)",
                    labels=("model",),
                )
                if bucket_fn is not None else None
            )
            # queue-stage deadline sheds: an item whose end-to-end
            # budget expired while waiting is failed at dequeue, never
            # dispatched (one shared family across the stages — the
            # pool/device register admission/decode on the same name)
            self._deadline_counter = deadline_exceeded_counter(metrics)
        else:
            self._batch_hist = self._queue_gauge = self._wait_hist = None
            self._padded_counter = None
            self._deadline_counter = None
        self.name = name
        self._thread = threading.Thread(target=self._run, daemon=True, name=f"gofr-batcher-{name}")
        self._thread.start()

    # -- client side ---------------------------------------------------------
    def submit(self, payload: Any) -> Future:
        if self._closed:
            raise RuntimeError("batcher is closed")
        item = _Item(payload)
        try:
            self._queue.put_nowait(item)
        except queue.Full:
            raise TooManyRequestsError("inference queue is full") from None
        if self._queue_gauge:
            self._queue_gauge.set(self._depth(), model=self.name)
        return item.future

    def _depth(self) -> int:
        """Requests waiting for a batch: the queue PLUS items cohort
        formation displaced into the worker's pending buffer (still
        waiting, still counted)."""
        return self._queue.qsize() + len(self._pending)

    def infer(self, payload: Any, timeout: float = 60.0) -> Any:
        """Blocking call for sync handlers."""
        return self.submit(payload).result(timeout=timeout)

    async def infer_async(self, payload: Any) -> Any:
        """Awaitable call for async handlers."""
        return await asyncio.wrap_future(self.submit(payload))

    # -- worker --------------------------------------------------------------
    def _run(self) -> None:
        # items displaced by cohort formation wait HERE, not in the queue:
        # they were already dequeued, their deadlines keep running, and
        # the next loop iteration serves them before any new arrival
        pending = self._pending
        while True:
            if pending:
                first = pending.popleft()
            else:
                try:
                    first = self._queue.get(timeout=0.5)
                except queue.Empty:
                    if self._closed:
                        return
                    continue
                if first is None:
                    return
            if not self._viable(first):
                continue  # shed/skipped at dequeue: never holds a batch open
            batch = [first]
            deadline = first.arrival + self.timeout_s
            closing = False
            # the batch window on the profiler's clock: first item in
            # hand -> cohort complete (the idle wait for a first item is
            # not a phase of any request)
            with phase(BATCHER_COLLECT):
                while len(batch) < self.max_batch:
                    if pending:
                        item = pending.popleft()
                        if self._viable(item):
                            batch.append(item)
                        continue
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        item = self._queue.get(timeout=remaining)
                    except queue.Empty:
                        break
                    if item is None:
                        closing = True
                        break
                    if self._viable(item):
                        batch.append(item)
            # final sweep BEFORE cohort formation: an item can expire (or
            # its caller vanish) during the drain wait above — expired
            # items must never consume cohort slots or padded tokens
            batch = [item for item in batch if self._viable(item)]
            if batch:
                cohort, rest = self._form_cohort(batch)
                pending.extend(rest)
                self._dispatch_pool.submit(self._dispatch, cohort)
            if closing:
                # displaced items are invisible to close()'s queue drain —
                # flush them as cohorts before exiting, never strand them
                while pending:
                    cohort, rest = self._form_cohort(list(pending))
                    pending.clear()
                    pending.extend(rest)
                    self._dispatch_pool.submit(self._dispatch, cohort)
                return

    def _viable(self, item: "_Item") -> bool:
        """Dequeue-time gate: False for items that must not dispatch.
        A cancelled/already-resolved future is skipped silently (the
        caller walked away — satellite of the delivery-time
        ``future.cancelled()`` check, which still left the item riding
        a cohort). An item whose end-to-end deadline expired while
        queued is SHED: its future fails with a 504-mapped
        :class:`DeadlineExceeded` (stage ``queue``), the shed counts on
        the stage counter, and its FlightRecord learns the stage — the
        device never sees it (no dispatch record, no padded tokens)."""
        future = item.future
        if future.cancelled() or future.done():
            return False
        if item.deadline is not None and item.deadline.expired():
            if item.record is not None:
                item.record.note_shed("queue")
            if self._deadline_counter is not None:
                self._deadline_counter.inc(stage="queue")
            waited = time.perf_counter() - item.arrival
            try:
                future.set_exception(DeadlineExceeded(
                    f"deadline expired after {waited * 1000:.0f} ms in "
                    f"the batch queue (budget "
                    f"{item.deadline.budget_s * 1000:.0f} ms)",
                    stage="queue",
                ))
            except InvalidStateError:
                # the caller cancelled between the check above and this
                # set: either way the item must not dispatch, and the
                # race must never kill the (unrecoverable) worker thread
                pass
            return False
        return True

    def _form_cohort(self, batch: list["_Item"]) -> tuple[list["_Item"], list["_Item"]]:
        """Split a drained batch into per-bucket cohorts and pick ONE to
        dispatch: the fullest (ties go to the cohort holding the oldest
        item). Returns (cohort, displaced). A mixed FIFO batch pads every
        row to the largest member's bucket; a bucket-homogeneous cohort
        pads only within its own bucket. Displaced items dispatch on the
        next loop iterations — their deadlines have typically already
        fired, so the extra wait is the (asynchronous) dispatch handoff,
        not another full timeout."""
        if self.bucket_fn is None or not self.cohort or len(batch) <= 1:
            return batch, []
        groups: dict[int, list[_Item]] = {}
        try:
            for item in batch:
                groups.setdefault(self.bucket_fn(item.payload), []).append(item)
        except Exception:
            return batch, []  # an unbucketable payload: dispatch as-is
        if len(groups) <= 1:
            return batch, []
        chosen = max(
            groups.values(),
            key=lambda g: (len(g), -min(i.arrival for i in g)),
        )
        keep = set(map(id, chosen))
        displaced = [i for i in batch if id(i) not in keep]
        return chosen, displaced

    def _dispatch(self, batch: list[_Item]) -> None:
        # last-chance shed before the device: a batch can wait for a
        # dispatch-pool worker (the pipeline handoff) long enough for a
        # member's deadline to expire — an expired item must never ride
        # the dispatch. Filtering HERE keeps it off the timeline too
        # (_note_dispatch below creates the DispatchRecord).
        batch = [item for item in batch if self._viable(item)]
        if not batch:
            return
        now = time.perf_counter()
        if self._batch_hist:
            self._batch_hist.observe(len(batch), model=self.name)
            self._queue_gauge.set(self._depth(), model=self.name)
            for item in batch:
                self._wait_hist.observe(now - item.arrival, model=self.name)
        bucket, drec = self._note_dispatch(batch)
        # interference scheduler: one batched prefill dispatch is one
        # bounded-compute chunk — wait for its decode-interleave turn.
        # Gated on bucket_fn: only runners with a prefill/bucket concept
        # (transformer, echo) count here — an MLP/BERT classification
        # dispatch is not a prefill chunk and has no decode pool to
        # interleave with.
        if self.bucket_fn is not None:
            defer = (
                self.scheduler.admit_prefill(
                    bucket * len(batch), program=("prefill", bucket, len(batch)))
                if self.scheduler is not None else 0.0
            )
            for item in batch:
                if item.record is not None:
                    item.record.note_prefill_chunk(bucket=bucket)
                    if defer:
                        item.record.note_sched_defer(defer)
        # one tpu-batch span per dispatch, parented to the first queued
        # request's span (a cohort can mix traces; one wins) and ACTIVATED
        # in this dispatch thread so run_batch's device code tags it /
        # nests under it via current_span()
        parent = next((item.span for item in batch if item.span is not None), None)
        span = get_tracer().start_span("tpu-batch", parent=parent)
        if drec is not None:
            # running starts AFTER the scheduler gate (the interleave
            # defer shows as the record's queue_wait tail) and activates
            # on this thread so device code (run_batch) can stamp the
            # token count only it knows
            drec.mark_running()
            activate_dispatch(drec)
        try:
            try:
                with self._watch("prefill", drec):
                    results = self.run_batch(
                        [item.payload for item in batch]
                    )
                self._finish_record(drec)  # before the error-sweep below
            except Exception as exc:
                self._finish_record(drec, status="error")
                span.set_tag("error", exc)
                for item in batch:
                    if not item.future.cancelled():
                        item.future.set_exception(exc)
                return
        finally:
            # ALWAYS deactivate (BaseException included): a leaked span
            # or dispatch record in this reused pool thread would become
            # every later dispatch's bogus parent via the contextvar.
            # finish() is idempotent, so the error-status sweep only
            # lands on records a BaseException escape left running.
            if drec is not None:
                activate_dispatch(None)
                self._finish_record(drec, status="error")
            span.__exit__(None, None, None)
        for item, result in zip(batch, results):
            if not item.future.cancelled():
                item.future.set_result(result)

    def _note_dispatch(self, batch: list["_Item"]) -> tuple[int, Any]:
        """Per-dispatch accounting BEFORE the scheduler gate: the padded
        token count the compiled shape burns, the dispatch-timeline
        record (queued at the OLDEST member's arrival), and the flight-
        record marks — every member's FlightRecord learns the dispatch
        id, so /admin/requests entries resolve to the /admin/dispatches
        records that carried them. queue_wait measures enqueue -> batch
        formed; the interleave defer is its own field (sched_defer_s),
        never double-counted inside queue_wait."""
        bucket = 0
        padded = 0
        if self.bucket_fn is not None:
            try:
                bucket = max(self.bucket_fn(item.payload) for item in batch)
            except Exception:
                bucket = 0
        if bucket:
            # bucket minus true length, summed: the FLOPs the compiled
            # shape spends on pad tokens (run_batch pads every row to it)
            padded = sum(
                max(bucket - min(int(getattr(i.payload, "size", 0) or 0), bucket), 0)
                for i in batch
            )
            if padded and self._padded_counter is not None:
                self._padded_counter.inc(padded, model=self.name)
        drec = None
        if self.timeline is not None:
            drec = self.timeline.begin(
                "prefill", bucket=bucket, batch_size=len(batch),
                padded_tokens=padded,
                queued_at=min(item.arrival for item in batch),
            )
        for item in batch:
            if item.record is not None:
                item.record.mark_dispatch(len(batch))
                if drec is not None:
                    item.record.note_dispatch_id(drec.dispatch_id)
        return bucket, drec

    def _finish_record(self, drec: Any, status: str = "ok") -> None:
        if self.timeline is not None and drec is not None:
            self.timeline.finish(drec, status=status)

    def _watch(self, kind: str, drec: Any) -> Any:
        """The stall watchdog's deadline over one device call (a no-op
        context manager when no watchdog is wired)."""
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.watch(
            kind, drec.dispatch_id if drec is not None else 0
        )

    def close(self) -> None:
        self._closed = True
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            pass
        self._thread.join(timeout=2.0)
        # fail anything still queued fast instead of letting blocking
        # callers sleep out their full timeout
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None and not item.future.done():
                item.future.set_exception(RuntimeError("batcher closed"))
        self._dispatch_pool.shutdown(wait=False)


def verify_width(max_k: int, k_max: int) -> int:
    """Cohort a pooled-spec verify's token width onto the pow2 ladder:
    the dispatch carries ``max_k`` drafts + 1 pending token per row, and
    compiling one executable per exact width would trade the compile
    budget the bucket ladder exists to bound. The width rounds up to
    the next power of two (clamped at ``k_max + 1``, the widest any
    cycle can need); rows with shorter drafts pad to it and their
    surplus positions verify as garbage — masked by the per-row
    acceptance exactly like bucket padding is masked by lengths. The
    whole ladder is ``log2(k_max)+1`` executables, warmed at pool
    construction."""
    if max_k < 0:
        raise ValueError(f"max_k must be >= 0, got {max_k}")
    return min(next_pow2(max_k + 1), k_max + 1)


def verify_width_ladder(k_max: int) -> tuple[int, ...]:
    """Every width a DISPATCHED spec cycle can need for ``k_max`` —
    the pool warms exactly these shapes at construction. Starts at 2:
    the worker never dispatches a zero-draft cycle (it falls back to
    the plain chunk), so the minimum live width is one draft + the
    pending token."""
    widths = []
    w = 2
    while w < k_max + 1:
        widths.append(w)
        w *= 2
    widths.append(k_max + 1)
    return tuple(sorted(set(widths)))


def pad_rows(rows: list[np.ndarray], target: int) -> np.ndarray:
    """Stack [n, ...] rows and pad the batch dim to ``target`` by repeating
    the last row (repeats keep shapes identical to real work, so padded and
    unpadded batches hit the same compiled executable)."""
    stacked = np.stack(rows)
    if len(rows) < target:
        pad = np.repeat(stacked[-1:], target - len(rows), axis=0)
        stacked = np.concatenate([stacked, pad], axis=0)
    return stacked


def pack_token_rows(
    rows: Sequence[np.ndarray], n_rows: int, width: int, pad_id: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Pack variable-length int32 id rows into a [n_rows, width] batch +
    per-row kept lengths. Overlong rows keep their LAST tokens. Uses the
    native gofr_pack_rows when the C++ library is available (the serving
    hot path); Python loop otherwise."""
    import ctypes

    from gofr_tpu import native

    out = np.full((n_rows, width), pad_id, np.int32)
    out_lens = np.zeros(n_rows, np.int32)
    if not rows:
        return out, out_lens
    lib = native.load()
    if lib is not None:
        flat = np.ascontiguousarray(
            np.concatenate([np.asarray(r, np.int32).reshape(-1) for r in rows])
        )
        lens = np.asarray([np.asarray(r).size for r in rows], np.int64)
        lib.gofr_pack_rows(
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(rows), width, pad_id,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out, out_lens
    for i, row in enumerate(rows):
        ids = np.asarray(row, np.int32).reshape(-1)[-width:]
        out[i, : ids.size] = ids
        out_lens[i] = ids.size
    return out, out_lens
