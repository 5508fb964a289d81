"""Continuous batching for decode: a slot-based KV-cache pool with a
pipelined dispatch loop.

Prefill is batched by the DynamicBatcher; without this module each
generation then decodes alone ([1, 1] dispatches), so N concurrent streams
cost N round trips per token. The pool keeps ONE batched cache of
``n_slots`` rows and a worker that decodes ALL active slots in a single
fixed-shape chunked dispatch — N streams share one round trip per chunk.

The dispatch loop is PIPELINED: the last sampled token of every slot stays
ON DEVICE (``_last_tokens``, fed forward chunk-to-chunk exactly like the
in-chunk scan feeds itself), so chunk N+1 dispatches immediately after
chunk N — its inputs are N's output futures — and the host fetch of chunk
N's tokens overlaps chunk N+1's execution. Without this, the device idles
one host round trip per chunk. At most two chunks are in flight, one
running and one queued: the host's part of a chunk is 1-2 ms against
chunks of 21-290 ms (PERF.md section 6, PR 31), and every further chunk in
flight is a chunk a prefill, and a newly seated row's first decode, queue
behind. For the same reason the queued chunk is issued when the device is
about to need it, not when the host can (PR 47): with a chunk running and
its own device time known (``run_s``), the worker HOLDS the next one until
``lead`` before the running one is due to end, ``lead`` being what the
host has needed to get a held chunk onto the device's queue. A short
prefill issued meanwhile runs behind the chunk that is running and not
behind the queued one too, and a row seated meanwhile rides the held
chunk (``_hold``; the interference scheduler decides which prefills go
ahead of the held chunk, tpu/scheduler.py).

Mechanics:
- a finished prefill row is copied into a free slot (one jitted
  dynamic_update_slice per cache field) and its first token is written
  into the device-resident token row;
- the worker keeps up to ``pipeline_depth`` chunks in flight (2), the
  last of them from ``lead`` before the device needs it where the
  chunk's run is known and long against the lead, else from the moment
  the fetch before it returned; each dispatch snapshots (slot index ->
  request) so a slot freed and reused mid-pipeline never leaks garbage
  tokens to the new request;
- inactive slots decode garbage in lockstep (fixed shapes = one compiled
  executable) and are overwritten on reuse;
- per-request host-tracked lengths stop a request at the cache bound;
- a prefilled request that finds every slot taken (or the KV ledger
  spent on pooled rows) WAITS for a seat, in arrival order, holding its
  one-row cache: the worker seats it before its next dispatch once a
  finishing request has freed a slot, so it rides the second chunk after
  that delivery, as a request submitted to a free slot does. At most
  ``standing_room`` prefilled requests wait so; ``gate()`` holds the
  ones past that before their prefill, with nothing on the device;
- requests with an explicit sampling seed bypass the pool (the
  per-request path reproduces exactly; pooled key order depends on
  co-tenants);
- LoRA adapter requests decode in the pool through a stacked adapter
  bank: per-slot ids gather each row's adapter (0 = a zero identity
  entry for base rows) inside the chunk executable, so two adapters and
  the base share one dispatch (``enable_lora``/``submit(adapter=...)``).
"""

from __future__ import annotations

import contextlib
import functools
import queue
import sys
import threading
from collections import deque
from time import perf_counter as _perf_counter
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.deadline import (
    cancellations_counter,
    current_deadline,
    deadline_exceeded_counter,
    pool_reject_counter,
)
from gofr_tpu.errors import DeadlineExceeded
from gofr_tpu.profiling import (
    POOL_DELIVER,
    POOL_FETCH_WAIT,
    POOL_HOLD,
    POOL_ISSUE,
    POOL_SEAT_WAIT,
    POOL_STATE_INSERT,
    POOL_WAIT_WORK,
    phase,
)
from gofr_tpu.telemetry import current_journal_entry, current_record
from gofr_tpu.tpu.scheduler import RUN_SAMPLES

DONE = object()  # end-of-stream marker on a slot's token queue
# precedes DONE on a slot queue whose request's end-to-end deadline
# expired mid-decode: the consumer re-raises DeadlineExceeded instead
# of treating the truncated stream as a clean finish
DEADLINE = object()
# how often a request waiting for a seat, or for a place before its
# prefill, looks at its stop event and its deadline (a seat itself wakes it
# at once)
_WAIT_POLL_S = 0.05
# a chunk is held back only where it runs this many times the lead: under
# that the hold gains a prefill little and a late wake-up costs every row
# (chunks of a few milliseconds are issued the moment the host can)
_HOLD_MIN_RUNS_PER_LEAD = 4.0


class PoolFailure:
    """Pushed to every waiter when the worker dies; carries the cause so
    request threads re-raise instead of silently truncating output."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Request:
    """Host-side bookkeeping for one pooled generation. Lives in dispatch
    snapshots; a slot's ``request`` pointer moves on to the next request
    while old snapshots still reference this one (then ``finished`` gates
    delivery)."""

    __slots__ = (
        "out_queue", "remaining", "cache_len", "stop", "stop_tokens",
        "finished", "want_lp", "want_top", "want_kv", "record",
        "kv_reserved", "journal", "deadline", "spec", "pending",
    )

    def __init__(self, out_queue: "queue.Queue", remaining: int, cache_len: int,
                 stop: Optional[threading.Event], stop_tokens: frozenset,
                 want_lp: bool = False, want_top: bool = False,
                 want_kv: bool = False, record: Any = None,
                 kv_reserved: int = 0, journal: Any = None,
                 deadline: Any = None, spec: Any = None,
                 pending: int = 0):
        self.out_queue: Optional[queue.Queue] = out_queue
        self.remaining = remaining
        self.cache_len = cache_len
        self.stop = stop
        self.stop_tokens = stop_tokens
        self.finished = False
        # bursts become (token, logprob, tops|None) triples; the lps ride
        # every chunk anyway (computed in-executable), these flags only
        # pick the delivery shape and gate the top-k fetch
        self.want_lp = want_lp
        self.want_top = want_top
        # hand the slot's KV row back at finish (("kv", row) precedes
        # DONE): the device stores it in the prefix cache so a follow-up
        # turn reuses the WHOLE conversation's KV
        self.want_kv = want_kv
        # the caller's FlightRecord (if any): every pooled chunk dispatch
        # stamps its dispatch id onto it (bounded by the record itself)
        self.record = record
        # paged-KV ledger reservation (block count): the request's
        # whole KV budget, claimed at admission and released THE MOMENT
        # the request finishes — freed budget admits the next request
        # mid-flight instead of waiting for any drain
        self.kv_reserved = kv_reserved
        # the caller's generation-journal entry (if journaling is on):
        # a pool death stamps WHERE the stream was interrupted so the
        # recovery-resume path can distinguish pool failures from
        # client aborts
        self.journal = journal
        # the request's end-to-end deadline (gofr_tpu/deadline.py):
        # the worker checks it per delivered chunk — an expired row
        # finishes with DEADLINE, freeing its slot and KV mid-flight
        self.deadline = deadline
        # pooled speculative decoding (tpu/spec_pool.py): this
        # request's draft source + adaptive-k controller, None when the
        # request is ineligible (sampled/penalized/adapter/logprobs) or
        # SPEC_POOLED is off. The worker runs spec verify cycles only
        # while EVERY active row carries one.
        self.spec = spec
        # the request's feed-forward token, host-tracked: the last
        # sampled token of its newest fetched chunk (or first_token at
        # submit). Spec cycles rebuild the device token vector from
        # these, so a spec cycle can follow a plain chunk exactly.
        self.pending = int(pending)


class _Waiter:
    """A prefilled request that stands waiting for a seat: its request and
    what else ``submit`` was given to seat it with. ``settled`` is set under
    the pool lock by whoever ends the wait (the worker that seated or
    refused it, a failing pool, the waiter itself on leaving); ``error`` is
    what ``submit`` then raises, None once seated."""

    __slots__ = ("request", "row_cache", "sampler", "penalty", "adapter",
                 "settled", "error")

    def __init__(self, request: _Request, row_cache: dict, sampler: Any,
                 penalty: Optional[tuple], adapter: Optional[str]):
        self.request = request
        self.row_cache = row_cache
        self.sampler = sampler
        self.penalty = penalty
        self.adapter = adapter
        self.settled = threading.Event()
        self.error: Optional[BaseException] = None


class _Slot:
    __slots__ = ("index", "request")

    def __init__(self, index: int):
        self.index = index
        self.request: Optional[_Request] = None


class DecodePool:
    def __init__(
        self,
        params: Any,
        cfg: Any,
        init_cache: Any,
        n_slots: int,
        chunk: int,
        metrics: Any = None,
        cache_shardings: Any = None,
        model: str = "",
        pipeline_depth: int = 2,
        penalties: str = "lazy",
        scheduler: Any = None,
        timeline: Any = None,
        watchdog: Any = None,
        kv: Any = None,
        spec: Any = None,
        standing_room: int = 2,
    ):
        from gofr_tpu.models.transformer import decode_chunk_pool

        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if penalties not in ("lazy", "eager", "off"):
            raise ValueError(
                f"penalties must be lazy|eager|off, got {penalties!r}"
            )
        # chunks kept in flight: one running, one queued. No
        # configuration key reaches this; only tests and a by-hand
        # comparison pass another number. A third bought no busy time on
        # the chip at chunks of 21-290 ms and stood in front of every
        # prefill (PERF.md section 6, PR 31); at 1 the device idles one
        # host round trip a chunk by construction
        self.pipeline_depth = pipeline_depth
        # WHEN the last place of the pipeline is filled (``_hold``). The
        # two estimates, both the worker's own: a chunk's device time
        # (the shortest of the last few delivery intervals with the
        # device busy throughout and no prefill admitted between the two
        # issues; forgotten when the pool drains), and the peak, slowly let down, of what the host
        # has needed to put a chunk on the device's queue (a held one:
        # how far after its due time it was there, the wake-up's lateness
        # included)
        self._run_samples: deque = deque(maxlen=RUN_SAMPLES)
        self._lead_peak_s = 0.0
        # a hold is on (read without the lock by whoever issues a program
        # of its own: that program goes ahead of the held chunk); the
        # scheduler's guard ended it early; what the hold hands the issue
        # it precedes: (when it began, its due time or None if ended early)
        self.holding = False
        self._hold_released = False
        self._held: Optional[tuple] = None
        self.held_issues = 0
        # of them, those that found the running chunk done: the device
        # had waited
        self.held_issues_late = 0
        # interference scheduler (tpu/scheduler.py): the pool NOTES each
        # chunk dispatch and each hold (never throttled) so prefill chunks
        # can interleave between decode turns instead of stalling them
        self._sched = scheduler
        # paged-KV admission (tpu/kv_blocks.py BlockPool, shared with
        # the prefix cache): submit reserves a request's block budget —
        # admission is block-granular against ONE HBM ledger, so cached
        # prefixes are evicted to admit live traffic and a finished
        # request's blocks admit the next one immediately
        self._kv = kv
        # engine introspection (tpu/introspect.py): every chunk dispatch
        # lands on the dispatch timeline and its host fetch runs under
        # the stall watchdog's deadline
        self._timeline = timeline
        self._watchdog = watchdog
        self._in_flight_chunks: deque = deque()  # replaced by the worker
        # dispatches this pool issued and has not yet fetched — a plain
        # int beside the deque, written by the worker alone, read without
        # the lock by whoever issues a program of its own (a prefill, a
        # solo chunk) to record what it queued behind on the device
        self.chunks_in_flight = 0
        # the record of a chunk BETWEEN begin() and its in_flight.append
        # (the jitted dispatch can raise in that window) — swept by
        # _abandon_in_flight like the appended ones
        self._pending_chunk_drec: Any = None
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.chunk = chunk
        self.max_len = cfg.max_seq
        self._init_cache = init_cache
        # per-slot penalty machinery (presence/counts/bias rows + knob
        # vectors + the penalized executable): "off" never pools penalized
        # requests (they decode solo, the pre-r04 behavior); "lazy" builds
        # it in a BACKGROUND thread on the first penalized submit (that
        # request solos while the executable compiles — the serving path
        # never compiles under the pool lock); "eager" builds it at boot
        self._pen_mode = penalties
        self._pen_ready = False
        self._pen_starting = False
        self._pen_slots: set[int] = set()
        # pooled multi-LoRA: a stacked adapter bank + per-slot adapter ids
        # let adapter requests share the pool chunk instead of decoding
        # solo (enable_lora builds the executable; the worker dispatches
        # it only while an adapter slot is active). Penalized and adapter
        # slots are mutually exclusive IN one chunk (different
        # executables) — submit rejects the later arrival, which solos.
        self._lora_ready = False
        self._lora_slots: set[int] = set()
        self._lora_index: dict[str, int] = {}
        self._lora_params: Any = None
        self._decode_lora: Any = None
        self._lora_pending: Optional[tuple] = None
        self._lora_ids = np.zeros(n_slots, np.int32)
        self._lora_dirty = True
        self._lora_ids_dev = None
        self.lora_chunks = 0  # dispatches through the adapter executable
        # under a serving mesh the pool cache takes the SAME placement as
        # the prefill cache (slot axis over dp/fsdp, kv heads over tp) so
        # the pooled decode compiles as one SPMD program — row caches
        # written in from prefill already live on the same mesh
        self._cache_shardings = cache_shardings
        self.cache = self._place(init_cache(cfg, n_slots))
        # one row's fixed-size state over all its layers (a retention
        # model's S and z; a state-space layer's state and convolution
        # tail), in bytes: what a live row reads and writes a step; 0 for
        # a cache of K/V rows alone
        from gofr_tpu.models.transformer import latent_token_bytes, state_row_bytes

        self._state_row_bytes = state_row_bytes(self.cache)
        # a latent cache: what one token holds over all its places
        self._latent_token_bytes = latent_token_bytes(self.cache)
        # the positions of K/V the attention kernel fetches at a time
        # (ops/flash.py, the decode form); 0 for a state
        from gofr_tpu.ops.flash import DEFAULT_BLOCK_KV

        self._kv_block = DEFAULT_BLOCK_KV if "k" in self.cache else 0
        self._live_mask: Optional[tuple] = None  # what cache["live"] holds
        self._model = model
        # under a mesh, pin EVERY executable's feedback outputs (tokens,
        # key) to replicated and the cache to its mesh placement: GSPMD
        # otherwise picks shardings per-jit (e.g. tokens over dp), and
        # the plain executable, the write ops, and the AOT penalized
        # executable would disagree the moment traffic switches between
        # them (reproduced as a dispatch-time sharding mismatch)
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = (
            next(iter(cache_shardings.values())).mesh
            if cache_shardings else None
        )
        from gofr_tpu.parallel.mesh import mesh_axes

        # the pool's own record of the mesh its executables compiled
        # for — occupancy() carries it so /admin/engine shows which
        # topology the slot cache is sharded over
        self.mesh_axes = mesh_axes(mesh)
        self._repl = (
            NamedSharding(mesh, PartitionSpec()) if mesh is not None else None
        )
        repl = self._repl
        self._last_tokens = self._replicate(jnp.zeros((n_slots, 1), jnp.int32))
        # donate the cache through both ops: the pool cache is the largest
        # live buffer and must be updated in place, not copied per chunk.
        # Inside the chunk the step loop and the layer loop carry that
        # same buffer (models/transformer.py::_run_cached), so no step
        # copies it either. The key also donates (it threads through
        # every chunk).
        self._decode = jax.jit(
            lambda p, t, c, key, temp, tk, tp, mp: decode_chunk_pool(
                p, t, c, cfg, chunk, key, temp, tk, tp, mp
            ),
            donate_argnums=(2, 3),
            out_shardings=(
                (repl, repl, repl, repl, repl, repl, dict(cache_shardings))
                if repl is not None else None
            ),
        )

        # a cache is K and V, a state, a tail, or K/V rows and a state
        # side by side, each stacked over the layers of its kind: every
        # stack has its slot axis second, ``lengths`` [slots] has it first
        def write_slot(pool: dict, row: dict, i) -> dict:
            return {
                name: jax.lax.dynamic_update_slice_in_dim(
                    leaf, row[name], i, axis=0 if leaf.ndim == 1 else 1)
                for name, leaf in pool.items()
            }

        self._write_slot = jax.jit(
            write_slot, donate_argnums=(0,),
            out_shardings=dict(cache_shardings) if repl is not None else None,
        )
        self._write_token = jax.jit(
            lambda toks, tok, i: jax.lax.dynamic_update_slice(toks, tok, (i, 0)),
            donate_argnums=(0,),
            out_shardings=repl,
        )

        def read_slot(pool: dict, i) -> dict:
            # COPY, not a view: the pool cache is donated into every later
            # chunk dispatch; a handed-back row must own its buffers
            # (``live`` comes back 1: the slot held the request in every
            # mask since its row was written)
            return {
                name: jnp.copy(jax.lax.dynamic_slice_in_dim(
                    leaf, i, 1, axis=0 if leaf.ndim == 1 else 1))
                for name, leaf in pool.items()
            }

        self._read_slot = jax.jit(read_slot)
        self.spec_cfg = spec
        self._verify_pool = None
        # consecutive no-draft spec rounds: past a small threshold the
        # worker restores full pipelining for the (undraftable) cohort
        self._spec_idle = 0
        if spec is not None:
            self._build_spec_exec(cfg, cache_shardings, repl)
        self._slots = [_Slot(i) for i in range(n_slots)]
        self._free = list(reversed(self._slots))
        self._active: dict[int, _Slot] = {}
        # prefilled requests waiting for a seat, in arrival order; each
        # holds one row's cache on the device, so ``gate()`` lets no more
        # than ``standing_room`` stand here: as many as one prefill dispatch
        # makes rows (the device passes its BATCH_MAX_SIZE; no
        # configuration key of its own)
        self._waiters: deque = deque()
        self.standing_room = standing_room
        # the gate: places not taken, and the turns of the requests
        # waiting for one before their prefill (each a threading.Event)
        self._places_free = n_slots + standing_room
        self._gate_line: deque = deque()
        self._temps = np.zeros(n_slots, np.float32)
        self._top_ks = np.zeros(n_slots, np.int32)
        self._top_ps = np.ones(n_slots, np.float32)
        self._min_ps = np.zeros(n_slots, np.float32)
        # device-resident copies, refreshed only when a submit changes them
        # (three host->device uploads per CHUNK otherwise — pure link waste)
        self._sampling_dirty = True
        self._temps_dev = self._top_ks_dev = self._top_ps_dev = None
        self._min_ps_dev = None
        # device-resident, advanced INSIDE each chunk dispatch (no per-chunk
        # host-side split op)
        self._key = self._replicate(
            jax.random.key(np.random.SeedSequence().entropy % (1 << 63))
        )
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._closed = False
        self._init_metrics(metrics)
        # warm the [n_slots]-shaped executable NOW: the first pooled request
        # must not compile under the pool lock on the serving path
        toks, _, _, _, _, self._key, self.cache = self._decode(
            self.params, self._last_tokens, self.cache,
            self._key, jnp.asarray(self._temps),
            jnp.asarray(self._top_ks), jnp.asarray(self._top_ps),
            jnp.asarray(self._min_ps),
        )
        toks.block_until_ready()
        # warm the finish-time row read too (prefix-cache hand-back): it
        # must never compile on the serving path
        row = self._read_slot(self.cache, 0)
        row["lengths"].block_until_ready()
        # and the admission writes: submit() runs them under the pool
        # lock, where a first-use compile stalls every pooled stream
        self.cache = self._write_slot(self.cache, row, 0)
        self._last_tokens = self._write_token(
            self._last_tokens, jnp.asarray([[0]], jnp.int32), 0
        )
        self._last_tokens.block_until_ready()
        if spec is not None:
            self._warm_spec()
        # reset the warmup writes; the old cache goes first, so that two
        # never stand side by side (a retention state is 3.3 GB at 12 slots)
        self.cache = None
        self.cache = self._place(init_cache(cfg, n_slots))
        self._last_tokens = self._replicate(jnp.zeros((n_slots, 1), jnp.int32))
        if penalties == "eager":
            self._enable_penalties()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="gofr-decode-pool"
        )
        self._thread.start()

    def _init_metrics(self, metrics: Any) -> None:
        """Register the pool's metric instruments (None registry = all
        instruments None; callers already guard on that)."""
        self._depth_gauge = (
            metrics.gauge("gofr_tpu_decode_slots_active", "active decode slots")
            if metrics is not None
            else None
        )
        # submit rejections by reason: this counter (and the
        # FlightRecord's pool_reject_reason) says WHY a stream missed the
        # pool and decoded solo. A full pool is not among them: the
        # request waits, and the histogram below says for how long
        self._reject_counter = (
            pool_reject_counter(metrics)
            if metrics is not None
            else None
        )
        self._seat_wait_hist = (
            metrics.histogram(
                "gofr_tpu_pool_seat_wait_seconds",
                "time a prefilled request waited for a decode slot while "
                "the pool was full (requests seated at once are not "
                "observed)",
                labels=("model",),
            )
            if metrics is not None
            else None
        )
        # deadline-aware serving: the admission gate and the per-chunk
        # row expiry share these families with the batcher's queue
        # stage (one registration home: gofr_tpu/deadline.py)
        self._deadline_counter = (
            deadline_exceeded_counter(metrics)
            if metrics is not None
            else None
        )
        self._cancel_counter = (
            cancellations_counter(metrics)
            if metrics is not None
            else None
        )
        # observed chunk cadence (EMA of the dispatch->fetch span per
        # chunk): the admission gate's unit of "can this request still
        # get even one chunk of decode before its deadline"
        self._chunk_ema_s = 0.0
        # lookup — the registration home (help text) is
        # tpu/device.py _init_metrics (GFL007)
        self._tokens_counter = (
            metrics.counter("gofr_tpu_tokens_total", labels=("model", "op"))
            if metrics is not None
            else None
        )

    # -- per-slot penalties ---------------------------------------------------
    def _enable_penalties(self) -> None:
        """Build the penalized-pool machinery: the [slots, V] presence/
        counts/bias state, per-slot knob vectors, slot write/zero ops, and
        the penalized executable (warmed on THROWAWAY state — the live
        cache must not be donated into a warmup)."""
        from gofr_tpu.models.transformer import decode_chunk_pool_penalized

        cfg, chunk, n = self.cfg, self.chunk, self.n_slots
        v = cfg.vocab_size

        def pen_fn(p, t, c, key, temp, tk, tp, mp, pres, rep, cnt, pp, fp,
                   bias):
            return decode_chunk_pool_penalized(
                p, t, c, cfg, chunk, key, temp, tk, tp, mp, pres, rep,
                cnt, pp, fp, bias,
            )

        def write_rows(pres, cnt, bias, pr, cr, br, i):
            return (
                jax.lax.dynamic_update_slice(pres, pr, (i, 0)),
                jax.lax.dynamic_update_slice(cnt, cr, (i, 0)),
                jax.lax.dynamic_update_slice(bias, br, (i, 0)),
            )

        def zero_bias_row(bias, i):
            return jax.lax.dynamic_update_slice(
                bias, jnp.zeros((1, v), jnp.float32), (i, 0)
            )

        # compile AHEAD OF TIME on abstract shapes: a live-serving lazy
        # build must not allocate a throwaway [slots] KV cache next to
        # the real one (the pool cache is the largest live buffer — a
        # second copy could OOM a cache-sized deployment mid-traffic).
        #
        # Under a mesh, every lowering input takes the POOL's pinned
        # shardings, never a live array's: params/cache keep their mesh
        # placement, everything else — INCLUDING the fed-back token/key,
        # whose live sharding at build time is whatever the plain
        # executable last produced — lowers as replicated, matching the
        # out_shardings every pool executable pins (a lazily built
        # executable that trusted a live P('dp') token sharding crashed
        # the first penalized dispatch under a dp mesh).
        repl = self._repl

        def abs_struct(shape, dtype):
            if repl is not None:
                return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)
            return jax.ShapeDtypeStruct(shape, dtype)

        def abs_repl(a):
            return abs_struct(a.shape, a.dtype)

        def abs_placed(a):
            sh = getattr(a, "sharding", None)
            if repl is not None and sh is not None:
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        write_rows_j = jax.jit(
            write_rows, donate_argnums=(0, 1, 2),
            out_shardings=(repl, repl, repl) if repl is not None else None,
        )
        zero_bias_j = jax.jit(
            zero_bias_row, donate_argnums=(0,), out_shardings=repl
        )

        with self._work:
            cache_meta = jax.tree.map(abs_placed, self.cache)
            tok_meta = abs_repl(self._last_tokens)
            key_meta = abs_repl(self._key)
        params_meta = jax.tree.map(abs_placed, self.params)
        f32v = abs_struct((n,), jnp.float32)
        i32v = abs_struct((n,), jnp.int32)
        rows_b = abs_struct((n, v), jnp.bool_)
        rows_f = abs_struct((n, v), jnp.float32)
        # outputs: (toks, lps, tvals, tids, next_tok, key, cache,
        # presence, counts) — cache keeps its mesh placement, everything
        # else (incl. the penalty state fed back as the next dispatch's
        # input) stays replicated, matching the row ops above
        decode_pen = jax.jit(
            pen_fn, donate_argnums=(2, 3, 8, 10),
            out_shardings=(
                (repl, repl, repl, repl, repl, repl,
                 dict(self._cache_shardings), repl, repl)
                if repl is not None else None
            ),
        )
        decode_pen_exec = decode_pen.lower(
            params_meta, tok_meta, cache_meta, key_meta,
            f32v, i32v, f32v, f32v, rows_b, f32v, rows_f, f32v, f32v,
            rows_f,
        ).compile()
        # warm the slot write/zero ops here too: submit and _deliver call
        # them under the pool lock, where a first-use trace+compile would
        # stall every pooled stream for the compile duration
        pres0 = jnp.zeros((n, v), jnp.bool_)
        cnt0 = jnp.zeros((n, v), jnp.float32)
        bias0 = jnp.zeros((n, v), jnp.float32)
        pres0, cnt0, bias0 = write_rows_j(
            pres0, cnt0, bias0,
            jnp.zeros((1, v), jnp.bool_), jnp.zeros((1, v), jnp.float32),
            jnp.zeros((1, v), jnp.float32), 0,
        )
        bias0 = zero_bias_j(bias0, 0)
        bias0.block_until_ready()
        with self._work:
            self._decode_pen = decode_pen_exec
            self._write_rows = write_rows_j
            self._zero_bias = zero_bias_j
            # the warmup wrote zero rows into zeros — still all-zero state
            self._pres = pres0
            self._cnts = cnt0
            self._bias = bias0
            self._reps = np.ones(n, np.float32)
            self._pps = np.zeros(n, np.float32)
            self._fps = np.zeros(n, np.float32)
            self._pen_dirty = True
            self._reps_dev = self._pps_dev = self._fps_dev = None
            self._pen_ready = True
            self._pen_starting = False

    def _pen_kick(self) -> None:
        """Start the one-shot background build of the penalty machinery
        (caller holds the pool lock)."""
        if self._pen_starting or self._pen_ready:
            return
        self._pen_starting = True

        def build() -> None:
            try:
                self._enable_penalties()
            except BaseException:
                # a failed build must not wedge the flag: the next
                # penalized submit retries (requests solo meanwhile)
                self._pen_starting = False
                raise

        threading.Thread(
            target=build, daemon=True, name="gofr-pool-pen-build"
        ).start()

    # -- pooled multi-LoRA ----------------------------------------------------
    def enable_lora(self, stacked: dict, index: "dict[str, int]") -> None:
        """Build (or rebuild) the per-slot adapter executable from a
        ``build_lora_stack`` tree and its name -> bank-index map. Compiles
        OUTSIDE the pool lock on abstract shapes (same AOT policy as the
        penalized build). If adapter slots are mid-generation, the swap is
        deferred to the worker (their ids index the OLD bank; new adapter
        submits solo meanwhile) — an admin adapter load must never block
        behind a long generation."""
        from gofr_tpu.models.transformer import decode_chunk_pool_lora

        if self._cache_shardings is not None:
            raise ValueError(
                "pooled multi-LoRA does not support a serving mesh yet — "
                "adapter requests decode solo under TPU_MESH"
            )
        cfg, chunk = self.cfg, self.chunk

        def lora_fn(p, ids, t, c, key, temp, tk, tp, mp):
            return decode_chunk_pool_lora(
                p, ids, t, c, cfg, chunk, key, temp, tk, tp, mp
            )

        def abs_of(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        with self._work:
            cache_meta = jax.tree.map(abs_of, self.cache)
            tok_meta = abs_of(self._last_tokens)
            key_meta = abs_of(self._key)
        n = self.n_slots
        f32v = jax.ShapeDtypeStruct((n,), jnp.float32)
        i32v = jax.ShapeDtypeStruct((n,), jnp.int32)
        exe = jax.jit(lora_fn, donate_argnums=(3, 4)).lower(
            jax.tree.map(abs_of, stacked), i32v, tok_meta, cache_meta,
            key_meta, f32v, i32v, f32v, f32v,
        ).compile()
        with self._work:
            if self._lora_slots:
                self._lora_ready = False  # stop new submits on the old bank
                self._lora_pending = (exe, stacked, dict(index))
            else:
                self._install_lora(exe, stacked, dict(index))

    def _install_lora(self, exe: Any, stacked: dict,
                      index: "dict[str, int]") -> None:
        """Swap in a compiled bank (pool lock held, no adapter slot active)."""
        self._decode_lora = exe
        self._lora_params = stacked
        self._lora_index = index
        self._lora_ids[:] = 0
        self._lora_dirty = True
        self._lora_pending = None
        self._lora_ready = True

    def disable_lora(self) -> None:
        """Stop pooling adapter requests (they solo). In-flight adapter
        slots finish on the bank they hold — the bank stays referenced
        until the next ``enable_lora`` replaces it."""
        with self._work:
            self._lora_ready = False
            self._lora_index = {}
            self._lora_pending = None

    def _place(self, cache: dict) -> dict:
        if self._cache_shardings is None:
            return cache
        return {k: jax.device_put(v, self._cache_shardings[k]) for k, v in cache.items()}

    def _replicate(self, x: Any) -> Any:
        """Commit a host-built feedback input (token vector, key) to the
        placement every pool executable pins its outputs to. Under a mesh
        jit keys its executable cache on input placement: a fresh
        uncommitted array at warmup and an executable's replicated output
        at serving time would be two signatures, and the first real chunk
        would recompile the pooled decode under the pool lock."""
        return x if self._repl is None else jax.device_put(x, self._repl)

    # -- request side --------------------------------------------------------
    def submit(
        self,
        row_cache: dict,
        start_len: int,
        first_token: int,
        max_new: int,
        sampler: Any,
        stop: Optional[threading.Event] = None,
        stop_tokens: frozenset = frozenset(),
        penalty: Optional[tuple] = None,
        want_logprobs: bool = False,
        want_top_logprobs: bool = False,
        adapter: Optional[str] = None,
        want_kv: bool = False,
        spec_ctx: Optional[Any] = None,
    ) -> "queue.Queue":
        """Seat a prefilled request; returns the queue its decoded token
        ids (then DONE) arrive on. With a slot and the request's KV budget
        free the seat is taken at once. With the pool FULL the call waits
        for one, in arrival order: the request keeps its one-row cache, the
        worker seats it before its next dispatch once a finishing request
        has freed a slot and budget, and the call returns then. The wait
        ends without a seat when ``stop`` is set (the queue comes back
        holding DONE alone), when the deadline can no longer cover a chunk
        (``DeadlineExceeded``, accounted as at the gate below), and when
        the pool closes or dies (``RuntimeError``: the caller decodes
        solo, as after a submit to a closed pool).

        Raises queue.Full for what no finishing request cures; the caller
        decodes such a request solo:

        ``penalty`` pools a penalized request: (presence_row [1, V] bool,
        counts_row [1, V] f32, bias_row [1, V] f32, repetition_penalty,
        presence_penalty, frequency_penalty) — rows already include the
        first emitted token, matching ``first_token``. Raises queue.Full
        while the penalized machinery is off/still building (the caller
        solos; a lazy build starts in the background on first use).

        ``adapter`` pools a LoRA request: the slot decodes with that
        adapter's bank entry while co-tenants keep theirs (or the base).
        The name resolves against the CURRENT bank under the lock — never
        a stale pre-checked index. Raises queue.Full when the bank is
        off/rebuilding, the name is unknown to the bank, or a penalized
        slot is active (the chunk runs ONE executable; the mix solos). A
        request that waited is asked again as it is seated.

        A KV ledger that is spent while NO row is pooled (something else
        holds it: live paged sequences, an operator's claim) is refused
        with ``kv_exhausted`` too: no finishing request would return it.

        ``spec_ctx`` (prompt token ids) arms pooled speculative decoding
        for this request when the pool has a spec config and the request
        is eligible — greedy, unpenalized, base weights, no logprobs
        (the verify executable computes argmaxes, not logprob rows).
        Ineligible requests pool normally; the worker speculates only
        while every active row is spec-armed."""
        out: "queue.Queue" = queue.Queue()
        deadline = current_deadline()
        spec_state = self._spec_arm(
            spec_ctx, first_token, sampler, penalty, adapter,
            want_logprobs, want_top_logprobs,
        )
        waiter = _Waiter(
            _Request(out, max_new, start_len, stop,
                     frozenset(stop_tokens or ()),
                     want_lp=want_logprobs, want_top=want_top_logprobs,
                     want_kv=want_kv, record=current_record(),
                     journal=current_journal_entry(),
                     deadline=deadline, spec=spec_state,
                     pending=first_token),
            row_cache, sampler, penalty, adapter,
        )
        with self._work:
            if self._closed:
                self._reject("closed", count_only=True)
                raise RuntimeError("decode pool closed")
            self._admit_deadline(deadline)
            adapter_idx = self._admit(adapter, penalty)
            # behind whoever already waits, even past a slot a delivery
            # has just freed: seats are taken in arrival order
            if not self._waiters and self._seat(waiter, adapter_idx):
                self._work.notify()
                return out
            self._waiters.append(waiter)
        self._await_seat(waiter)
        return out

    def _seat(self, waiter: _Waiter, adapter_idx: int) -> bool:
        """Seat a prefilled request if a slot and its KV budget are free
        (pool lock held): the reservation, the slot's knobs, the row and
        token writes, the marks. False when it has to wait: no slot, or
        the ledger spent on rows whose finish returns it."""
        if not self._free:
            return False
        req = waiter.request
        record = req.record
        kv_reserved = self._reserve_kv(req.cache_len, req.remaining, record)
        if kv_reserved is None:
            return False
        slot = self._free.pop()
        slot.request = req
        req.kv_reserved = kv_reserved
        if record is not None and kv_reserved:
            record.note_kv(kv_reserved)
        self._apply_sampling(slot.index, waiter.sampler)
        if req.spec is not None:
            # a fresh request's context may draft where the current
            # cohort's could not — re-open the spec window
            self._spec_idle = 0
        if adapter_idx:
            self._lora_ids[slot.index] = adapter_idx
            self._lora_dirty = True
            self._lora_slots.add(slot.index)
        if waiter.penalty is not None:
            self._apply_penalty(slot.index, waiter.penalty)
        # cache/token writes happen under the lock: jax sequences them
        # after any in-flight chunk (their inputs are its outputs), so
        # the new request's first real decode lands in the next
        # dispatched chunk
        with phase(POOL_STATE_INSERT, record, start="t_state_insert",
                   end="t_state_inserted"):
            self.cache = self._write_slot(
                self.cache, waiter.row_cache, slot.index)
        self._last_tokens = self._write_token(
            self._last_tokens,
            jnp.asarray([[req.pending]], jnp.int32), slot.index,
        )
        self._active[slot.index] = slot
        if record is not None:
            # flight record: this request decodes pooled, alongside
            # len(_active)-1 co-tenants
            record.mark_pooled(len(self._active))
        if self._depth_gauge:
            self._depth_gauge.set(len(self._active))
        return True

    def _seat_waiters(self) -> None:
        """Seat who waits, in arrival order, while slots are free (pool
        lock held; the worker, before it dispatches): a row freed when
        chunk k is delivered is written here and rides chunk k+2, k+1
        being queued already. The head of the line keeps its place while
        the KV ledger cannot cover it. One the pool can no longer run
        beside its rows (an executable mix that arose while it waited) is
        refused as it would have been on arrival."""
        while self._waiters and self._free:
            waiter = self._waiters[0]
            try:
                if not self._seat(waiter, self._admit(
                        waiter.adapter, waiter.penalty,
                        waiter.request.record)):
                    return
            except queue.Full as exc:
                waiter.error = exc
            self._waiters.popleft()
            waiter.settled.set()

    def _await_seat(self, waiter: _Waiter) -> None:
        """The caller's side of the wait (no lock held): block until the
        worker settles the waiter, looking every ``_WAIT_POLL_S`` at what
        ends the wait without a seat."""
        req = waiter.request
        started = _perf_counter()
        try:
            with phase(POOL_SEAT_WAIT, req.record, start="t_seat_wait",
                       end="t_seated"):
                while not waiter.settled.wait(_WAIT_POLL_S):
                    self._leave_if_hopeless(waiter)
        finally:
            if self._seat_wait_hist is not None:
                self._seat_wait_hist.observe(
                    _perf_counter() - started, model=self._model)
        if waiter.error is not None:
            raise waiter.error

    def _leave_if_hopeless(self, waiter: _Waiter) -> None:
        """Take the waiter out of the line if its client has gone (its
        queue gets DONE) or its deadline can no longer cover a chunk
        (``_admit_deadline`` accounts and raises); the caller's thread."""
        req = waiter.request
        with self._work:
            if waiter.settled.is_set():
                return  # seated, or failed, while this thread took the lock
            if req.stop is None or not req.stop.is_set():
                try:
                    self._admit_deadline(req.deadline)
                except DeadlineExceeded:
                    self._waiters.remove(waiter)
                    raise
                return
            self._waiters.remove(waiter)
            req.out_queue.put(DONE)
            waiter.settled.set()

    def _fail_waiters(self) -> None:
        """The pool closed or died (pool lock held): whoever waits for a
        seat leaves as a submit to a closed pool does."""
        while self._waiters:
            waiter = self._waiters.popleft()
            self._reject("closed", count_only=True,
                         record=waiter.request.record)
            waiter.error = RuntimeError("decode pool closed")
            waiter.settled.set()

    @contextlib.contextmanager
    def gate(self, stop: Optional[threading.Event] = None) -> Any:
        """One of the pool's ``n_slots + standing_room`` places, held from
        before a request's prefill to its end: the requests past them
        wait HERE, in arrival order and with nothing on the device, so
        that no more than ``standing_room`` prefilled rows ever stand
        waiting for a seat. The wait gives up, and the request goes on
        without a place, when its ``stop`` is set, its deadline has run
        out or the pool is closed: the paths behind end such a request
        (the stream's own stop, the batcher's deadline shed, the closed
        pool's refusal)."""
        deadline = current_deadline()
        turn = threading.Event()  # set, under the pool lock, with the place
        with self._work:
            if self._places_free and not self._gate_line:
                self._places_free -= 1
                turn.set()
            else:
                self._gate_line.append(turn)
        while not turn.wait(_WAIT_POLL_S):
            if (
                self._closed
                or (stop is not None and stop.is_set())
                or (deadline is not None and deadline.expired())
            ):
                with self._work:
                    if not turn.is_set():  # else a place came meanwhile
                        self._gate_line.remove(turn)
                        break
        try:
            yield
        finally:
            if turn.is_set():
                with self._work:
                    if self._gate_line:  # to whoever has waited longest
                        self._gate_line.popleft().set()
                    else:
                        self._places_free += 1

    def _reserve_kv(self, start_len: int, max_new: int,
                    record: Any) -> Optional[int]:
        """Reserve the request's whole KV block budget (pool lock held):
        prompt + first token + every decode step it may take, capped at
        the cache bound — a LEDGER claim on the shared BlockPool (the
        bytes themselves live in this pool's slot cache; cached prefix
        blocks count as reclaimable against the same budget). None when
        the ledger cannot cover it while rows are pooled: their finish
        returns budget, so the request waits (the BlockPool counts the
        failed reservation, ``kv_exhausted_rejects``, which is what the
        fleet's prober reads as saturation). With no row pooled nothing
        here returns budget: that is the ``kv_exhausted`` refusal
        (distinct from the executable-mix ones), and the caller's solo
        fallback serves the request."""
        if self._kv is None:
            return 0
        from gofr_tpu.tpu.kv_blocks import KVExhausted

        try:
            return self._kv.reserve_ledger(
                min(start_len + 1 + max_new, self.max_len)
            )
        except KVExhausted as exc:
            if not self._active:
                self._reject(
                    "kv_exhausted", f"KV block budget exhausted: {exc}",
                    record=record,
                )
            return None

    def _admit_deadline(self, deadline: Any) -> None:
        """Deadline admission gate (pool lock held): a request whose
        remaining budget cannot cover even ONE decode chunk at the
        pool's observed cadence is hopeless — admitting it would burn a
        slot, KV blocks, and chunk dispatches on an answer that misses
        its deadline by construction. Unlike every other reject reason
        this does NOT fall back to solo decode (solo is slower, not
        faster): it raises the 504-mapped :class:`DeadlineExceeded`
        after accounting the ``deadline`` pool-reject reason and the
        ``admission`` stage counter."""
        if deadline is None:
            return
        remaining = deadline.remaining()
        if remaining > 0 and remaining >= self._chunk_ema_s:
            return
        # idle-pool bypass: with no rows decoding, the observed cadence
        # is STALE (one anomalous chunk — a GC pause, a host preemption
        # — would otherwise inflate the EMA, reject everything, and
        # never decay because rejections prevent the chunks that decay
        # it). An idle pool runs the chunk immediately; only a budget
        # that is already spent is hopeless there.
        if remaining > 0 and not self._active:
            return
        self._reject("deadline", count_only=True)
        if self._deadline_counter is not None:
            self._deadline_counter.inc(stage="admission")
        record = current_record()
        if record is not None:
            record.note_shed("admission")
        raise DeadlineExceeded(
            f"remaining deadline budget {max(remaining, 0) * 1000:.0f} ms "
            f"cannot cover one decode chunk (observed cadence "
            f"{self._chunk_ema_s * 1000:.0f} ms)", stage="admission",
        )

    def _admit(self, adapter: Optional[str], penalty: Optional[tuple],
               record: Any = None) -> int:
        """The submit reject gates (pool lock held): raises queue.Full
        via ``_reject`` on any executable-mix or readiness conflict.
        Returns the adapter's bank index (0 = base weights). ``record``
        is the request's when another thread asks for it (the worker, as
        it seats a request that waited)."""
        reject = functools.partial(self._reject, record=record)
        adapter_idx = 0
        if adapter is not None:
            if penalty is not None:
                reject(
                    "penalized_adapter",
                    "penalized adapter requests decode solo",
                )
            if not self._lora_ready:
                reject(
                    "bank_rebuilding", "adapter bank off or rebuilding"
                )
            if self._pen_slots:
                reject(
                    "penalized_mix",
                    "penalized slots active (one executable per chunk)",
                )
            idx = self._lora_index.get(adapter)
            if idx is None:
                reject(
                    "unknown_adapter",
                    f"adapter '{adapter}' not in the pool bank",
                )
            adapter_idx = idx
        if penalty is not None and self._lora_slots:
            reject(
                "adapter_mix",
                "adapter slots active (one executable per chunk)",
            )
        if penalty is not None and not self._pen_ready:
            if self._pen_mode == "lazy":
                self._pen_kick()
            reject(
                "penalties_off" if self._pen_mode == "off"
                else "penalties_warming",
                "penalized pool path "
                + ("disabled" if self._pen_mode == "off" else "warming"),
            )
        return adapter_idx

    def _apply_sampling(self, index: int, sampler: Any) -> None:
        """Write the slot's sampling knobs (pool lock held); dirties the
        device copies only when something actually changed."""
        if (
            self._temps[index] != sampler.temperature
            or self._top_ks[index] != sampler.top_k
            or self._top_ps[index] != sampler.top_p
            or self._min_ps[index] != sampler.min_p
        ):
            self._temps[index] = sampler.temperature
            self._top_ks[index] = sampler.top_k
            self._top_ps[index] = sampler.top_p
            self._min_ps[index] = sampler.min_p
            self._sampling_dirty = True

    def _apply_penalty(self, index: int, penalty: tuple) -> None:
        """Write a penalized request's rows/knobs into slot state (pool
        lock held)."""
        pres_row, cnt_row, bias_row, rep, pp, fp = penalty
        self._pres, self._cnts, self._bias = self._write_rows(
            self._pres, self._cnts, self._bias,
            pres_row, cnt_row.astype(jnp.float32),
            bias_row.astype(jnp.float32), index,
        )
        self._reps[index] = rep
        self._pps[index] = pp
        self._fps[index] = fp
        self._pen_dirty = True
        self._pen_slots.add(index)

    def _reject(self, reason: str, msg: str = "", count_only: bool = False,
                record: Any = None):
        """Account a submit rejection (counter + the request's flight
        record: the calling thread's, or ``record`` where the worker
        refuses a request that waited) and raise ``queue.Full`` unless
        ``count_only`` — the device's fallback path then decodes the
        request solo. A full pool is never a rejection: that request
        waits for a seat (``submit``)."""
        if self._reject_counter is not None:
            self._reject_counter.inc(reason=reason)
        record = record or current_record()
        if record is not None:
            record.note_pool_reject(reason)
        if not count_only:
            raise queue.Full(msg)

    # -- worker --------------------------------------------------------------
    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as exc:  # device/compile errors must not hang waiters
            self._abandon_in_flight()
            with self._work:
                self._closed = True
                self._fail_active(exc)

    def _abandon_in_flight(self) -> None:
        """The worker died: close every dispatch record it still had in
        flight as errored — a phantom 'running' decode chunk with
        ever-growing duration would misdirect the exact wedged-device
        diagnosis the timeline exists to provide."""
        self.chunks_in_flight = 0  # whatever was in flight is not fetched
        if self._timeline is None:
            return
        if self._pending_chunk_drec is not None:
            # the dispatch itself raised before its chunk ever reached
            # in_flight — same thread, read after the worker frame unwound
            self._timeline.finish(self._pending_chunk_drec, status="error")
            self._pending_chunk_drec = None
        for entry in list(self._in_flight_chunks):
            if entry[6] is not None:
                self._timeline.finish(entry[6], status="error")

    def _fail_active(self, exc: BaseException) -> None:
        self._fail_waiters()
        for slot in self._active.values():
            req = slot.request
            if req is not None and not req.finished and req.out_queue is not None:
                if req.journal is not None:
                    # stamp the interruption CAUSE before the waiter even
                    # re-raises: the journal entry is what the recovery
                    # resume path claims back
                    req.journal.note_interrupted(
                        f"decode pool failed: {type(exc).__name__}: {exc}"
                    )
                req.out_queue.put(PoolFailure(exc))
                req.out_queue.put(DONE)
                req.finished = True
            if req is not None and req.kv_reserved:
                # a dead pool must not pin KV budget against the prefix
                # cache and any future reinit
                self._kv.release_ledger(req.kv_reserved)
                req.kv_reserved = 0
            slot.request = None
        self._active.clear()
        self._free = list(reversed(self._slots))
        self._pen_slots.clear()
        self._lora_slots.clear()
        self._lora_ids[:] = 0
        self._lora_dirty = True
        if self._lora_pending:
            self._install_lora(*self._lora_pending)
        if self._sched is not None:
            self._sched.note_decode_idle()  # a dead pool must not gate prefill

    def _loop(self) -> None:
        in_flight: deque = deque()  # (records, toks_dev, ..., dispatch_start, drec)
        # worker-owned, but exposed so the _run failure path (same
        # thread, after this frame unwound) can close abandoned records
        self._in_flight_chunks = in_flight
        last_fetch_done: float = 0.0
        while True:
            with self._work:
                self._seat_waiters()
                while not self._active and not in_flight and not self._closed:
                    # the rows that come next make another chunk
                    self._run_samples.clear()
                    with phase(POOL_WAIT_WORK):  # parked: no live slot
                        self._work.wait()
                if self._closed:
                    # closing mid-stream is an ERROR for waiters, never a
                    # silently-truncated "ok" result; un-fetched chunks'
                    # records close too (a clean shutdown/reinit must not
                    # leave phantom "running" dispatches on the timeline)
                    self._abandon_in_flight()
                    self._fail_active(RuntimeError("decode pool closed mid-generation"))
                    return
                # spec cycles are depth-1 by construction (the host must
                # read the verify to roll back before the next dispatch)
                # and never overlap plain chunks in flight
                cycle = None
                spec_armed = self._spec_ready()
                if not in_flight and spec_armed:
                    cycle = self._spec_dispatch()
                    self._spec_idle = 0 if cycle is not None else (
                        self._spec_idle + 1
                    )
                if cycle is None:
                    # dispatch until the pipeline is full: chunk N+1's
                    # inputs are chunk N's output futures, so this never
                    # blocks. While a spec-armed cohort is PRODUCTIVE
                    # the depth clamps to 1 — a filled pipeline would
                    # never drain while rows stay active, so the spec
                    # window (in_flight empty) could never re-open;
                    # productive cohorts trade pipeline depth for
                    # multi-token dispatches by design. But a cohort
                    # whose drafts keep missing (free-form content the
                    # n-gram source cannot predict) gets its full
                    # pipeline back after a few dry rounds — losing
                    # BOTH speculation and pipelining forever was the
                    # worst of both worlds (a new submit re-opens the
                    # window: fresh context may draft).
                    # The LAST place is filled when the device is about
                    # to need it (``_hold``), a pool that closes meanwhile
                    # fails its rows at the top of the loop.
                    depth = (
                        1 if spec_armed and self._spec_idle < 4
                        else self.pipeline_depth
                    )
                    while self._active and len(in_flight) < depth:
                        if in_flight and len(in_flight) == depth - 1:
                            self._hold(in_flight[-1], last_fetch_done)
                            if self._closed:
                                break
                        self._dispatch_chunk(in_flight)
                    if self._closed:
                        continue
            if cycle is not None:
                last_fetch_done = self._spec_fetch_deliver(
                    cycle, last_fetch_done
                )
            elif in_flight:
                last_fetch_done = self._fetch_and_deliver(
                    in_flight, last_fetch_done
                )

    def _chunk_run_s(self) -> float:
        """A chunk's own device time, 0.0 while unknown: the SHORTEST of
        the last few clean delivery intervals, because the device cannot
        need the next chunk sooner than that after it began this one. A
        chunk's run moves by some 5% from one to the next (which experts
        its rows hit, a row gone), and the median made one held chunk in
        fifty find the device waiting (PERF.md section 6, PR 47); an
        interval cut short by a late fetch errs to the early side."""
        return min(self._run_samples, default=0.0)

    def _hold_terms(self) -> Optional[tuple]:
        """(a chunk's own device time, the lead the host is given), or
        None where the next chunk is issued at once: no clean interval
        since the pool last drained, or a chunk too short against the
        lead. The lead is the interpreter's switch interval, which a
        wake-up can lose to another thread whatever was observed, plus
        the peak of what the host has needed."""
        run_s = self._chunk_run_s()
        if not run_s:
            return None
        lead_s = sys.getswitchinterval() + self._lead_peak_s
        if run_s < _HOLD_MIN_RUNS_PER_LEAD * lead_s:
            return None
        return run_s, lead_s

    def _hold(self, running: tuple, last_fetch_done: float) -> None:
        """Hold the chunk that would queue behind ``running`` (pool lock
        held; released while waiting): until ``lead`` before the running
        chunk is due to end, which is ``run_s`` after it began, i.e. after
        the fetch before it returned or, with nothing ahead of it, after
        its own issue. The wait is on ``_work``, so a row seated
        meanwhile (``submit`` takes the lock) rides the held chunk;
        ``close`` ends it, and so does the scheduler for a prefill that
        is to go behind the chunk (``_release_hold``). Whatever the
        device runs meanwhile that the pool did not issue is ahead of the
        held chunk and behind the running one. No hold without an
        estimate, past the due time, or with the running chunk done."""
        terms = self._hold_terms()
        if terms is None:
            return
        run_s, lead_s = terms
        due = max(last_fetch_done, running[5]) + run_s - lead_s
        began = _perf_counter()
        if due <= began or running[1].is_ready():
            return
        self._hold_released = False
        self.holding = True
        if self._sched is not None:
            self._sched.note_hold(run_s, self._release_hold)
        try:
            with phase(POOL_HOLD):
                while not (self._closed or self._hold_released):
                    remaining = due - _perf_counter()
                    if remaining <= 0:
                        break
                    self._work.wait(remaining)
        finally:
            self.holding = False
        self._held = (began, None if self._hold_released else due)

    def _release_hold(self) -> None:
        """The scheduler's guard (a prefill thread, no lock of the
        scheduler's held): issue the held chunk now, the prefill that
        asks goes behind it."""
        with self._work:
            self._hold_released = True
            self._work.notify_all()

    def _note_issued(self, drec: Any, started: float, behind_busy: bool) -> None:
        """The lead's bookkeeping once a chunk is on the device's queue
        (pool lock held): what the host needed for it (a held chunk: from
        its due time, the wake-up's lateness included; any other: the
        issue alone) raises the peak at once and lets it down slowly, and
        a held chunk that found the running one done (the device waited
        for the host, or the chunk was shorter than ``run_s``) doubles it."""
        now = _perf_counter()
        held, self._held = self._held, None
        needed_s = now - started
        if held is not None:
            began, due = held
            self.held_issues += 1
            if drec is not None:
                drec.held_s = started - began
            if due is not None:
                needed_s = now - due
                if drec is not None:
                    drec.held_late_s = needed_s
                if not behind_busy:
                    self.held_issues_late += 1
                    needed_s = max(needed_s, 2.0 * self._lead_peak_s)
        self._lead_peak_s = max(
            needed_s, self._lead_peak_s + 0.1 * (needed_s - self._lead_peak_s))

    def _note_interval(self, interval_s: float, admitted: list) -> None:
        """A delivery interval with the device busy throughout (pool lock
        held): with no prefill admitted between the two issues
        it is a reading of a chunk's own device time, else what it holds
        beyond a chunk is the scheduler's reading of a prefill's run."""
        if not admitted:
            self._run_samples.append(interval_s)
        elif self._run_samples and self._sched is not None:
            self._sched.note_interval(
                admitted, interval_s - self._chunk_run_s())

    def _dispatch_chunk(self, in_flight: deque) -> None:
        """Dispatch ONE pipelined chunk (pool lock held): timeline
        record, device dispatch through whichever executable the active
        slot mix selects, early D2H copy kickoff, in-flight append."""
        records = [
            (slot.index, slot.request) for slot in self._active.values()
        ]
        if self._sampling_dirty:
            self._temps_dev = jnp.asarray(self._temps)
            self._top_ks_dev = jnp.asarray(self._top_ks)
            self._top_ps_dev = jnp.asarray(self._top_ps)
            self._min_ps_dev = jnp.asarray(self._min_ps)
            self._sampling_dirty = False
        self._sync_live()
        drec = None
        if self._timeline is not None:
            # dispatch timeline: one record per chunk; every active
            # request's FlightRecord learns the id (its own cap bounds
            # the growth)
            drec = self._timeline.begin(
                "decode_chunk", batch_size=len(records),
            )
            drec.mark_running()
            drec.chunks_ahead = self.chunks_in_flight  # depth reached
            if self._state_row_bytes:
                drec.state_bytes = (
                    len(records) * 2 * self._state_row_bytes * self.chunk
                )
            for _, req in records:
                if req is not None and req.record is not None:
                    req.record.note_dispatch_id(drec.dispatch_id)
            # a dispatch-side raise before the append below must not
            # leak this record as running forever
            self._pending_chunk_drec = drec
        dispatch_start = _perf_counter()
        with phase(POOL_ISSUE, drec, end="t_issued"):
            toks_dev, lps_dev, tvals_dev, tids_dev = self._run_executable(
                records
            )
        # start the D2H copy NOW: the transfer begins the moment the
        # chunk's compute finishes, so the blocking fetch later waits on
        # an already-in-flight copy and the per-chunk fetches OVERLAP
        # across the pipeline instead of serializing. top-k alternatives
        # are fetched only when some active request asked for
        # ALTERNATIVES (the executables always compute
        # them; fetching is the opt-in part — plain logprobs requests
        # stay at the scalar-per-token fetch)
        want_top = any(
            req is not None and req.want_top for _, req in records
        )
        if not want_top:
            tvals_dev = tids_dev = None
        toks_dev.copy_to_host_async()
        lps_dev.copy_to_host_async()
        if want_top:
            tvals_dev.copy_to_host_async()
            tids_dev.copy_to_host_async()
        # was the chunk before this one still running when this one
        # reached the device's queue: then the device goes from one to the
        # other (and to whatever was issued between them) without a gap,
        # and the interval between their deliveries is device time
        behind_busy = bool(in_flight) and not in_flight[-1][1].is_ready()
        self._note_issued(drec, dispatch_start, behind_busy)
        # decode keeps its cadence; prefill chunks take the gaps between
        # these notes, and the note says which were admitted in this one
        admitted = (
            self._sched.note_decode_chunk(len(records))
            if self._sched is not None else None
        )
        in_flight.append(
            (records, toks_dev, lps_dev, tvals_dev, tids_dev,
             dispatch_start, drec, admitted or [], behind_busy)
        )
        self._pending_chunk_drec = None  # owned by in_flight now
        self.chunks_in_flight += 1

    def _sync_live(self) -> None:
        """Keep ``cache["live"]`` to the active slots (pool lock held): a
        step reads no K/V and moves no state for a slot that holds no
        request (ops/flash.py, ops/retention.py)."""
        live = tuple(int(i in self._active) for i in range(self.n_slots))
        if live != self._live_mask:
            self._live_mask = live
            self.cache = {**self.cache, **self._place(
                {"live": jnp.asarray(live, jnp.int32)})}

    # -- pooled speculative decoding (spec cycles) ----------------------------
    def _build_spec_exec(self, cfg: Any, cache_shardings: Any,
                         repl: Any) -> None:
        """Build the spec-cycle executables (constructor helper): a
        spec cycle verifies [n_slots, width] candidate tokens (each
        row's pending token + its drafts) in ONE target dispatch —
        verify_chunk is already batch-generic and reads each row's
        write offset from the cache lengths, so the pool reuses the
        solo path's executable at pool shapes. Rejected tokens roll
        back by LENGTH (_write_lengths): garbage KV past a row's
        committed length is masked by attention and overwritten by
        later steps — the same convention stale slot rows already
        ride."""
        from gofr_tpu.models.transformer import verify_chunk

        self._verify_pool = jax.jit(
            lambda p, t, c: verify_chunk(p, t, c, cfg),
            donate_argnums=(2,),
            out_shardings=(
                (repl, dict(cache_shardings))
                if repl is not None else None
            ),
        )
        self._write_lengths = jax.jit(
            lambda c, l: {**c, "lengths": l},
            donate_argnums=(0,),
            out_shardings=(
                dict(cache_shardings) if repl is not None else None
            ),
        )

    def _warm_spec(self) -> None:
        """Warm EVERY verify width the cohort ladder can produce plus
        the lengths rollback — a spec cycle must never compile on the
        serving path. The cache is donated through each warm and reset
        by the constructor like the plain warmup's writes; tokens are
        host-built exactly like a serving-path cycle (jit reshards
        under a mesh; warm placement must match serve placement or the
        first cycle recompiles)."""
        from gofr_tpu.tpu.batcher import verify_width_ladder

        for w in verify_width_ladder(self.spec_cfg.k_max):
            ids, self.cache = self._verify_pool(
                self.params,
                jnp.asarray(np.zeros((self.n_slots, w), np.int32)),
                self.cache,
            )
            ids.block_until_ready()
        self.cache = self._write_lengths(
            self.cache, jnp.asarray(np.zeros(self.n_slots, np.int32))
        )
        self.cache["lengths"].block_until_ready()

    def _spec_arm(self, spec_ctx: Any, first_token: int, sampler: Any,
                  penalty: Any, adapter: Any, want_logprobs: bool,
                  want_top_logprobs: bool) -> Any:
        """Build a request's draft state when pooled speculation is on
        and the request is eligible — greedy, unpenalized, base
        weights, no logprobs (the verify executable computes argmaxes,
        not logprob rows). Called OUTSIDE the pool lock (it copies the
        prompt into the draft context)."""
        if (
            self.spec_cfg is None or spec_ctx is None
            or penalty is not None or adapter is not None
            or want_logprobs or want_top_logprobs
            or not getattr(sampler, "greedy", False)
        ):
            return None
        return self.spec_cfg.new_state(
            [int(t) for t in spec_ctx], first_token
        )

    def _spec_ready(self) -> bool:
        """Spec cycles run only while EVERY active row is spec-armed
        (pool lock held): one executable per dispatch is the pool's
        standing contract, and a sampled/penalized/adapter co-tenant
        needs the plain chunk — mixed cohorts decode plain, spec rows
        keep their draft context coherent via note_plain."""
        if self.spec_cfg is None or not self._active:
            return False
        if self._pen_slots or self._lora_slots:
            return False
        return all(
            slot.request is not None and slot.request.spec is not None
            for slot in self._active.values()
        )

    def _spec_dispatch(self) -> Optional[tuple]:
        """Draft + dispatch ONE batched verify (pool lock held): every
        active row proposes up to its adaptive k draft tokens (brownout
        and deadline clamped), the widths cohort onto the pow2 ladder,
        and the target verifies all rows' pending+draft tokens in one
        [n_slots, width] dispatch. Returns the in-flight cycle tuple, or
        None when no row drafted anything — the plain pipelined chunk is
        strictly better then (more steps per dispatch, no rollback)."""
        from gofr_tpu.deadline import clamp_spec_k
        from gofr_tpu.tpu.batcher import verify_width

        cfg = self.spec_cfg
        level = cfg.level()
        records = [
            (slot.index, slot.request) for slot in self._active.values()
        ]
        drafts: dict[int, list] = {}
        max_k = 0
        for index, req in records:
            k = clamp_spec_k(
                req.spec.adaptive.current(), level, req.deadline,
                self._chunk_ema_s,
            )
            # room for the drafts + bonus inside the request's token
            # budget and its cache row
            k = min(k, req.remaining - 1, self.max_len - req.cache_len - 1)
            d = req.spec.propose(k) if k > 0 else []
            drafts[index] = d
            max_k = max(max_k, len(d))
        if max_k == 0:
            return None
        width = verify_width(max_k, cfg.k_max)
        tokens = np.zeros((self.n_slots, width), np.int32)
        for index, req in records:
            tokens[index, 0] = req.pending
            row = drafts[index]
            tokens[index, 1 : 1 + len(row)] = row
        drec = None
        if self._timeline is not None:
            drec = self._timeline.begin(
                "spec_verify", batch_size=len(records), tokens=width,
            )
            drec.mark_running()
            for _, req in records:
                if req.record is not None:
                    req.record.note_dispatch_id(drec.dispatch_id)
            self._pending_chunk_drec = drec
        self._sync_live()
        dispatch_start = _perf_counter()
        next_dev, self.cache = self._verify_pool(
            self.params, jnp.asarray(tokens), self.cache
        )
        next_dev.copy_to_host_async()
        self._pending_chunk_drec = None
        self.chunks_in_flight += 1
        if self._sched is not None:
            self._sched.note_decode_chunk(len(records))
        return records, drafts, next_dev, width, dispatch_start, drec

    def _spec_fetch_deliver(
        self, cycle: tuple, last_fetch_done: float
    ) -> float:
        """Fetch one spec verify outside the lock (watchdogged exactly
        like a plain chunk fetch), then deliver + roll back under it."""
        records, drafts, next_dev, width, dispatch_start, drec = cycle
        watch = (
            self._watchdog.watch(
                "spec_verify", drec.dispatch_id if drec else 0
            )
            if self._watchdog is not None else contextlib.nullcontext()
        )
        try:
            with watch:
                next_ids = np.asarray(next_dev)
            self.chunks_in_flight -= 1
            fetch_done = _perf_counter()
            # depth-1 dispatch: the span IS the inter-delivery interval
            elapsed = fetch_done - max(dispatch_start, last_fetch_done)
            with self._work:
                self._spec_deliver(records, drafts, next_ids, width,
                                   elapsed, drec)
        except BaseException:
            if self._timeline is not None and drec is not None:
                self._timeline.finish(drec, status="error")
            raise
        if self._timeline is not None and drec is not None:
            self._timeline.finish(drec)
        return fetch_done

    def _spec_deliver(
        self, records: list, drafts: dict, next_ids: np.ndarray,
        width: int, elapsed: float, drec: Any,
    ) -> None:
        """Acceptance + rollback for one fetched verify (pool lock
        held): per row, the longest draft prefix matching the target's
        argmaxes commits (plus the bonus token — the target's own
        continuation, so output never depends on draft quality); the
        rejected tail rolls back by writing every row's committed
        length back into the cache lengths vector (one dispatch), and
        the pending-token vector is rebuilt host-side so the next
        dispatch — spec or plain — feeds forward correctly."""
        if elapsed > 0:
            self._chunk_ema_s = (
                elapsed if self._chunk_ema_s <= 0
                else 0.8 * self._chunk_ema_s + 0.2 * elapsed
            )
        delivered_total = drafted_total = accepted_total = 0
        for index, req in records:
            if req is None or req.finished:
                continue
            d = drafts[index]
            row = next_ids[index]
            n_acc = 0
            while n_acc < len(d) and d[n_acc] == int(row[n_acc]):
                n_acc += 1
            burst = [int(row[j]) for j in range(n_acc + 1)]
            delivered = self._spec_deliver_one(index, req, burst, n_acc,
                                               len(d))
            delivered_total += delivered
            drafted_total += len(d)
            accepted_total += min(n_acc, len(d))
        lengths = np.zeros(self.n_slots, np.int32)
        pendings = np.zeros((self.n_slots, 1), np.int32)
        for index, slot in self._active.items():
            req = slot.request
            if req is not None:
                lengths[index] = req.cache_len
                pendings[index, 0] = req.pending
        # ONE rollback dispatch: garbage KV past each row's committed
        # length is dead (attention masks it; later steps overwrite it)
        self.cache = self._write_lengths(self.cache, jnp.asarray(lengths))
        self._last_tokens = self._replicate(jnp.asarray(pendings))
        if self._sched is not None and not self._active:
            self._sched.note_decode_idle()
        if self._depth_gauge:
            self._depth_gauge.set(len(self._active))
        if drec is not None:
            drec.tokens = delivered_total
        self._account_chunk(delivered_total)
        # per-ROW semantics on the shared gauge: one verify serves
        # len(records) rows, and the echo mirror publishes per-request
        # values — dividing keeps "1.0 = plain decode" true for both
        # producers (batch totals would read cohort size as spec win)
        self.spec_cfg.note_cycle(
            drafted_total, accepted_total, delivered_total,
            dispatches=len(records),
        )

    def _spec_deliver_one(self, index: int, req: "_Request", burst: list,
                          n_acc: int, drafted: int) -> int:
        """One row's share of a verify cycle (pool lock held): burst
        put (stop-token truncated), cache/budget bookkeeping, draft
        state commit, terminal finish — the spec mirror of
        _deliver_one. Returns the tokens actually delivered."""
        cancelled = req.stop is not None and req.stop.is_set()
        expired = (
            not cancelled
            and req.deadline is not None and req.deadline.expired()
        )
        hit_stop_token = False
        emit: list = []
        if not cancelled and not expired and req.out_queue is not None:
            for t in burst:
                if t in req.stop_tokens:
                    hit_stop_token = True
                    break
                emit.append(t)
            if emit:
                if req.record is not None:
                    req.record.note_delivered(len(emit))
                req.out_queue.put(list(emit))
        # committed tokens: everything emitted (the stop token itself is
        # never emitted nor committed — the request ends at it)
        committed = len(emit)
        req.cache_len += committed
        req.remaining -= committed
        req.spec.commit(emit, drafted, n_acc)
        req.pending = req.spec.pending
        if req.record is not None:
            req.record.note_spec(drafted, n_acc, len(emit))
        if (
            cancelled
            or expired
            or hit_stop_token
            or req.remaining <= 0
            or req.cache_len >= self.max_len
        ):
            if expired:
                self._account_expiry(req)
            self._finish_request(index, req, cancelled, expired=expired)
        return len(emit)

    def _account_expiry(self, req: "_Request") -> None:
        """Deadline-expiry accounting for a finishing row (pool lock
        held) — one home for the plain-chunk and spec-cycle deliver
        paths, so the stage/cause/journal semantics cannot drift."""
        if self._deadline_counter is not None:
            self._deadline_counter.inc(stage="decode")
        if self._cancel_counter is not None:
            self._cancel_counter.inc(cause="deadline")
        if req.record is not None:
            req.record.note_shed("decode")
        if req.journal is not None:
            req.journal.note_interrupted("deadline exceeded mid-decode")

    def _run_executable(self, records: list) -> tuple:
        """ONE device dispatch (pool lock held): RNG advance and the
        feed-forward token slice happen inside the jitted chunk. The
        penalized executable runs only while a penalized slot is active
        — penalty-free traffic keeps the plain one."""
        if self._lora_slots:
            if self._lora_dirty:
                self._lora_ids_dev = jnp.asarray(self._lora_ids)
                self._lora_dirty = False
            self.lora_chunks += 1
            (toks_dev, lps_dev, tvals_dev, tids_dev,
             self._last_tokens, self._key,
             self.cache) = self._decode_lora(
                self._lora_params, self._lora_ids_dev,
                self._last_tokens, self.cache, self._key,
                self._temps_dev, self._top_ks_dev,
                self._top_ps_dev, self._min_ps_dev,
            )
        elif self._pen_slots:
            if self._pen_dirty:
                self._reps_dev = jnp.asarray(self._reps)
                self._pps_dev = jnp.asarray(self._pps)
                self._fps_dev = jnp.asarray(self._fps)
                self._pen_dirty = False
            (toks_dev, lps_dev, tvals_dev, tids_dev,
             self._last_tokens, self._key, self.cache,
             self._pres, self._cnts) = self._decode_pen(
                self.params, self._last_tokens, self.cache,
                self._key, self._temps_dev, self._top_ks_dev,
                self._top_ps_dev, self._min_ps_dev, self._pres,
                self._reps_dev, self._cnts, self._pps_dev,
                self._fps_dev, self._bias,
            )
        else:
            (toks_dev, lps_dev, tvals_dev, tids_dev,
             self._last_tokens, self._key,
             self.cache) = self._decode(
                self.params, self._last_tokens, self.cache, self._key,
                self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                self._min_ps_dev,
            )
        return toks_dev, lps_dev, tvals_dev, tids_dev

    def _fetch_and_deliver(
        self, in_flight: deque, last_fetch_done: float
    ) -> float:
        """Fetch the OLDEST chunk outside the lock (the device is
        meanwhile executing the younger in-flight chunk(s), and new
        submissions can take the lock to join the next dispatch), then
        deliver its tokens. Returns the fetch-completion mark the next
        call uses as its throughput-denominator anchor."""
        from gofr_tpu.models.transformer import unpack_expert_counts

        (records, toks_dev, lps_dev, tvals_dev, tids_dev,
         dispatch_start, drec, admitted, behind_busy) = in_flight.popleft()
        # the blocking host fetch is WHERE a wedged device manifests:
        # it runs under the stall watchdog's deadline so a hang flips
        # the engine state instead of silently parking this worker
        watch = (
            self._watchdog.watch(
                "decode_chunk", drec.dispatch_id if drec else 0
            )
            if self._watchdog is not None else contextlib.nullcontext()
        )
        try:
            # fetch wait: the host blocked on this chunk — the device
            # queue ahead of it, its compute, the D2H copy
            with watch, phase(
                POOL_FETCH_WAIT, drec, start="t_fetch", end="t_fetched"
            ):
                # an expert model's routing counts ride behind the rows
                toks, routing = unpack_expert_counts(
                    np.asarray(toks_dev), self.n_slots,
                    getattr(self.cfg, "routing_width", 0))
                if drec is not None and routing is not None:
                    drec.note_routing(routing, self.cfg.n_experts, self.cfg.top_k,
                                      self.cfg.n_shared_experts)
                lps = np.asarray(lps_dev)
                tvals = (
                    np.asarray(tvals_dev) if tvals_dev is not None else None
                )
                tids = (
                    np.asarray(tids_dev) if tids_dev is not None else None
                )
            self.chunks_in_flight -= 1
            fetch_done = _perf_counter()
            # the cadence: the interval between consecutive deliveries
            # at steady state (dispatch->fetch spans depth x chunk
            # computes when the pipeline is full); after an idle gap,
            # fall back to this chunk's own span. Floor at span/depth: a
            # host stall can make every in-flight chunk finish before
            # the next fetch, shrinking the inter-delivery gap to ~0.
            span = fetch_done - dispatch_start
            dispatch_elapsed = max(
                fetch_done - max(dispatch_start, last_fetch_done),
                span / self.pipeline_depth,
            )
            if drec is not None:
                # the interval between deliveries: one chunk's device
                # time while the pipeline is full, plus whatever else
                # the device ran in between (a prefill, solo chunks)
                drec.cadence_s = dispatch_elapsed
            with phase(POOL_DELIVER, drec), self._work:
                if behind_busy and last_fetch_done:
                    # the fetch before this one was of the chunk before it
                    self._note_interval(fetch_done - last_fetch_done, admitted)
                self._deliver(records, toks, lps, tvals, tids,
                              dispatch_elapsed, drec)
        except BaseException:
            # the chunk was already popped from in_flight: close its
            # record here (the worker's failure path sweeps the rest)
            if self._timeline is not None and drec is not None:
                self._timeline.finish(drec, status="error")
            raise
        if self._timeline is not None and drec is not None:
            self._timeline.finish(drec)
        return fetch_done

    def _deliver(self, records: list, toks: np.ndarray, lps: np.ndarray,
                 tvals: Any, tids: Any, elapsed: float,
                 drec: Any = None) -> None:
        # observed cadence EMA (pool lock held): the steady-state
        # inter-delivery interval — what one more chunk of decode
        # actually costs a deadline right now
        if elapsed > 0:
            self._chunk_ema_s = (
                elapsed if self._chunk_ema_s <= 0
                else 0.8 * self._chunk_ema_s + 0.2 * elapsed
            )
        if drec is not None and self._latent_token_bytes:
            drec.latent_bytes = self._latent_token_bytes * sum(
                min(req.cache_len + step + 1, self.max_len)
                for _, req in records if req is not None for step in range(self.chunk))
        if drec is not None and self._kv_block:
            drec.kv_blocks_read = self._kv_blocks_read(records)
            drec.kv_blocks_held = (
                self.chunk * self.n_slots * (self.max_len // self._kv_block)
            )
        delivered = 0
        for index, req in records:
            if req is None or req.finished:
                continue  # freed mid-pipeline; this chunk's row is garbage
            delivered += self._deliver_one(index, req, toks, lps, tvals, tids)
        if self._sched is not None and not self._active:
            self._sched.note_decode_idle()  # release any waiting prefill
        if self._depth_gauge:
            self._depth_gauge.set(len(self._active))
        if drec is not None:
            drec.tokens = delivered
        self._account_chunk(delivered)

    def _kv_blocks_read(self, records: list) -> int:
        """Blocks of K/V the fetched chunk's attention had to read a layer
        (pool lock held, before the rows' lengths move on): each step of
        each row that rode it reads up to the row's length, the token
        of that step included."""
        return sum(
            -(-min(req.cache_len + step + 1, self.max_len) // self._kv_block)
            for _, req in records if req is not None
            for step in range(self.chunk)
        )

    def _deliver_one(self, index: int, req: "_Request", toks: np.ndarray,
                     lps: np.ndarray, tvals: Any, tids: Any) -> int:
        """Deliver one request's share of a fetched chunk (pool lock
        held): burst put, bookkeeping, terminal finish when the request
        cancelled, hit a stop token, or ran out of budget/cache.
        Returns the tokens actually put on the request's queue."""
        room = self.max_len - req.cache_len  # valid steps this chunk
        req.cache_len += self.chunk
        take = min(self.chunk, req.remaining, max(room, 0))
        cancelled = req.stop is not None and req.stop.is_set()
        # per-chunk deadline check: an expired row finishes NOW —
        # status deadline_exceeded to the waiter, slot + KV released
        # mid-flight exactly like the cancellation path, so a queued
        # request admits into the freed budget within one chunk
        expired = (
            not cancelled
            and req.deadline is not None and req.deadline.expired()
        )
        hit_stop_token = False
        delivered = 0
        if not cancelled and not expired and req.out_queue is not None:
            burst, hit_stop_token = self._build_burst(
                req, index, toks[index], lps[index], tvals, tids, take
            )
            if burst:
                if req.record is not None:
                    # from here the tokens are the stream's to send:
                    # each one's frame waits from this stamp
                    req.record.note_delivered(len(burst))
                req.out_queue.put(burst)
                delivered = len(burst)  # only tokens a request received
            if req.spec is not None:
                # a spec-armed row rode a plain chunk (mixed cohort /
                # no-draft cycle): keep its draft context and pending
                # token coherent so the next spec cycle drafts from the
                # real stream. A continuing row always consumed the full
                # chunk (shorter takes finish below), so the last
                # delivered token IS the device's feed-forward token.
                req.spec.note_plain(burst)
                req.pending = req.spec.pending
                if req.record is not None:
                    # the chunk streamed weights once per scan step:
                    # plain chunks ridden while spec-armed count at
                    # ~1.0 tokens/stream, so the request's
                    # tokens_per_dispatch reflects its REAL mix, not
                    # just its verify cycles
                    req.record.note_spec(
                        0, 0, delivered, dispatches=self.chunk
                    )
        req.remaining -= take
        if (
            cancelled
            or expired
            or hit_stop_token
            or req.remaining <= 0
            or req.cache_len >= self.max_len
        ):
            if expired:
                self._account_expiry(req)
            self._finish_request(index, req, cancelled, expired=expired)
        return delivered

    def _account_chunk(self, delivered: int) -> None:
        """Count one delivered chunk's useful tokens (pool lock held):
        only tokens put on request queues, not garbage rows, cancelled
        requests or discarded chunk tails."""
        if delivered and self._tokens_counter is not None:
            self._tokens_counter.inc(delivered, model=self._model, op="decode")

    def _build_burst(
        self, req: "_Request", index: int, emitted: Any, emitted_lps: Any,
        tvals: Any, tids: Any, take: int,
    ) -> tuple:
        """ONE queue put per chunk (a burst list), not one per token:
        per-token puts wake the consuming request thread up to chunk
        times per dispatch, and that GIL churn is on the worker's
        critical path between dispatches. Returns (burst,
        hit_stop_token) — a stop token ends the stream and is not
        emitted."""
        burst: list = []
        for j, t in enumerate(emitted[:take]):
            if int(t) in req.stop_tokens:
                return burst, True
            if req.want_lp:
                # (token, lp, tops|None): tops only for requests that
                # asked for alternatives — building 5 tuples per token
                # sits on the worker's critical path
                tops = None
                if req.want_top:
                    tops = [
                        (int(tids[index, j, m]), float(tvals[index, j, m]))
                        for m in range(tids.shape[-1])
                    ]
                burst.append((int(t), float(emitted_lps[j]), tops))
            else:
                burst.append(int(t))
        return burst, False

    def _finish_request(self, index: int, req: "_Request",
                        cancelled: bool, expired: bool = False) -> None:
        """Terminal delivery for one request (pool lock held): optional
        KV hand-back, DONE (preceded by the DEADLINE marker for an
        expired row), and — unless the slot was already reused —
        freeing it with every per-slot state reset (sampling knobs,
        adapter id, penalty rows)."""
        req.finished = True
        if (
            req.want_kv and not cancelled and not expired
            and req.out_queue is not None
            and self._slots[index].request is req
        ):
            # hand the slot's KV row back before DONE so the
            # device can seed its prefix cache with the WHOLE
            # conversation. Enqueued under the pool lock: the
            # copy is ordered before any later dispatch donates
            # the cache, and before any write_slot reuses the
            # row — the prefix positions it reads are final.
            # (Lockstep garbage decode only APPENDS past the
            # request's length; the device rolls the copy back.)
            req.out_queue.put(
                ("kv", self._read_slot(self.cache, index))
            )
        if req.out_queue is not None:
            if expired:
                # the waiter must re-raise DeadlineExceeded, not treat
                # the truncated stream as a clean early finish
                req.out_queue.put(DEADLINE)
            req.out_queue.put(DONE)
        req.out_queue = None
        req.stop = None
        if req.kv_reserved:
            # free the KV reservation NOW (not at slot reuse): the
            # budget is back on the shared ledger before this delivery
            # even returns, so a request waiting on kv_exhausted admits
            # mid-flight — continuous batching at block granularity
            self._kv.release_ledger(req.kv_reserved)
            req.kv_reserved = 0
        slot = self._slots[index]
        if slot.request is req:  # not already reused
            slot.request = None
            del self._active[index]
            self._free.append(slot)
            self._reset_slot(index)

    def _reset_slot(self, index: int) -> None:
        """Reset a freed slot's per-slot state (pool lock held):
        sampling knobs, adapter id, penalty knobs + bias row."""
        # reset the slot's sampling knobs to greedy: one past
        # sampled request must not keep jnp.all(temps <= 0)
        # false forever and defeat the all-greedy fast path in
        # sample_logits_rows (a full-vocab sort per step)
        if (
            self._temps[index] != 0.0
            or self._top_ks[index] != 0
            or self._top_ps[index] != 1.0
            or self._min_ps[index] != 0.0
        ):
            self._temps[index] = 0.0
            self._top_ks[index] = 0
            self._top_ps[index] = 1.0
            self._min_ps[index] = 0.0
            self._sampling_dirty = True
        if index in self._lora_slots:
            # the freed slot must stop selecting the adapter:
            # a plain request reusing it under the adapter
            # executable gathers bank entry 0 (exact zero
            # delta = base numerics)
            self._lora_slots.discard(index)
            self._lora_ids[index] = 0
            self._lora_dirty = True
            if self._lora_pending and not self._lora_slots:
                # a bank rebuild waited for these slots
                self._install_lora(*self._lora_pending)
        if index in self._pen_slots:
            # identity knobs: a plain request reusing the slot
            # under the penalized executable must sample
            # exactly like the plain one. Presence/counts need
            # no reset — identity knobs neutralize them (and
            # lockstep garbage decode re-dirties them anyway);
            # the bias row is written only at submit and
            # applied unconditionally, so IT must be zeroed.
            self._pen_slots.discard(index)
            self._reps[index] = 1.0
            self._pps[index] = 0.0
            self._fps[index] = 0.0
            self._pen_dirty = True
            self._bias = self._zero_bias(self._bias, index)

    def occupancy(self) -> dict:
        """Point-in-time slot occupancy for ``GET /admin/engine``."""
        guard = getattr(self._sched, "stats", {})
        with self._work:
            return {
                "slots": self.n_slots,
                "active": len(self._active),
                "free": len(self._free),
                # prefilled requests waiting for a seat (a full pool)
                "waiting": len(self._waiters),
                "chunk": self.chunk,
                "pipeline_depth": self.pipeline_depth,
                "lora_slots": len(self._lora_slots),
                "penalized_slots": len(self._pen_slots),
                "closed": self._closed,
                "mesh_axes": self.mesh_axes,
                # the deadline admission gate's unit: what one more
                # chunk of decode costs right now (0 = not yet observed)
                "chunk_cadence_s": self._chunk_ema_s,
                # the hold (``_hold``): a chunk's own device time and the
                # lead the host is given (0 / the floor before a reading),
                # chunks that were held and those of them that came late,
                # and the scheduler's two answers to a prefill admitted
                # during a hold
                "chunk_run_s": self._chunk_run_s(),
                "issue_lead_s": sys.getswitchinterval() + self._lead_peak_s,
                "held_issues": self.held_issues,
                "held_issues_late": self.held_issues_late,
                "prefills_ahead_of_held": guard.get("prefills_ahead_of_held", 0),
                "prefills_kept_behind": guard.get("prefills_kept_behind", 0),
                "kv": self._kv.stats() if self._kv is not None else None,
                # pooled speculative decoding: armed + its width bound
                # (per-request accept/width state lives on the flight
                # records and the spec gauges)
                "spec": (
                    {"k_max": self.spec_cfg.k_max,
                     "ngram": self.spec_cfg.ngram}
                    if self.spec_cfg is not None else None
                ),
            }

    def close(self) -> None:
        with self._work:
            self._closed = True
            # the worker fails them too as it leaves; a worker that has
            # died already cannot
            self._fail_waiters()
            self._work.notify_all()
        self._thread.join(timeout=5)
