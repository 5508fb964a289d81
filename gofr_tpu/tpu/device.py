"""TPU device datasource: model loading, compiled entry points, dynamic
batching, health, query logging, metrics.

Config keys (SURVEY.md §2 #22 TPU-native additions):
- ``MODEL_NAME``: mlp | bert-tiny | bert-base | tiny | small | llama3-8b |
  llama3-70b | zaya1-8b (transformer names from
  gofr_tpu.models.llama.CONFIGS; the entry states the attention kind and
  the feed-forward kind, no setting does)
- ``MODEL_PATH``: optional checkpoint — an HF safetensors file/dir (routed
  through models/ingest.py) or an orbax dir (absent -> seeded init)
- ``MODEL_QUANT``: "int8" (per-channel) or "int4" (group-wise scales) for
  weight-only quantized serving — decode streams the whole weight set per
  step, so packed weights raise its throughput ceiling 2x / ~4x over bf16
  (refused for a model of routed experts: the quantiser does not take
  expert-stacked leaves)
- ``MODEL_KV_DTYPE``: "f8" stores the KV cache in float8_e4m3fn (2x
  context length or decode slots per HBM byte, small accuracy cost); a
  model whose cache is a retention state holds it in float32 when unset
  and in bfloat16 with "bf16", and refuses f8
- ``MODEL_ATTN_IMPL``: auto (default) | xla | pallas — forces the
  attention implementation (ops/attention.py); "auto" picks the Pallas
  flash kernel when shapes are tile-friendly and profitable
- ``MODEL_BUCKETS``: comma-separated sequence buckets to compile at boot
  (default: the SEQ_BUCKETS ladder up to max_seq). Prompts longer than
  the largest bucket prefill CHUNKED through it on the generate path
  (full context from one small compiled shape; fast cold boot) — the
  batched /infer path keeps the recency clip
- ``DRAFT_MODEL_NAME`` / ``DRAFT_TOKENS`` / ``DRAFT_MODEL_PATH``:
  greedy speculative decoding — a small same-vocab draft model proposes
  DRAFT_TOKENS tokens per cycle and the target verifies them in one
  forward (output bit-identical to plain greedy; latency mode, so greedy
  requests bypass the continuous-batching pool)
- ``SPEC_POOLED`` / ``SPEC_NGRAM`` / ``SPEC_K_MAX`` /
  ``SPEC_FAKE_ACCEPT``: POOLED speculative decoding (tpu/spec_pool.py)
  — speculation through the continuous-batching pool with zero-weight
  n-gram drafting, batched multi-row verify, length/refcount rollback,
  and a per-request adaptive-k controller (brownout + deadline
  clamped); pooled-spec output stays bit-identical to plain pooled
  decode. When both SPEC_POOLED and DRAFT_MODEL_NAME are set, the
  pooled mode wins for pool-eligible requests
- ``LORA_ADAPTERS``: "name=path,..." named LoRA adapter artifacts
  (models/lora.py::export_adapter, orbax-saved) served over the shared
  base; requests select one via generate(adapter=...). Adapter requests
  prefill solo but DECODE IN THE SHARED POOL via a stacked adapter bank
  (per-slot selection); they fall back to solo decode under a serving
  mesh, for rank/target-mismatched adapter sets, or mid bank rebuild
- ``PREFIX_CACHE``: keep the KV rows of the n most recent distinct
  prompts/conversations — an exact repeat (retries) skips prefill
  entirely on the generate path; a prompt sharing a long-enough common
  prefix with a cached entry (shared system prompt, differing user turn)
  resumes from its KV and prefills only the tail; and completed
  generations seed the cache with the whole conversation so multi-turn
  follow-ups prefill only the new message. Sizing: each entry is one
  FULL max_seq KV row of HBM (~1 GB for llama3-8b bf16 at 8k; halved by
  MODEL_KV_DTYPE=f8) — ``gofr_tpu_prefix_entries`` gauges the live
  count, ``gofr_tpu_prefix_hit_ratio`` / ``_partial_hit_ratio`` the
  exact / shared-prefix hit rates per lookup
- ``PREFIX_LCP_MIN``: minimum shared-prefix tokens for a partial hit
  (default 0 = the smallest compiled bucket; -1 = exact-only matching,
  restoring the pre-LCP behavior and skipping its warmup compiles)
- ``KV_PAGED`` (default on): block-granular paged KV (tpu/kv_blocks.py)
  — the prefix cache stores refcounted token BLOCKS instead of whole
  ``max_seq`` rows (exact/LCP hits alias blocks copy-free, conversation
  stores alias the prefix they extend, LRU eviction under the arena
  budget yields cached blocks to live admission), and the decode pool
  reserves a request's block budget at submit (``kv_exhausted`` reject
  when even eviction cannot cover it) and frees it the instant the
  request finishes. ``off`` restores the whole-row slot model
- ``KV_BLOCK_TOKENS`` (default 64): tokens per KV block; must divide
  the model's ``max_seq``
- ``KV_BLOCKS`` / ``KV_HBM_BUDGET_MB``: arena size, in blocks or HBM
  megabytes (0 = auto: decode slots + prefix entries worth of blocks,
  which makes the budget non-binding; set one to make eviction and
  block-granular admission real)
- ``TPU_BOOT``: "background" boots the stack off-thread; the server
  accepts immediately and /.well-known/ready reports warmup progress
- ``BATCH_MAX_SIZE`` / ``BATCH_TIMEOUT_MS``: batcher shape
- ``PREFILL_CHUNK_TOKENS``: per-dispatch prefill compute budget — a
  solo prefill whose bucket would exceed it runs CHUNKED through the
  largest compiled bucket inside the budget, resuming from the partial
  KV, so no single prefill dispatch occupies the device much longer
  than a decode chunk (0 = off; chunks reuse warmed bucket executables)
- ``SCHED_POLICY``: prefill/decode interference policy (tpu/scheduler.py)
  — ``fair`` (default: one prefill chunk per decode-chunk interval
  under load), ``decode-first`` (one per two intervals), or
  ``prefill-first`` (never defer, the pre-scheduler behavior);
  ``SCHED_MAX_DEFER_MS`` bounds any single chunk's wait
- ``BATCH_COHORT``: "off" restores FIFO mixed-length prefill batches —
  by default the batcher drains into per-bucket cohorts and dispatches
  bucket-homogeneous batches (no cross-bucket padding waste;
  ``gofr_tpu_prefill_padded_tokens_total`` measures what remains)
- ``TPU_MESH``: multi-chip serving mesh, e.g. "tp=4" (llama3-8b on
  v5e-4: Megatron-sharded weights + tp-sharded KV heads) or "tp=4,dp=4"
  (llama3-70b on v5e-16: tensor-parallel replicas, batch over dp).
  Collectives are emitted by GSPMD over ICI; absent -> single chip.
  (``TPU_TOPOLOGY`` in "axis=N" form is accepted as an alias, but the
  "NxM" physical-grid values TPU VMs export under that name are ignored.)
  Composition: paged KV, chunked prefill, the prefix cache, and the
  pooled penalized path all COMPOSE with tp-only meshes (the paged
  block arena shards its head axis over tp); dp/fsdp meshes degrade
  paged KV and chunked prefill to their fallbacks and pooled multi-LoRA
  degrades under any mesh — every degrade logs AND increments
  ``gofr_tpu_mesh_degrade_total{feature}``. The live mesh shape is on
  ``GET /admin/engine`` (``mesh``), ``gofr_tpu_mesh_axis_size{axis}``,
  and each request's FlightRecord (``mesh_axes``). The echo runner
  parses ``TPU_MESH`` too (host-mesh mode): its paged block arena
  shards every block across the tp fake devices, so mesh code paths
  run compile-free in tier-1.
- ``TPU_ENABLED``: force the datasource on without MODEL_NAME

The datasource receives the container treatment the reference gives Redis
and SQL: non-fatal degraded startup (container.py), ``health_check`` with
device liveness + memory stats, typed TPULog entries, Prometheus metrics
(requests, TTFT, batch sizes, queue depth, device memory).
"""

from __future__ import annotations

import contextlib
import pathlib
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.datasource.health import DOWN, UP, Health
from gofr_tpu.profiling import (
    PREFILL_FETCH_WAIT,
    PREFILL_ISSUE,
    SOLO_FETCH_WAIT,
    SOLO_ISSUE,
    phase,
)
from gofr_tpu.telemetry import current_record as telemetry_record
from gofr_tpu.tpu.batcher import (
    DynamicBatcher,
    next_pow2,
    pack_token_rows,
    pad_rows,
)
from gofr_tpu.tpu.introspect import (
    DispatchTimeline,
    EngineState,
    StallWatchdog,
    current_dispatch,
)
from gofr_tpu.tracing import current_span, get_tracer

# stall deadline the watchdog arms itself with when the operator set no
# explicit WATCHDOG_DISPATCH_TIMEOUT_S and the probe found a real TPU.
# Serving dispatches complete in <1s on a healthy chip, but a dispatch
# may legitimately carry a LAZY compile (an opt-in executable variant or
# remainder chunk length compiling on first use — the executable-cache
# "miss" path): the auto deadline sits well above a compile (the 8B
# pooled-decode executable, the longest, compiles for v5e in under 30s)
# so a compile is never misdiagnosed as a stall, while still catching a
# device runtime that stops answering. Operators who pre-warm everything
# can tighten it via WATCHDOG_DISPATCH_TIMEOUT_S.
WATCHDOG_AUTO_TIMEOUT_S = 120.0

# nullcontext is stateless/reentrant: one shared instance serves every
# unwatched dispatch without a per-call allocation
_NULLCTX = contextlib.nullcontext()


def _note_queue_ahead(drec: Any, runner: Any) -> None:
    """As the runner is about to issue a program of its own: the pool
    dispatches issued and not yet fetched, which is what that program
    queues behind on the device (``DispatchRecord.chunks_ahead``: one
    running and one queued, or the running one alone where the pool holds
    its next chunk back until the device is about to need it, and then
    ``ahead_of_held``). Plain reads, no lock."""
    pool = runner.decode_pool
    drec.chunks_ahead = pool.chunks_in_flight if pool is not None else 0
    if pool is not None and pool.holding:
        drec.ahead_of_held = True


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return the directory
    in effect. THE one site that sets it: called where a process first
    builds a device (``TPUDevice._boot``, before the probe).

    ``JAX_COMPILATION_CACHE_DIR`` set by the caller wins untouched — JAX
    reads the variable itself and the program sets nothing. Otherwise the
    cache lives at ``<checkout>/.jax_cache`` (git-ignored), derived from
    this package's location: the directory is part of the cache key, so
    it must not move between runs (never a tempdir, pid, or clock)."""
    from gofr_tpu.config import get_env

    placed = get_env("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclass
class TPULog:
    """Typed device-query log entry (gofr style, SURVEY.md §2 #21)."""

    model: str
    op: str
    batch_size: int
    duration_us: int

    def pretty_terminal(self) -> str:
        return (
            f"\x1b[33mTPU\x1b[0m [{self.model}.{self.op} b={self.batch_size}] "
            f"{self.duration_us}µs"
        )

    def log_fields(self) -> dict[str, Any]:
        return {
            "datasource": "tpu",
            "model": self.model,
            "op": self.op,
            "batch_size": self.batch_size,
            "duration_us": self.duration_us,
        }


class TokenStream:
    """What ``generate_stream`` returns: a generation's tokens one at a
    time to whoever iterates (gRPC streaming, the fan-out's pumps, the
    non-stream consumers), and ``ready()`` for a transport that sends what
    is there together: true while a TOKEN can be had without waiting (the
    stream's end is not one). ``close()`` cancels the decode behind it, as
    dropping the last reference does."""

    __slots__ = ("_tokens", "ready")

    def __init__(self, tokens: Any, ready: Any):
        self._tokens = tokens
        self.ready = ready

    def __iter__(self) -> "TokenStream":
        return self

    def __next__(self) -> Any:
        return next(self._tokens)

    def close(self) -> None:
        self._tokens.close()


def _checkpoint_eos_ids(model_path, tokenizer) -> set:
    """EOS ids for default stopping: the checkpoint's
    generation_config.json (eos_token_id int or list — Llama-3 instruct
    lists BOTH <|end_of_text|> and <|eot_id|>), else the tokenizer's own
    eos. Empty when neither exists (seeded test models)."""
    import json as _json
    import os as _os

    if model_path:
        base = model_path if _os.path.isdir(model_path) else _os.path.dirname(model_path)
        gc_path = _os.path.join(base, "generation_config.json")
        if _os.path.isfile(gc_path):
            try:
                with open(gc_path, encoding="utf-8") as fh:
                    eos = _json.load(fh).get("eos_token_id")
            except (OSError, ValueError) as exc:
                # silently dropping the checkpoint's extra EOS ids (e.g.
                # Llama-3's <|eot_id|>) would run every chat past the
                # turn boundary — fail the boot loudly instead
                raise ValueError(
                    f"cannot read {gc_path}: {exc} — fix the checkpoint "
                    "or set GEN_STOP_TOKENS / GEN_STOP_EOS=off"
                ) from None
            if isinstance(eos, int):
                return {eos}
            if isinstance(eos, list) and all(isinstance(t, int) for t in eos):
                return set(eos)
    if tokenizer is not None:
        try:
            return {tokenizer.special_id("eos")}
        except ValueError:
            pass
    return set()


class TPUDevice:
    def __init__(self, config: Any, logger: Any, metrics: Any):
        self.logger = logger
        self.metrics = metrics
        self._config = config
        self.model_name = config.get_or_default("MODEL_NAME", "mlp")
        self.max_batch = int(config.get_or_default("BATCH_MAX_SIZE", "8"))
        self.timeout_ms = float(config.get_or_default("BATCH_TIMEOUT_MS", "5"))
        # "int8" | "int4" | "" — validated eagerly so a MODEL_QUANT typo
        # fails at startup, not behind a background boot
        from gofr_tpu.models.quant import quantizer_for

        self.quant = config.get_or_default("MODEL_QUANT", "")
        quantizer_for(self.quant)
        self.model_path = config.get("MODEL_PATH")
        from gofr_tpu.tokenizer import load_tokenizer

        self.tokenizer = load_tokenizer(config)
        self.default_stop_ids = self._resolve_default_stop_ids(config)

        # devices are NOT touched here: jax.devices() blocks on runtime
        # init, and a device runtime that does not answer would hang app
        # construction before the server ever listens. _boot probes them
        # (off-thread under TPU_BOOT=background), so a dead device shows
        # up as a 503 readiness with a "probing device runtime" stage
        # instead of a silent hang.
        self._mesh_request = (
            config.get_or_default("TPU_MESH", "")
            or config.get_or_default("TPU_TOPOLOGY", "")
        )
        # syntax/axis validation is device-free and fails FAST here; only
        # the device-count check and mesh construction defer to the probe
        _parse_mesh_request(self._mesh_request)
        self.devices: list = []
        self.platform = "pending"
        self.device_kind = "pending"
        self.mesh = None
        self.mesh_axes: Optional[dict[str, int]] = None
        self.compile_cache_dir = ""  # placed by _boot, before the probe

        self._init_metrics(metrics)

        self._parse_serving_config(config)
        # engine introspection (tpu/introspect.py): the explicit state
        # machine, the dispatch timeline behind /admin/dispatches, and
        # the stall watchdog — constructed BEFORE any boot work so the
        # probe itself is already observable
        self.engine = EngineState(metrics=metrics, logger=logger)
        self.timeline = DispatchTimeline(
            capacity=int(
                config.get_or_default("DISPATCH_TIMELINE_SIZE", "512")
            ),
            metrics=metrics,
        )
        self.watchdog = StallWatchdog(
            self.engine, metrics=metrics, logger=logger,
            timeout_s=self._watchdog_timeout,
        )
        # durable generation journal: prompt hash + sampling params +
        # emitted token ids per request, the substrate resumable streams
        # rebuild from after a wedge (see generate/generate_stream)
        from gofr_tpu.telemetry import GenerationJournal

        self.journal_wal = None
        if self._journal_enabled and self._journal_dir:
            # crash-durable journal: the WAL rehydrates this process's
            # pre-crash resumable entries BEFORE serving starts, so an
            # X-Resume-From that raced the restart finds them waiting
            from gofr_tpu.journal_wal import JournalWAL

            self.journal_wal = JournalWAL(
                self._journal_dir,
                segment_bytes=self._journal_segment_bytes,
                retain=self._journal_segments,
                fsync=self._journal_fsync,
                logger=logger,
            )
        self.journal = (
            GenerationJournal(
                capacity=self._journal_capacity,
                max_tokens=self._journal_max_tokens,
                metrics=metrics,
                wal=self.journal_wal,
            )
            if self._journal_enabled else None
        )
        if self.journal is not None and self.journal_wal is not None:
            rehydrated = self.journal.rehydrate()
            if rehydrated and logger is not None:
                logger.infof(
                    "journal WAL: rehydrated %s resumable entr%s from %s",
                    rehydrated, "y" if rehydrated == 1 else "ies",
                    self._journal_dir,
                )
        # overload brownout controller: graded shed off host-side
        # signals (batcher queue depth, KV-block utilization); the
        # signal callables read through getattr because the batcher and
        # kv_pool are (re)built by _build_stack and recovery rebuilds
        from gofr_tpu.deadline import BrownoutController

        self.brownout = BrownoutController(
            metrics=metrics,
            queue_hi=self._brownout_queue_hi,
            kv_hi=self._brownout_kv_hi,
            shed_priority=self._brownout_shed_priority,
            clamp_tokens=self._brownout_clamp,
            queue_depth_fn=self._brownout_queue_depth,
            kv_util_fn=self._brownout_kv_util,
        )
        # wedge-recovery supervisor: listens on the engine state machine
        # and drives quarantine -> rebuild -> serving on wedged
        from gofr_tpu.tpu.recovery import RecoverySupervisor

        self.recovery = RecoverySupervisor(
            self, metrics=metrics, logger=logger,
            max_attempts=self._recovery_attempts,
            backoff_s=self._recovery_backoff,
            backoff_max_s=self._recovery_backoff_max,
            attempt_timeout_s=self._recovery_attempt_timeout,
            enabled=self._recovery_enabled,
        )
        # per-stage boot wall times ({stage, kind, bucket, seconds}) —
        # the boot timeline /admin/engine serves; compile stages also
        # feed gofr_tpu_compile_seconds{kind,bucket}
        self.boot_timeline: list[dict[str, Any]] = []
        self._open_stage: Optional[tuple] = None
        self._last_reinit = 0.0
        self._reinit_lock = threading.Lock()
        # serializes adapter admin (load/unload + pool-bank rebuild):
        # without it, two concurrent loads race their bank compiles and
        # the LAST COMPILE TO FINISH — not the last call — would win,
        # silently installing a stale bank
        self._adapter_lock = threading.Lock()
        # boot status: surfaced by /.well-known/ready and health details so
        # a slow cold boot (8B-class warmup compiles) is observable, never
        # indistinguishable from a hang
        self.boot_status: dict[str, Any] = {"state": "booting", "detail": ""}
        self._ready = threading.Event()
        self._boot_error: Optional[BaseException] = None
        # ValueError-class boot failures (mesh/bucket/config validation)
        # are permanent: auto-reinit never retries them
        self._boot_error_permanent = False
        self._closed = False
        if config.get_or_default("TPU_BOOT", "") == "background":
            # serve /.well-known/ready (503 warming) while compiles run
            threading.Thread(
                target=self._boot, name="gofr-tpu-boot", daemon=True
            ).start()
        else:
            self._boot()


    def _resolve_default_stop_ids(self, config: Any) -> frozenset:
        """Default stop ids: EVERY generation ends at the checkpoint's EOS
        (OpenAI semantics — a real instruct model must not run past
        <|eot_id|> to max_tokens). Sources, best first: GEN_STOP_TOKENS
        (explicit ids), the checkpoint's generation_config.json
        eos_token_id (int or list) next to MODEL_PATH, the tokenizer's
        own eos. GEN_STOP_EOS=off disables."""
        if config.get_or_default("GEN_STOP_EOS", "on") == "off":
            return frozenset()
        explicit = config.get("GEN_STOP_TOKENS")
        if explicit:
            try:
                return frozenset(
                    int(t) for t in str(explicit).split(",") if t.strip()
                )
            except ValueError:
                raise ValueError(
                    "GEN_STOP_TOKENS must be comma-separated token ids"
                ) from None
        return frozenset(
            _checkpoint_eos_ids(self.model_path, self.tokenizer)
        )

    def _init_metrics(self, metrics: Any) -> None:
        self._requests = metrics.counter(
            "gofr_tpu_requests_total", "TPU inference requests", labels=("model", "op", "status")
        )
        self._ttft = metrics.histogram(
            "gofr_tpu_ttft_seconds", "time to first token / result", labels=("model", "op")
        )
        self._mem_gauge = metrics.gauge(
            "gofr_tpu_device_memory_bytes", "device memory", labels=("kind",)
        )
        self._tokens_counter = metrics.counter(
            "gofr_tpu_tokens_total", "tokens processed", labels=("model", "op")
        )
        self._spec_gauge = metrics.gauge(
            "gofr_tpu_spec_acceptance",
            "speculative decoding: accepted draft tokens / drafted",
            labels=("model",),
        )
        self._prefix_gauge = metrics.gauge(
            "gofr_tpu_prefix_hit_ratio",
            "prefix cache: exact prompt hits / lookups",
            labels=("model",),
        )
        self._prefix_partial_gauge = metrics.gauge(
            "gofr_tpu_prefix_partial_hit_ratio",
            "prefix cache: shared-prefix (tail-only prefill) hits / lookups",
            labels=("model",),
        )
        # capacity planning: each entry is one FULL max_seq KV row
        # (~n_layers x max_seq x kv_heads x head_dim x 2 x kv_bytes —
        # ~1 GB for llama3-8b bf16 at 8k), so PREFIX_CACHE sizes HBM
        self._prefix_entries_gauge = metrics.gauge(
            "gofr_tpu_prefix_entries",
            "prefix cache: live entries (each one max_seq KV row of HBM)",
            labels=("model",),
        )
        from gofr_tpu.metrics import COMPILE_BUCKETS

        # compile/cache observability (engine introspection layer): every
        # warmup compile stage lands here with its bucket, so a slow cold
        # boot decomposes into per-executable compile cost
        self._compile_hist = metrics.histogram(
            "gofr_tpu_compile_seconds",
            "XLA compile stage duration by kind and sequence bucket",
            labels=("kind", "bucket"), buckets=COMPILE_BUCKETS,
        )
        self._compiles = metrics.counter(
            "gofr_tpu_compiles_total",
            "XLA compile stages run (warmup and lazy)",
            labels=("kind",),
        )
        self._cache_events = metrics.counter(
            "gofr_tpu_cache_events_total",
            "framework cache lookups by result: cache=prefix (prompt KV "
            "reuse) or executable (compiled-shape reuse on the decode/"
            "prefill paths), event=hit|partial_hit|miss",
            labels=("cache", "event"),
        )
        # serving-mesh shape (TPU_MESH): one sample per non-trivial axis,
        # set once the probe builds the mesh — dashboards answer "what
        # mesh is this replica on" without scraping /admin/engine
        self._mesh_axis_gauge = metrics.gauge(
            "gofr_tpu_mesh_axis_size",
            "serving mesh axis sizes (TPU_MESH; absent axes are 1)",
            labels=("axis",),
        )
        # features that silently degraded because of the mesh shape
        # (paged KV under dp/fsdp, pooled multi-LoRA, chunked prefill,
        # the decode pool on indivisible slots): each boot-time degrade
        # increments its feature — a log line alone is not a signal an
        # alert can watch
        self._mesh_degrade = metrics.counter(
            "gofr_tpu_mesh_degrade_total",
            "serving features degraded/disabled by the TPU_MESH shape "
            "(the feature still serves through its fallback path)",
            labels=("feature",),
        )
        from gofr_tpu.fleet.kvwire import transfer_counter

        self._kv_transfer_counter = transfer_counter(metrics)
        # host-side mirror of the counter for /admin/engine (plus the
        # donor-side `served` count, which is not a receiver outcome):
        # the fleet prober scrapes this into /admin/fleet per replica
        self.kv_transfer_stats: dict[str, int] = {
            "ok": 0, "timeout": 0, "corrupt": 0, "evicted": 0,
            "fallback": 0, "served": 0,
        }
        self._kv_transfer_lock = threading.Lock()
        # per-transfer evidence ledgers (bounded rings, both served on
        # /admin/engine under `kv_transfer`): the donor's recent serves
        # and the receiver's recent pulls, each stamped with the fleet
        # request id that caused it — the donor-transfer leg
        # /admin/fleet/trace/<id> joins into its causal timeline
        self._kv_served_ledger: deque = deque(maxlen=64)
        self._kv_pull_ledger: deque = deque(maxlen=64)


    def _parse_serving_config(self, config: Any) -> None:
        """Config parsing + eager validation for every serving knob: a
        typo must fail at construction, never minutes later behind a
        background boot."""
        self._decode_chunk_cfg = int(config.get_or_default("DECODE_CHUNK", "8"))
        # MODEL_NAME=echo only: artificial per-token decode delay so the
        # no-JAX loopback runner mimics a real decode cadence
        self._echo_step_ms = float(config.get_or_default("ECHO_STEP_MS", "0"))
        if self._echo_step_ms < 0:
            raise ValueError("ECHO_STEP_MS must be >= 0")
        raw_max_seq = config.get("MODEL_MAX_SEQ")
        self._max_seq_cfg = int(raw_max_seq) if raw_max_seq else None
        # MODEL_KV_DTYPE=f8 stores the KV cache in float8_e4m3fn — half the
        # HBM per cached token, so 2x MODEL_MAX_SEQ (or decode slots) on a
        # capacity-bound chip at a small accuracy cost
        attn_raw = config.get_or_default("MODEL_ATTN_IMPL", "").strip().lower()
        if attn_raw not in ("", "auto", "xla", "pallas"):
            raise ValueError(
                f"MODEL_ATTN_IMPL '{attn_raw}' not supported — use auto, "
                "xla, or pallas"
            )
        self._attn_impl = attn_raw or None
        kv_raw = config.get_or_default("MODEL_KV_DTYPE", "").strip().lower()
        if kv_raw == "":
            self._kv_dtype = None
        elif kv_raw in ("bf16", "bfloat16"):
            # a K/V cache's default; a retention state's is float32, so
            # there bf16 is a stated (lower) type (_TransformerRunner)
            self._kv_dtype = jnp.bfloat16
        elif kv_raw in ("f8", "fp8", "float8", "float8_e4m3fn"):
            self._kv_dtype = jnp.float8_e4m3fn
        else:
            raise ValueError(
                f"MODEL_KV_DTYPE '{kv_raw}' not supported — use bf16 or f8"
            )
        raw_buckets = config.get_or_default("MODEL_BUCKETS", "").strip()
        # MODEL_BUCKETS="64,512" bounds which sequence buckets exist (each
        # bucket is one ahead-of-time prefill compile at boot — flagship
        # boots compile only what they will serve)
        self._buckets_cfg = (
            tuple(sorted(int(b) for b in raw_buckets.split(","))) if raw_buckets else None
        )
        if self._buckets_cfg and self._buckets_cfg[0] <= 0:
            raise ValueError(
                f"MODEL_BUCKETS entries must be positive, got {raw_buckets!r} "
                "(a zero-width bucket would silently serve empty prefills)"
            )
        # speculative decoding (DRAFT_MODEL_NAME): a small draft model
        # proposes DRAFT_TOKENS tokens per cycle, the target verifies them
        # in ONE forward — greedy output is EXACTLY the target's, at a
        # fraction of the per-token weight streams when drafts are accepted
        self._draft_name = config.get_or_default("DRAFT_MODEL_NAME", "").strip()
        self._draft_tokens = int(config.get_or_default("DRAFT_TOKENS", "4"))
        self._draft_path = config.get("DRAFT_MODEL_PATH")
        if self._draft_name and self._draft_tokens < 2:
            # acceptance is capped at k-1 (the draft cache holds at most k
            # committed positions per cycle), so k=1 could never accept a
            # draft — strictly slower than plain decode. A stale
            # DRAFT_TOKENS without a draft model is ignored.
            raise ValueError("DRAFT_TOKENS must be >= 2")
        # pooled speculative decoding (tpu/spec_pool.py): SPEC_POOLED
        # routes speculation THROUGH the continuous-batching pool (the
        # solo DRAFT_MODEL_NAME latency mode bypasses it) with
        # zero-weight n-gram drafting (SPEC_NGRAM) bounded at SPEC_K_MAX
        # drafts per cycle; SPEC_FAKE_ACCEPT scripts the echo runner's
        # per-cycle accept counts for deterministic tier-1 coverage
        self._spec_pooled = (
            config.get_or_default("SPEC_POOLED", "off").strip().lower()
            == "on"
        )
        self._spec_ngram = (
            config.get_or_default("SPEC_NGRAM", "on").strip().lower()
            != "off"
        )
        self._spec_k_max = int(config.get_or_default("SPEC_K_MAX", "4"))
        if self._spec_k_max < 1:
            raise ValueError("SPEC_K_MAX must be >= 1")
        raw_fake = config.get_or_default("SPEC_FAKE_ACCEPT", "").strip()
        from gofr_tpu.tpu.spec_pool import parse_fake_accept

        self._spec_fake_accept = (
            parse_fake_accept(raw_fake) if raw_fake else None
        )
        if self._spec_pooled and not (
            self._spec_ngram or self._spec_fake_accept
        ):
            raise ValueError(
                "SPEC_POOLED=on needs a draft source: keep SPEC_NGRAM=on "
                "(zero-weight prompt-lookup drafting) or script "
                "SPEC_FAKE_ACCEPT (echo runner)"
            )
        # LORA_ADAPTERS="name=path,name2=path2": named adapter sets
        # (orbax artifacts from models/lora.py::export_adapter) served
        # over ONE shared base — requests pick one with {"adapter": name}
        raw_adapters = config.get_or_default("LORA_ADAPTERS", "").strip()
        self._lora_adapters: dict[str, str] = {}
        if raw_adapters:
            for part in raw_adapters.split(","):
                name, sep, path = part.strip().partition("=")
                if not sep or not name or not path:
                    raise ValueError(
                        f"LORA_ADAPTERS entry '{part.strip()}' is malformed "
                        "— expected name=path[,name2=path2...]"
                    )
                self._lora_adapters[name] = path
        # PREFIX_CACHE=n keeps the KV rows of the n most recent distinct
        # prompts: an exact-match repeat (system prompts, retries) skips
        # prefill entirely — TTFT collapses to the decode path
        self._prefix_cache_size = int(config.get_or_default("PREFIX_CACHE", "0"))
        if self._prefix_cache_size < 0:
            raise ValueError("PREFIX_CACHE must be >= 0")
        # PREFIX_LCP_MIN=n: minimum shared-prefix tokens for a PARTIAL hit
        # (resume from a cached entry's KV, prefill only the tail);
        # 0 = one smallest-bucket's worth (the default worthwhileness bar);
        # -1 = exact-only (no LCP scan, no tail-prefill warmup compiles)
        self._prefix_lcp_min = int(config.get_or_default("PREFIX_LCP_MIN", "0"))
        if self._prefix_lcp_min < -1:
            raise ValueError("PREFIX_LCP_MIN must be >= -1")
        # prefill/decode interference scheduling (tpu/scheduler.py):
        # chunk budget, interleave policy, per-chunk defer bound, and the
        # batcher's cohort formation switch — all validated eagerly
        self._prefill_chunk_cfg = int(
            config.get_or_default("PREFILL_CHUNK_TOKENS", "0")
        )
        if self._prefill_chunk_cfg < 0:
            raise ValueError("PREFILL_CHUNK_TOKENS must be >= 0 (0 = off)")
        from gofr_tpu.tpu.scheduler import POLICIES

        self._sched_policy = (
            config.get_or_default("SCHED_POLICY", "fair").strip().lower()
        )
        if self._sched_policy not in POLICIES:
            raise ValueError(
                f"SCHED_POLICY '{self._sched_policy}' not supported — use "
                f"one of {POLICIES}"
            )
        self._sched_max_defer_ms = float(
            config.get_or_default("SCHED_MAX_DEFER_MS", "1000")
        )
        if self._sched_max_defer_ms <= 0:
            raise ValueError("SCHED_MAX_DEFER_MS must be > 0")
        self._batch_cohort = config.get_or_default("BATCH_COHORT", "on") != "off"
        # paged KV (tpu/kv_blocks.py): block-granular KV storage for the
        # prefix cache (copy-free aliasing, LRU eviction under budget)
        # and block-granular decode-pool admission. KV_PAGED=off restores
        # the whole-row slot model; KV_BLOCK_TOKENS sets the block size
        # (must divide max_seq on transformer models); KV_BLOCKS pins the
        # arena size in blocks (0 = auto: slots + prefix entries worth);
        # KV_HBM_BUDGET_MB sizes the arena by HBM bytes instead
        self._kv_paged = config.get_or_default("KV_PAGED", "on") != "off"
        self._kv_block_tokens = int(
            config.get_or_default("KV_BLOCK_TOKENS", "64")
        )
        if self._kv_block_tokens < 1:
            raise ValueError("KV_BLOCK_TOKENS must be >= 1")
        self._kv_blocks_cfg = int(config.get_or_default("KV_BLOCKS", "0"))
        if self._kv_blocks_cfg < 0:
            raise ValueError("KV_BLOCKS must be >= 0 (0 = auto-size)")
        self._kv_budget_mb = float(
            config.get_or_default("KV_HBM_BUDGET_MB", "0")
        )
        if self._kv_budget_mb < 0:
            raise ValueError("KV_HBM_BUDGET_MB must be >= 0 (0 = auto)")
        # cross-replica KV transfer (fleet/kvwire.py + /admin/kv): this
        # replica serves its cached block tables to peers and, when a
        # request arrives with an X-KV-Donor hint, pulls the warm prefix
        # instead of re-prefilling. KV_TRANSFER=off disarms both sides;
        # KV_TRANSFER_TIMEOUT_S bounds one pull (the client's read
        # budget AND the serving side's default deadline);
        # KV_TRANSFER_PIN_TTL_S bounds how long an export can pin
        # blocks if its serving thread dies mid-send.
        self.kv_transfer_enabled = (
            config.get_or_default("KV_TRANSFER", "on") != "off"
        )
        # X-KV-Donor names a URL this replica will FETCH and whose
        # payload seeds the SHARED prefix cache — client-minted it is
        # an SSRF + cache-poisoning primitive, so the hint is acted on
        # only when the operator declares the front door trusted
        # (replicas behind the fleet router; the
        # FLEET_TRUST_TENANT_HEADER contract)
        self.kv_hint_trusted = (
            config.get_or_default("KV_TRANSFER_TRUST_HINT", "off") == "on"
        )
        self._kv_transfer_timeout = float(
            config.get_or_default("KV_TRANSFER_TIMEOUT_S", "2")
        )
        if self._kv_transfer_timeout <= 0:
            raise ValueError("KV_TRANSFER_TIMEOUT_S must be > 0")
        self._kv_pin_ttl = float(
            config.get_or_default("KV_TRANSFER_PIN_TTL_S", "60")
        )
        if self._kv_pin_ttl <= 0:
            raise ValueError("KV_TRANSFER_PIN_TTL_S must be > 0")
        # the donor's /admin/kv sits on the token-gated admin plane
        # (ADMIN_TOKEN): the fleet shares one token, so pulls forward
        # ours — otherwise a tokened fleet would 401 every transfer and
        # misread its own lockout as donor timeouts
        self._kv_admin_token = config.get("ADMIN_TOKEN") or ""
        # cache-key -> prompt-hash memo for kv_export's donor-side scan
        # (sha256 over every cached key per pull would otherwise repeat;
        # pruned against the live cache when it outgrows it)
        self._kv_hash_memo: dict[bytes, str] = {}
        # the role this replica advertises to the fleet router
        # (disaggregated prefill/decode; /admin/engine carries it):
        # prefill replicas take prefill-heavy work and act as KV
        # donors, decode replicas take token generation, mixed (the
        # default) takes anything — exactly today's behavior
        self.role = (
            config.get_or_default("FLEET_ROLE", "mixed").strip().lower()
        )
        if self.role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"FLEET_ROLE '{self.role}' not supported — use prefill, "
                "decode, or mixed"
            )
        self._refuse_what_a_state_cannot_do(config)
        self._pool_enabled = config.get_or_default("DECODE_POOL", "on") != "off"
        self._pool_slots = int(config.get_or_default("DECODE_SLOTS", str(self.max_batch)))
        # lazy (default): the penalized-pool executable builds in the
        # background on first penalized request (which solos meanwhile);
        # eager: build at boot; off: penalized requests always decode solo
        self._pool_penalties = config.get_or_default(
            "DECODE_POOL_PENALTIES", "lazy"
        ).strip().lower()
        if self._pool_penalties not in ("lazy", "eager", "off"):
            raise ValueError(
                "DECODE_POOL_PENALTIES must be lazy, eager, or off"
            )
        # stall watchdog deadline: unset -> auto (arms itself at
        # WATCHDOG_AUTO_TIMEOUT_S once the probe sees a TPU platform);
        # "off"/"0" -> disabled; a positive float -> armed from
        # construction (the probe itself then runs under the deadline)
        raw_wd = (
            config.get_or_default("WATCHDOG_DISPATCH_TIMEOUT_S", "") or ""
        ).strip().lower()
        self._watchdog_auto = raw_wd == ""
        if raw_wd in ("", "off"):
            self._watchdog_timeout = 0.0
        else:
            self._watchdog_timeout = float(raw_wd)
            if self._watchdog_timeout < 0:
                raise ValueError(
                    "WATCHDOG_DISPATCH_TIMEOUT_S must be >= 0 (0/off = "
                    "disabled, unset = auto-arm on TPU platforms)"
                )
        # wedge-recovery supervisor (tpu/recovery.py): on wedged, emit
        # evidence, quarantine the stuck dispatch, rebuild the stack,
        # re-enter warming->serving — bounded attempts with exponential
        # backoff, then terminal failed. RECOVERY_ENABLED=off restores
        # the pre-recovery behavior (wedged until the stall resolves or
        # a human restarts the process).
        self._recovery_enabled = (
            config.get_or_default("RECOVERY_ENABLED", "on") != "off"
        )
        self._recovery_attempts = int(
            config.get_or_default("RECOVERY_MAX_ATTEMPTS", "3")
        )
        self._recovery_backoff = float(
            config.get_or_default("RECOVERY_BACKOFF_S", "1")
        )
        self._recovery_backoff_max = float(
            config.get_or_default("RECOVERY_BACKOFF_MAX_S", "30")
        )
        self._recovery_attempt_timeout = float(
            config.get_or_default("RECOVERY_ATTEMPT_TIMEOUT_S", "300")
        )
        # durable generation journal (telemetry.py GenerationJournal):
        # per-request prompt hash + sampling params + emitted token ids,
        # so interrupted requests resume after recovery instead of
        # truncating. JOURNAL=off disables (streams then abort on wedge
        # exactly as before); JOURNAL_CAPACITY bounds retained entries,
        # JOURNAL_MAX_TOKENS bounds one entry's recorded tokens.
        self._journal_enabled = config.get_or_default("JOURNAL", "on") != "off"
        self._journal_capacity = int(
            config.get_or_default("JOURNAL_CAPACITY", "256")
        )
        if self._journal_capacity < 1:
            raise ValueError("JOURNAL_CAPACITY must be >= 1")
        self._journal_max_tokens = int(
            config.get_or_default("JOURNAL_MAX_TOKENS", "8192")
        )
        if self._journal_max_tokens < 1:
            raise ValueError("JOURNAL_MAX_TOKENS must be >= 1")
        # journal durability (journal_wal.py): JOURNAL_DIR arms the
        # disk-backed WAL — a SIGKILLed replica rehydrates its
        # resumable entries at next boot (unset = in-memory only, the
        # pre-WAL behavior); JOURNAL_FSYNC picks the durability/latency
        # trade (interrupt | always | off), JOURNAL_SEGMENT_BYTES /
        # JOURNAL_SEGMENTS bound the on-disk footprint via rotation +
        # retention (live entries carry across on rotation checkpoints)
        self._journal_dir = config.get_or_default("JOURNAL_DIR", "")
        self._journal_fsync = config.get_or_default(
            "JOURNAL_FSYNC", "interrupt"
        )
        self._journal_segment_bytes = int(
            config.get_or_default("JOURNAL_SEGMENT_BYTES", str(1 << 20))
        )
        if self._journal_segment_bytes < 4096:
            raise ValueError("JOURNAL_SEGMENT_BYTES must be >= 4096")
        self._journal_segments = int(
            config.get_or_default("JOURNAL_SEGMENTS", "4")
        )
        if self._journal_segments < 1:
            raise ValueError("JOURNAL_SEGMENTS must be >= 1")
        # overload brownout (gofr_tpu/deadline.py BrownoutController):
        # thresholds arm the graded shed — queue depth and/or KV-block
        # utilization; both 0 (the default) keeps the controller inert.
        # BROWNOUT_SHED_PRIORITY is the tier boundary (level 1 sheds
        # below it, level 2 sheds at-or-below it); BROWNOUT_CLAMP_TOKENS
        # clamps max_tokens at level 2 (0 = never clamp).
        from gofr_tpu.deadline import PRIORITY_MAX, PRIORITY_MIN

        self._brownout_queue_hi = int(
            config.get_or_default("BROWNOUT_QUEUE_DEPTH", "0")
        )
        if self._brownout_queue_hi < 0:
            raise ValueError("BROWNOUT_QUEUE_DEPTH must be >= 0 (0 = off)")
        self._brownout_kv_hi = float(
            config.get_or_default("BROWNOUT_KV_UTIL", "0")
        )
        if not 0.0 <= self._brownout_kv_hi < 1.0:
            raise ValueError(
                "BROWNOUT_KV_UTIL must be a fraction in [0, 1) (0 = off)"
            )
        self._brownout_shed_priority = int(
            config.get_or_default("BROWNOUT_SHED_PRIORITY", "5")
        )
        if not PRIORITY_MIN <= self._brownout_shed_priority <= PRIORITY_MAX:
            raise ValueError(
                f"BROWNOUT_SHED_PRIORITY must be {PRIORITY_MIN}.."
                f"{PRIORITY_MAX}"
            )
        self._brownout_clamp = int(
            config.get_or_default("BROWNOUT_CLAMP_TOKENS", "0")
        )
        if self._brownout_clamp < 0:
            raise ValueError("BROWNOUT_CLAMP_TOKENS must be >= 0 (0 = off)")

    def _brownout_queue_depth(self) -> int:
        """Brownout signal: requests waiting for a prefill batch (queue
        + cohort-displaced). 0 before the batcher exists (booting) —
        brownout must never shed on a replica that has no queue yet."""
        batcher = getattr(self, "batcher", None)
        return batcher._depth() if batcher is not None else 0

    def _brownout_kv_util(self) -> float:
        """Brownout signal: fraction of the paged-KV ledger budget that
        is COMMITTED — active rows plus admission reservations (0
        without a paged pool). Cached prefix-cache blocks are excluded
        on purpose: they are reclaimable (they evict the moment live
        traffic needs blocks, the allocator's own admission math
        excludes them too), and counting them would pin a warm,
        otherwise-idle replica at level 2 forever."""
        kv = getattr(self, "kv_pool", None)
        if kv is None:
            return 0.0
        stats = kv.stats()
        budget = stats.get("ledger") or stats.get("total") or 0
        if not budget:
            return 0.0
        used = stats.get("active", 0) + stats.get("reserved", 0)
        return min(1.0, used / budget)

    def _probe_devices(self) -> None:
        """First touch of the device runtime (can block or fail when the
        runtime does not answer — that is WHY it lives in _boot, not
        __init__). An unknown TPU ``device_kind`` fails here (flops.py has
        no peak for it), before any model is built. Multi-host
        runtimes join here first: jax.distributed.initialize blocks until
        peers arrive, and jax.devices() must span the slice afterwards."""
        from gofr_tpu.parallel import multihost

        if self._config.get("TPU_COORDINATOR"):
            self._boot_progress("joining multi-host runtime")
            if multihost.init_from_config(self._config, self.logger):
                self.logger.infof(
                    "multi-host runtime joined: %s", multihost.process_info()
                )
        self._boot_progress("probing device runtime")
        # a runtime that stops answering hangs inside this call: with an
        # EXPLICIT watchdog deadline it runs watched (the auto-armed
        # watchdog starts only after the platform is known)
        probe_rec = self.timeline.begin("device_probe", detail="jax.devices()")
        try:
            with self.watchdog.watch("device_probe", probe_rec.dispatch_id):
                self.devices = jax.devices()
        except BaseException:
            self.timeline.finish(probe_rec, status="error")
            raise
        self.timeline.finish(probe_rec)
        self.platform = self.devices[0].platform
        if self._watchdog_auto and self.platform == "tpu":
            # a real device: arm the stall deadline so a runtime that
            # stops answering mid-serving becomes a diagnosed state
            # instead of a silent hang
            self.watchdog.arm(WATCHDOG_AUTO_TIMEOUT_S)
        self.device_kind = getattr(self.devices[0], "device_kind", self.platform)
        self.mesh = _mesh_from_topology(self._mesh_request, self.devices)
        from gofr_tpu.parallel.mesh import mesh_axes

        # live mesh shape -> gauge + snapshot field + flight records:
        # "what mesh is this replica on" must never require a log dig
        self.mesh_axes = mesh_axes(self.mesh)
        if self.mesh is not None:
            for axis, size in self.mesh.shape.items():
                if size > 1 or axis in ("dp", "fsdp", "tp"):
                    self._mesh_axis_gauge.set(size, axis=axis)

    def _boot(self) -> None:
        del self.boot_timeline[:]
        try:
            self.compile_cache_dir = configure_compile_cache()
            self._probe_devices()
            self._build_stack()
        except BaseException as exc:
            self._close_boot_stage(status="error")
            self._boot_error = exc
            self._boot_error_permanent = isinstance(exc, ValueError)
            self.boot_status = {"state": "failed", "detail": repr(exc)}
            self.engine.transition("failed", repr(exc))
            self._ready.set()
            if threading.current_thread().name == "gofr-tpu-boot":
                self.logger.errorf("TPU boot failed: %r", exc)
                return
            raise
        self._close_boot_stage()
        if self._closed:
            # the device was closed while the background boot compiled —
            # tear down the freshly built stack instead of leaking its
            # worker threads and device buffers
            self._boot_error = RuntimeError("device closed during boot")
            self.boot_status = {"state": "closed", "detail": ""}
            self.engine.transition("closed")
            self._teardown_stack()
            self._ready.set()
            return
        self.boot_status = {"state": "ready", "detail": ""}
        self.engine.transition("serving")
        self._ready.set()
        if threading.current_thread().name == "gofr-tpu-boot":
            # the accurate device-topology line operators grep for — the
            # container's construction-time log could only say "booting"
            self.logger.infof("TPU datasource ready: %s", self.describe())

    def _teardown_stack(self) -> None:
        # the runner closes too (echo runner: poisons its in-flight
        # generate loops so a recovery rebuild interrupts streams on the
        # OLD stack instead of letting them emit forever beside the new
        # one — the compile-free mirror of the pool's PoolFailure)
        runner_close = getattr(getattr(self, "runner", None), "close", None)
        for closer in (
            lambda: self.batcher.close() if getattr(self, "batcher", None) else None,
            lambda: self.decode_pool.close() if getattr(self, "decode_pool", None) else None,
            lambda: runner_close() if runner_close is not None else None,
        ):
            try:
                closer()
            except Exception:
                # gofrlint: disable=GFL006 — shutdown path: every
                # closer must run even if one fails
                pass

    # -- readiness (distinct from liveness/health) ---------------------------
    def ready(self) -> bool:
        return self._ready.is_set() and self._boot_error is None

    def wait_ready(self, timeout: Optional[float] = None) -> None:
        """Block until the boot (warmup compiles) finished; re-raise the
        boot error if it failed. Request paths call this so handlers block
        (rather than crash) during a background boot."""
        if not self._ready.wait(timeout):
            raise TimeoutError(
                f"TPU boot still {self.boot_status['state']} "
                f"({self.boot_status['detail']}) after {timeout}s"
            )
        if self._boot_error is not None:
            raise RuntimeError("TPU boot failed") from self._boot_error

    def _refuse_what_a_state_cannot_do(self, config: Any) -> None:
        """A model whose cache has a leaf that is not K/V rows cannot be
        served by what aliases, rolls back, ships or shards K/V rows: a
        fixed-size state per row (every layer power retention) has no rows
        at all, a "cca" cache keeps a fixed tail per row beside its K and
        V, a model with state-space layers among its attention layers
        keeps a state and a convolution tail per row beside the K/V rows of
        its attention layers, and a latent cache (MLA) keeps one latent and
        one rotated key a token that all heads share, not (kv heads, head
        size) rows; none of those settings would carry the state or the
        tail along, or knows the latent's shape. What the cache holds is
        asked of the kinds of layer the model has
        (``models/transformer.py::MIXERS``). Each such setting is refused
        here, by name, at boot: none of them may give a wrong answer
        instead. So is ``MODEL_QUANT`` for a model whose experts are
        stacked leaves, or whose layers are stacked per kind (of mixer, or
        of feed-forward: a dense layer before expert layers), neither of
        which the quantiser takes, and ``LORA_ADAPTERS`` for a tree that is
        not one stack of layers."""
        from gofr_tpu.models.llama import CONFIGS
        from gofr_tpu.models.transformer import MIXERS

        cfg = CONFIGS.get(self.model_name)
        kinds = tuple(getattr(cfg, "kinds_present", ("softmax",)))
        mixed = len(kinds) > 1
        paired = getattr(cfg, "mixers_per_layer", 1) > 1
        by_ffn = getattr(cfg, "ffn_stacked", False)
        stacking = ("per kind" if mixed else "per feed-forward kind (a dense layer "
                    "before expert layers)" if by_ffn else "in pairs of sublayers")
        if self.quant and (getattr(cfg, "routed", False) or mixed):
            raise ValueError(
                f"MODEL_QUANT is not supported for MODEL_NAME '{self.model_name}': "
                "the quantiser does not take "
                + (f"layers stacked {stacking}" if mixed or by_ffn else "expert-stacked leaves")
            )
        if (mixed or paired or by_ffn) and self._lora_adapters:
            raise ValueError(
                f"LORA_ADAPTERS is not supported for MODEL_NAME '{self.model_name}': "
                f"an adapter wraps one stack of layers, and this model's are stacked {stacking}"
            )
        leaves = {name for kind in kinds for name in MIXERS[kind].cache}
        if leaves <= {"k", "v"}:
            return
        # which of the four reasons applies: 0 a state and no K/V rows,
        # 1 a tail beside K/V rows, 2 a state beside K/V rows, 3 latent rows
        case = (3 if "latent" in leaves else 0 if "k" not in leaves
                else 1 if kinds == ("cca",) else 2)
        stated = (config.get("KV_TRANSFER") or "").strip().lower()
        blocks = ("the paged arena holds K/V blocks",
                  "the paged arena holds K/V blocks and would drop the tail",
                  "the paged arena holds K/V blocks and would drop the state",
                  "the paged arena holds blocks of (kv heads, head size) rows; a latent "
                  "is one vector a token")
        rollback = ("speculation rolls a cache back by length; a state has no length",
                    "speculation rolls a cache back by length; the tail of the token "
                    "rolled back to is gone",
                    "speculation rolls a cache back by length; the state of the token "
                    "rolled back to is gone",
                    "speculation over a latent cache (a verify chunk through the "
                    "absorbed form, two leaves rolled back) is not covered yet")
        wire = ("the wire format carries K/V blocks",
                "the wire format carries K/V blocks, not the tail",
                "the wire format carries K/V blocks, not the state",
                "the wire format carries K/V blocks, not latents")
        moves = ("prefill/decode disaggregation moves K/V over the wire",) * 4
        refused = {  # setting -> (is it on, why each of the four cases cannot serve it)
            "PREFIX_CACHE": (self._prefix_cache_size > 0, (
                "prefix sharing aliases K/V rows; a state would need snapshots",
                "prefix sharing aliases K/V rows; a shared prefix would need the tail "
                "at its last token",
                "prefix sharing aliases K/V rows; a shared prefix would need the state "
                "at its last token",
                "prefix sharing aliases and copies K/V rows by their (kv heads, head "
                "size) shape; a latent row has another")),
            "KV_BLOCKS": (self._kv_paged and self._kv_blocks_cfg > 0, blocks),
            "KV_HBM_BUDGET_MB": (self._kv_paged and self._kv_budget_mb > 0, blocks),
            "DRAFT_MODEL_NAME": (bool(self._draft_name), rollback),
            "SPEC_POOLED": (self._spec_pooled, rollback),
            # float8 is a K/V type: K and V beside a tail or a state take
            # it (the tail stays in the model's type, the state float32)
            "MODEL_KV_DTYPE": (case in (0, 3) and self._kv_dtype == jnp.float8_e4m3fn, (
                "f8 is a K/V type; a state takes float32 (unset) or bf16", "", "",
                "f8 is a K/V type; the latent is normed and scaled for the model's type")),
            "KV_TRANSFER": (stated not in ("", "off"), wire),
            "KV_TRANSFER_TRUST_HINT": (self.kv_hint_trusted, wire),
            "FLEET_ROLE": (self.role != "mixed", moves),
            "TPU_MESH": (_parse_mesh_request(self._mesh_request) is not None, (
                "the state is not yet sharded over a mesh (by kv head under tp)",
                "neither the tail nor the expert stacks are sharded over a mesh yet",
                "neither the state nor the per-kind parameter stacks are sharded "
                "over a mesh yet",
                "neither the latent (which has no head axis) nor the expert stacks "
                "are sharded over a mesh yet")),
        }
        what = ("whose cache is a retention state",
                "whose cache keeps a tail per row beside its K/V rows",
                "whose cache holds a state per row beside its K/V rows",
                "whose cache holds a latent a token, not K/V rows")[case]
        for name, (on, why) in refused.items():
            if on:
                raise ValueError(
                    f"{name} is not supported for MODEL_NAME '{self.model_name}', "
                    f"{what}: {why[case]}"
                )
        # unset, KV transfer is armed by default; here there is nothing to send
        self.kv_transfer_enabled = False

    def _build_stack(self) -> None:
        """Construct (or reconstruct, on reinit) runner + pool + batcher."""
        from gofr_tpu.tpu.scheduler import InterferenceScheduler

        # ONE scheduler instance shared by both dispatchers: the decode
        # pool notes its chunk cadence, prefill dispatches (batcher
        # cohorts and solo chunked prefills) wait for their turn
        self.scheduler = InterferenceScheduler(
            policy=self._sched_policy,
            metrics=self.metrics,
            model=self.model_name,
            max_defer_ms=self._sched_max_defer_ms,
        )
        self._boot_progress("building runner (model init / checkpoint load)")
        self.runner = _build_runner(
            self.model_name, self.quant, self.model_path, self.max_batch,
            mesh=self.mesh, decode_chunk=self._decode_chunk_cfg,
            max_seq=self._max_seq_cfg, buckets=self._buckets_cfg,
            kv_dtype=self._kv_dtype, draft_name=self._draft_name,
            draft_tokens=self._draft_tokens, draft_path=self._draft_path,
            attn_impl=self._attn_impl,
            prefix_cache=self._prefix_cache_size,
            prefix_lcp_min=self._prefix_lcp_min,
            lora_adapters=self._lora_adapters,
            echo_step_ms=self._echo_step_ms,
            prefill_chunk_tokens=self._prefill_chunk_cfg,
            timeline=self.timeline,
            watchdog=self.watchdog,
            cache_events=self._note_cache_event,
            kv_paged=self._kv_paged,
            kv_block_tokens=self._kv_block_tokens,
            kv_blocks=self._kv_blocks_cfg,
            kv_budget_bytes=int(self._kv_budget_mb * 1024 * 1024),
            kv_reserve_seqs=self._pool_slots,
            metrics=self.metrics,
        )
        self._wire_paged_kv()
        if self._spec_pooled and hasattr(self.runner, "enable_pooled_spec"):
            # echo runner: the compile-free pooled-spec mirror (tier-1);
            # the only consumer of the SPEC_FAKE_ACCEPT schedule
            self.runner.enable_pooled_spec(
                self._build_spec_cfg(include_fake=True)
            )
        if (
            self._prefill_chunk_cfg
            and hasattr(self.runner, "_can_chunk_prefill")
            and getattr(self.runner, "prefill_chunk_bucket", None) is None
        ):
            # a silently inert knob voids the documented bound — say so
            # (and count it: gofr_tpu_mesh_degrade_total is the alertable
            # half of this warning)
            self._mesh_degrade.inc(feature="chunked_prefill")
            self.logger.warnf(
                "PREFILL_CHUNK_TOKENS=%d is inert under a dp/fsdp serving "
                "mesh (chunked prefill needs an unsharded cache batch "
                "axis) — over-budget prompts prefill unbounded",
                self._prefill_chunk_cfg,
            )
        self.runner.warmup(progress=self._boot_progress)
        # continuous batching: concurrent decodes share one fixed-shape
        # dispatch per chunk; seeded requests bypass it (device.generate
        # routes them solo — the per-request key sequence must reproduce).
        # With KV_PAGED the pool additionally reserves each request's KV
        # block budget from the SAME BlockPool the prefix cache stores
        # into — one HBM ledger, cached prefixes evicted for admission.
        self.decode_pool = None
        pool_ok = self._pool_enabled
        if pool_ok and self.mesh is not None:
            rows = self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1)
            if self._pool_slots % rows:
                self._mesh_degrade.inc(feature="decode_pool")
                self.logger.warnf(
                    "decode pool disabled: DECODE_SLOTS=%d not divisible by "
                    "dp*fsdp=%d (pool cache shards its slot axis)",
                    self._pool_slots, rows,
                )
                pool_ok = False
        if hasattr(self.runner, "_init_cache") and pool_ok:
            from gofr_tpu.tpu.decode_pool import DecodePool

            self._boot_progress(
                f"warming decode pool ({self._pool_slots} slots)",
                kind="decode_pool",
            )
            self.decode_pool = DecodePool(
                self.runner.params,
                self.runner.cfg,
                self.runner._init_cache,
                n_slots=self._pool_slots,
                chunk=self.runner.decode_chunk_size,
                metrics=self.metrics,
                cache_shardings=getattr(self.runner, "_cache_shardings", None),
                model=self.model_name,
                penalties=self._pool_penalties,
                scheduler=self.scheduler,
                timeline=self.timeline,
                watchdog=self.watchdog,
                kv=self.kv_pool,
                # as many prefilled requests may stand waiting for a
                # seat as one prefill dispatch makes rows
                standing_room=self.max_batch,
                # the real pool speculates only with a real draft
                # source: n-gram. A fake-schedule-only config (echo
                # tier-1 scaffolding) must not clamp a transformer
                # pool's pipeline depth while drafting nothing.
                spec=(
                    self._build_spec_cfg(include_fake=False)
                    if self._spec_pooled and self._spec_ngram else None
                ),
            )
            self.runner.decode_pool = self.decode_pool
            if self._spec_pooled and not self._spec_ngram:
                self.logger.warnf(
                    "SPEC_POOLED=on is inert for the decode pool: "
                    "SPEC_NGRAM=off leaves it no draft source "
                    "(SPEC_FAKE_ACCEPT drives only the echo runner)"
                )
            if getattr(self.runner, "adapters", None):
                self._boot_progress(
                    "warming pooled multi-LoRA bank", kind="lora_bank"
                )
                self._refresh_pool_lora()
        self.batcher = DynamicBatcher(
            self._run_batch,
            max_batch=self.max_batch,
            timeout_ms=self.timeout_ms,
            metrics=self.metrics,
            name=self.model_name,
            bucket_fn=getattr(self.runner, "bucket_for_payload", None),
            scheduler=self.scheduler,
            cohort=self._batch_cohort,
            timeline=self.timeline,
            watchdog=self.watchdog,
        )

    def _build_spec_cfg(self, include_fake: bool) -> Any:
        """One PoolSpecConfig per stack build (SPEC_POOLED=on): draft
        width bound, draft source, the live brownout probe, and the
        shared accept-ratio / tokens-per-dispatch gauges.
        ``include_fake`` gates the SPEC_FAKE_ACCEPT schedule to the
        echo runner — the fake source drafts against a known TRUE
        continuation, which only echo's position-indexed decode has; on
        the real pool it would silently draft nothing forever while
        still clamping the pipeline depth."""
        from gofr_tpu.tpu.spec_pool import PoolSpecConfig

        return PoolSpecConfig(
            k_max=self._spec_k_max,
            ngram=self._spec_ngram,
            fake_schedule=self._spec_fake_accept if include_fake else None,
            brownout_level=self.brownout.level,
            metrics=self.metrics,
            model=self.model_name,
        )

    def _wire_paged_kv(self) -> None:
        """Attach the paged-KV layer to the freshly built runner.

        Transformer runners build their own device-arena BlockPool
        (``_init_paged_kv``) — this only lifts it onto the device for
        the decode pool and ``/admin/engine``. The echo runner gets a
        HOST arena engine here (the device owns config + metrics), so
        the whole allocator/aliasing/admission path runs compile-free
        in tier-1."""
        self.kv_pool = getattr(self.runner, "kv_pool", None)
        reason = getattr(self.runner, "kv_paged_disabled", "")
        if reason:
            if getattr(self.runner, "kv_paged_mesh_degraded", False):
                # mesh-shaped degrade (dp/fsdp batch sharding), not a
                # config typo: count it where alerts can see it
                self._mesh_degrade.inc(feature="kv_paged")
            self.logger.warnf("paged KV disabled: %s", reason)
        if not (
            self._kv_paged
            and self.kv_pool is None
            and hasattr(self.runner, "enable_paged_kv")
        ):
            return
        from gofr_tpu.tpu.kv_blocks import (
            BlockPool,
            HostPagedKV,
            HostTokenArena,
        )

        bt = self._kv_block_tokens
        if self._kv_blocks_cfg:
            n_blocks = self._kv_blocks_cfg
        elif self._kv_budget_mb:
            n_blocks = max(
                int(self._kv_budget_mb * 1024 * 1024)
                // (bt * HostTokenArena.TOKEN_BYTES),
                2,
            )
        else:
            n_blocks = 1024  # ~64k tokens of host "KV" — ample for echo
        # host-mesh mode: a TPU_MESH tp axis shards every block's token
        # span across tp fake devices (the echo analogue of the device
        # arena's head sharding) — fleet/chaos and paged-echo tests then
        # exercise the mesh code paths with zero compiles. Divisibility
        # fails the boot with the axis named, same contract as the
        # transformer's head check.
        tp = (self.mesh_axes or {}).get("tp", 1)
        arena = HostTokenArena(n_blocks, bt, shards=tp)
        pool = BlockPool(
            n_blocks, bt, arena=arena,
            hbm_budget_bytes=n_blocks * arena.block_bytes,
            # echo has no PREFIX_CACHE knob of its own: reuse it when
            # set, else a default bound that keeps tier-1 aliasing real
            cache_entries=self._prefix_cache_size or 32,
            metrics=self.metrics,
        )
        lcp_min = self._prefix_lcp_min
        if lcp_min == 0:
            lcp_min = 8  # echo has no compiled buckets to anchor on
        elif lcp_min < 0:
            lcp_min = 1 << 30  # -1 = exact-only, same as the row store
        from gofr_tpu.deadline import pool_reject_counter

        self.runner.enable_paged_kv(
            HostPagedKV(pool, arena, lcp_min=lcp_min),
            reject_counter=pool_reject_counter(self.metrics),
        )
        self.kv_pool = pool

    # -- cross-replica KV transfer (fleet/kvwire.py) -------------------------
    def _kv_store(self) -> Any:
        """The runner's paged store (echo: HostPagedKV; transformer:
        _PagedPrefixStore) — the object both transfer directions work
        against. None when paged KV is off/degraded."""
        runner = getattr(self, "runner", None)
        store = getattr(runner, "paged", None)
        if store is None:
            store = getattr(runner, "_paged_prefix", None)
        return store

    def kv_transfer_snapshot(self) -> dict:
        with self._kv_transfer_lock:
            out: dict[str, Any] = dict(self.kv_transfer_stats)
            out["served_recent"] = [dict(e) for e in self._kv_served_ledger]
            out["pulls_recent"] = [dict(e) for e in self._kv_pull_ledger]
        out["enabled"] = self.kv_transfer_enabled
        return out

    def _note_transfer(self, outcome: str) -> None:
        self._kv_transfer_counter.inc(outcome=outcome)
        with self._kv_transfer_lock:
            self.kv_transfer_stats[outcome] = (
                self.kv_transfer_stats.get(outcome, 0) + 1
            )

    def kv_export(self, prompt_hash: str,
                  request_id: str = "") -> Optional[tuple]:
        """Donor side of a KV pull: locate the cached entry whose key
        hashes to ``prompt_hash`` and PIN its blocks for the transfer
        (a concurrent admission evicting the entry mid-send must not
        free blocks the wire is still reading). Returns
        ``(spec, table, arena, pin)`` or None (evicted / never seen /
        transfer off — the endpoint 404s cleanly). The caller owns the
        pin: release on stream close; the pin's own TTL guard covers a
        serving thread that dies mid-send."""
        if not self.kv_transfer_enabled:
            return None
        store = self._kv_store()
        if store is None:
            return None
        from gofr_tpu.fleet.kvwire import hash_of_key
        from gofr_tpu.tpu.kv_blocks import BlockTable, TransferPin, blocks_for

        pool, arena = store.pool, store.arena
        # hash the snapshot OUTSIDE pool.lock: sha256 over every cached
        # key under the admission lock would serialize concurrent pulls
        # against reserve/release on the serving hot path
        memo = self._kv_hash_memo
        items = pool.cache_items()
        key = None
        for k, _ in items:
            h = memo.get(k)
            if h is None:
                h = hash_of_key(k)
                memo[k] = h
            if h == prompt_hash:
                key = k
                break
        if len(memo) > 2 * len(items) + 16:
            live = {k for k, _ in items}
            self._kv_hash_memo = {
                k: v for k, v in memo.items() if k in live
            }
        if key is None:
            return None
        with pool.lock:
            entry = pool.cache_lookup(key)
            if entry is None:
                # evicted between scan and pin: the endpoint's clean 404
                return None
            length = entry.table.length
            nb = min(
                blocks_for(length, pool.block_tokens), len(entry.table.blocks)
            )
            blocks = list(entry.table.blocks[:nb])
            pin = TransferPin(pool, blocks, ttl_s=self._kv_pin_ttl)
        from gofr_tpu.telemetry import request_key

        ids = np.frombuffer(key, np.int32)
        spec = dict(arena.wire_spec())
        spec.update({
            "prompt_hash": prompt_hash,
            "model": self.model_name,
            # sampling-identity digest (telemetry.request_key): prompt
            # KV is sampler-independent, but the identity pins MODEL +
            # prompt — a donor serving different weights must be
            # refused before its KV is trusted
            "identity": request_key(self.model_name, ids.tolist(), 0),
            "length": int(length),
            "n_blocks": nb,
            "meta": {
                "length": int(length),
                "next_token": entry.meta.get("next_token"),
            },
        })
        with self._kv_transfer_lock:
            self.kv_transfer_stats["served"] += 1
            self._kv_served_ledger.append({
                "ts": time.time(),  # gofrlint: wall-clock — ledger display timestamp
                "prompt_hash": prompt_hash,
                "request_id": request_id or None,
                "n_blocks": nb,
            })
        return spec, BlockTable(blocks, length), arena, pin

    def prefetch_kv(self, tokens: Any) -> None:
        """Receiving side: when admission parsed an ``X-KV-Donor`` hint
        (the fleet router's KV-locality routing), pull the warm prefix
        from that replica BEFORE paged admission, so the imminent admit
        aliases it copy-free instead of re-prefilling. Strictly
        best-effort: every failure (donor gone, timeout, corruption,
        version skew, eviction, local exhaustion) is counted on
        ``gofr_tpu_kv_transfer_total{outcome}`` and the request falls
        back to local chunked prefill — a transfer can make a request
        faster, never break it."""
        from gofr_tpu.fleet.kvwire import current_kv_hint

        hint = current_kv_hint()
        if (
            hint is None
            or not self.kv_transfer_enabled
            or not self.kv_hint_trusted
        ):
            return
        store = self._kv_store()
        if store is None or not hasattr(store, "install_remote"):
            return
        if isinstance(tokens, str):
            return  # hints ride token-id requests only (hash identity)
        ids = np.asarray(tokens, np.int32).reshape(-1)
        if ids.size == 0:
            return
        with store.pool.lock:
            if store.pool.cache_lookup(ids.tobytes()) is not None:
                return  # already warm locally — no pull, no fallback
        pull_start = time.perf_counter()
        outcome = self._pull_kv(hint, ids, store)
        # receiver-side transfer ledger: which donor, what outcome, how
        # long, for which fleet request — the receiving half of the
        # transfer evidence /admin/fleet/trace/<id> assembles
        from gofr_tpu.fleet.kvwire import prompt_hash as _phash
        from gofr_tpu.telemetry import current_origin

        origin = current_origin()
        with self._kv_transfer_lock:
            self._kv_pull_ledger.append({
                "ts": time.time(),  # gofrlint: wall-clock — ledger display timestamp
                "donor": hint,
                "prompt_hash": _phash(ids),
                "outcome": outcome,
                "request_id": (origin or {}).get("request_id") or None,
                "elapsed_ms": round(
                    (time.perf_counter() - pull_start) * 1000, 1
                ),
            })
        if outcome == "ok":
            self._note_transfer("ok")
            return
        if outcome != "local_exhausted":
            # a transfer-side failure: count the cause AND the fallback
            self._note_transfer(outcome)
        self._note_transfer("fallback")

    def _pull_kv(self, donor: str, ids: np.ndarray, store: Any) -> str:
        """One bounded pull + verify + install. Returns the outcome:
        ok | timeout | corrupt | evicted | local_exhausted."""
        from gofr_tpu.deadline import current_deadline
        from gofr_tpu.fleet import kvwire
        from gofr_tpu.service import HTTPService
        from gofr_tpu.tpu.kv_blocks import ForeignKVRejected, blocks_for

        budget = self._kv_transfer_timeout
        deadline = current_deadline()
        if deadline is not None:
            # the pull spends the REQUEST's budget: never let a slow
            # donor eat time the local prefill fallback will still need
            budget = min(budget, deadline.remaining() * 0.5)
        if budget <= 0.01:
            return "timeout"
        phash = kvwire.prompt_hash(ids)
        streaming = None
        start = time.perf_counter()
        try:
            # HTTPService holds config, not connections (every call
            # opens and closes its own socket) — nothing to cache
            client = HTTPService(
                donor, self.logger, name="kv-donor",
                connect_timeout=2.0,
                read_timeout=self._kv_transfer_timeout,
            )
            headers = {
                "X-Request-Deadline-Ms": str(max(1, int(budget * 1000)))
            }
            # forward the originating fleet request id so the DONOR's
            # served ledger carries it too (both halves of the transfer
            # then join on one id in the assembled trace)
            from gofr_tpu.telemetry import current_origin

            origin = current_origin()
            if origin and origin.get("request_id"):
                headers["X-Gofr-Request-Id"] = origin["request_id"]
            if self._kv_admin_token:
                headers["Authorization"] = f"Bearer {self._kv_admin_token}"
            streaming = client.stream(
                "GET", f"/admin/kv/{phash}",
                headers=headers,
                connect_timeout=min(budget, 2.0),
                read_timeout=budget,
            )
            if streaming.status_code == 404:
                streaming.read(budget_s=min(budget, 1.0))
                return "evicted"
            if streaming.status_code != 200:
                # donor unhealthy/refusing: same verdict as unreachable
                streaming.read(budget_s=min(budget, 1.0))
                return "timeout"
            header, payloads = kvwire.decode_stream(
                self._budgeted_chunks(streaming, start, budget),
                # an over-claiming donor is refused at its header, not
                # buffered: the prompt bounds what a pull may carry
                max_blocks=blocks_for(
                    int(ids.size), store.pool.block_tokens
                ),
            )
            kvwire.check_spec(header, store.arena.wire_spec())
            if header.get("prompt_hash") != phash:
                raise kvwire.VersionSkew(
                    f"donor answered for hash {header.get('prompt_hash')!r}"
                )
            if int(header.get("length") or 0) != int(ids.size):
                raise kvwire.VersionSkew(
                    f"donor entry is {header.get('length')!r} tokens, "
                    f"prompt is {ids.size}"
                )
            from gofr_tpu.telemetry import request_key

            if header.get("identity") != request_key(
                self.model_name, ids.tolist(), 0
            ):
                raise kvwire.VersionSkew(
                    "sampling/model identity mismatch (donor serves "
                    "different weights?)"
                )
            meta = header.get("meta") if isinstance(
                header.get("meta"), dict
            ) else {}
            installed = store.install_remote(ids, payloads, meta)
        except kvwire.KVWireError as exc:
            self.logger.warnf("KV pull from %s: %s", donor, exc)
            return exc.outcome
        except ForeignKVRejected as exc:
            self.logger.warnf("KV pull from %s rejected: %s", donor, exc)
            return "corrupt"
        except TimeoutError:
            # socket.timeout: the donor stalled past the read budget
            return "timeout"
        except Exception as exc:
            from gofr_tpu.service import ServiceCallError

            if isinstance(exc, ServiceCallError):
                return "timeout"  # never connected / request never sent
            # the stream broke mid-body (reset, protocol error): the
            # payload is a partial read — corruption, not slowness
            self.logger.warnf("KV pull from %s broke mid-body: %r", donor, exc)
            return "corrupt"
        finally:
            if streaming is not None:
                streaming.close()
        return "ok" if installed else "local_exhausted"

    @staticmethod
    def _budgeted_chunks(streaming: Any, start: float, budget: float) -> Any:
        """The pull's chunk source with an OVERALL budget: the socket
        timeout only bounds silence between chunks — a donor dripping
        one frame per second would stay inside it forever."""
        for chunk in streaming.iter_chunks():
            if time.perf_counter() - start > budget:
                raise TimeoutError(
                    f"KV pull exceeded its {budget * 1000:.0f} ms budget"
                )
            yield chunk

    def _boot_progress(
        self, detail: str, kind: str = "", bucket: int = 0
    ) -> None:
        """Per-stage boot progress: logged AND surfaced on the readiness
        endpoint, so an 8B cold boot shows which compile it is on.

        Each call also CLOSES the previous stage's wall-time measurement
        into the boot timeline (/admin/engine); stages that name a
        ``kind`` are compile stages — they additionally land on the
        dispatch timeline (kind warmup_compile) and feed the
        ``gofr_tpu_compile_seconds{kind,bucket}`` histogram."""
        self._close_boot_stage()
        if self.boot_status["state"] != "ready":
            self.boot_status = {"state": "warming", "detail": detail}
            self.engine.transition("warming", detail)
        rec = (
            self.timeline.begin("warmup_compile", bucket=bucket, detail=detail)
            if kind else None
        )
        self._open_stage = (detail, kind, bucket, time.perf_counter(), rec)
        self.logger.infof("TPU boot [%s]: %s", self.model_name, detail)

    def _close_boot_stage(self, status: str = "ok") -> None:
        if self._open_stage is None:
            return
        detail, kind, bucket, start, rec = self._open_stage
        self._open_stage = None
        seconds = time.perf_counter() - start
        self.boot_timeline.append({
            "stage": detail, "kind": kind or None,
            "bucket": bucket or None, "seconds": round(seconds, 3),
            "status": status,
        })
        if kind and status == "ok":
            # a stage the boot DIED in must not pollute the compile
            # histogram with its truncated wall time
            self._compile_hist.observe(seconds, kind=kind, bucket=str(bucket))
            self._compiles.inc(kind=kind)
        if rec is not None:
            self.timeline.finish(rec, status=status)

    # -- handler-facing API --------------------------------------------------
    def infer(self, payload: Any, timeout: float = 60.0) -> Any:
        """Blocking single inference (sync handlers). Payload shape depends
        on the model: MLP -> feature vector; bert -> {"tokens": [...]};
        transformer -> {"tokens": [...]} returning next-token logits argmax."""
        wait_start = time.perf_counter()
        self.wait_ready(timeout)
        # the batcher gets what REMAINS of the caller's deadline (waiting
        # out a cold boot must not double the timeout budget)
        remaining = max(0.001, timeout - (time.perf_counter() - wait_start))
        start = time.perf_counter()
        # ACTIVATED device span (an activate=False span here never became
        # anyone's parent): the batcher queue item captures it, so the
        # dispatch-side tpu-batch span joins the caller's trace
        with get_tracer().start_span(f"tpu-{self.model_name}"):
            try:
                result = self.batcher.infer(self._prepare(payload), timeout=remaining)
                self._observe("infer", "ok", start)
                return result
            except Exception:
                self._observe("infer", "error", start)
                raise

    async def infer_async(self, payload: Any) -> Any:
        if not self._ready.is_set():
            import asyncio

            await asyncio.get_running_loop().run_in_executor(None, self.wait_ready, 600.0)
        elif self._boot_error is not None:
            raise RuntimeError("TPU boot failed") from self._boot_error
        start = time.perf_counter()
        try:
            result = await self.batcher.infer_async(self._prepare(payload))
            self._observe("infer", "ok", start)
            return result
        except Exception:
            self._observe("infer", "error", start)
            raise

    def _journal_key(self, ids: Any, max_new_tokens: int, sampler: Any,
                     stop_tokens: Any, adapter: Optional[str]) -> str:
        """The request's durable identity (telemetry.request_key over
        the COMPOSED stop set — resume and original must agree)."""
        from gofr_tpu.telemetry import request_key

        model = f"{self.model_name}+{adapter}" if adapter else self.model_name
        return request_key(model, ids, max_new_tokens, sampler, stop_tokens)

    def _journal_start(self, ids: Any, max_new_tokens: int, sampler: Any,
                       stop_tokens: Any, adapter: Optional[str],
                       journal_key: Optional[str],
                       journal_prior: Optional[list]) -> Any:
        """Open this generation's journal entry (None when journaling is
        off). Deterministic = greedy or seeded: the property resume
        leans on (replaying the request reproduces the stream)."""
        if self.journal is None:
            return None
        greedy = sampler is None or sampler.greedy
        seeded = sampler is not None and sampler.seeded
        key = journal_key or self._journal_key(
            ids, max_new_tokens, sampler, stop_tokens, adapter
        )
        return self.journal.start(
            key, self.model_name, max_new_tokens,
            seeded=seeded, deterministic=greedy or seeded,
            prior=journal_prior,
        )

    def generate(
        self,
        tokens: list[int],
        max_new_tokens: int = 32,
        on_token: Optional[Any] = None,
        stop: Optional[Any] = None,
        sampler: Optional[Any] = None,
        stop_tokens: Optional[Any] = None,
        logprobs: bool = False,
        top_logprobs: bool = False,
        adapter: Optional[str] = None,
        adapter_params: Optional[Any] = None,
        journal_key: Optional[str] = None,
        journal_prior: Optional[list] = None,
        resume_from: int = 0,
    ) -> "list[int] | tuple[list[int], list[float]] | tuple":
        """Autoregressive generation (transformer models): prefill goes
        through the dynamic batcher (TTFT path); decode steps run per
        request. ``on_token`` streams each new token id (SSE endpoints);
        ``stop`` (a threading.Event) aborts decode between steps — set it
        when the client disconnects so the device stops doing unread work.
        ``tokens`` may be a str when a tokenizer is configured; ``sampler``
        (ops.sampling.Sampler) sets temperature/top-k/top-p — default
        greedy. ``stop_tokens`` (iterable of ids) end generation; the stop
        token itself is not emitted. ``logprobs=True`` returns
        (tokens, logprobs) — the chosen tokens' RAW model log-softmax
        values (delivered from the shared pool — logprobs ride every pool
        chunk). ``top_logprobs=True`` returns (tokens, logprobs, tops)
        where tops[i] is the TOP_LOGPROBS [(alt_id, alt_lp), ...]
        alternatives at position i, best first.

        Journal plumbing (resume path, see ``generate_stream``):
        ``journal_key`` pins the journal identity to the ORIGINAL
        request when this call is a teacher-forced continuation over
        prompt+emitted (whose own key would differ); ``journal_prior``
        pre-seeds the entry with the tokens the interrupted incarnation
        already produced; ``resume_from`` asks a natively-resumable
        runner (echo) to start its emission at that position."""
        self.wait_ready(600.0)
        if isinstance(tokens, str):
            tokens = self._detokenize(tokens)["tokens"]
        # the checkpoint's EOS always ends generation (OpenAI semantics);
        # request stops compose with it
        stop_tokens = frozenset(stop_tokens or ()) | self.default_stop_ids
        # disaggregated prefill/decode: a router-stamped donor hint
        # pulls the warm prefix into the local paged arena BEFORE
        # admission (best-effort — any failure falls back to local
        # prefill, counted on gofr_tpu_kv_transfer_total)
        if self.kv_transfer_enabled:
            self.prefetch_kv(tokens)
        start = time.perf_counter()
        record = telemetry_record()
        entry = self._journal_start(
            tokens, max_new_tokens, sampler, stop_tokens, adapter,
            journal_key, journal_prior,
        )
        if record is not None and self.mesh_axes:
            # flight records carry the serving-mesh shape: a latency
            # regression must be attributable to the topology it ran on
            record.note_mesh(self.mesh_axes)

        def _ttft() -> None:
            # explicit exemplar: this callback fires on batcher/pool
            # threads whose context may lack the request's contextvars —
            # the captured record carries the trace_id regardless, so the
            # TTFT histogram's OpenMetrics buckets still resolve to the
            # flight record that produced them
            exemplar = (
                {"trace_id": record.trace_id}
                if record is not None and record.trace_id else None
            )
            self._ttft.observe(
                time.perf_counter() - start, exemplar=exemplar,
                model=self.model_name, op="generate",
            )
            if record is not None:
                record.mark_first_token()

        emit = on_token
        if record is not None or entry is not None:
            def emit(item: Any, _cb: Any = on_token) -> None:
                if record is not None:
                    record.note_tokens(1)
                if entry is not None:
                    # journal the bare id ((token, lp) rides logprob runs)
                    entry.append(item[0] if isinstance(item, tuple) else item)
                if _cb is not None:
                    _cb(item)
        from gofr_tpu.telemetry import activate_journal_entry

        journal_token = activate_journal_entry(entry) if entry is not None else None
        extra: dict[str, Any] = {}
        if resume_from and getattr(self.runner, "supports_resume", False):
            # natively-resumable runner (echo): emission starts at the
            # resume position instead of replaying from zero
            extra["resume_from"] = resume_from
        try:
            # activated per-request device span: the prefill batcher item
            # captures it, so tpu-batch nests under it in the same trace
            with get_tracer().start_span(f"tpu-{self.model_name}-generate") as span:
                out = self.runner.generate(
                    tokens, max_new_tokens, on_token=emit, stop=stop,
                    sampler=sampler, stop_tokens=stop_tokens,
                    decode_pool=self.decode_pool,
                    prefill_batcher=self.batcher, logprobs=logprobs,
                    top_logprobs=top_logprobs,
                    adapter=adapter, adapter_params=adapter_params,
                    ttft_cb=_ttft,
                    scheduler=getattr(self, "scheduler", None),
                    **extra,
                )
                emitted = out[0] if isinstance(out, tuple) else out
                span.set_tag("tpu.tokens_out", len(emitted))
            self._requests.inc(model=self.model_name, op="generate", status="ok")
            stats = getattr(self.runner, "spec_stats", None)
            if stats and stats["drafted"]:
                with self.runner._spec_lock:
                    ratio = stats["accepted"] / stats["drafted"]
                self._spec_gauge.set(ratio, model=self.model_name)
            pstats = getattr(self.runner, "prefix_stats", None)
            if pstats:
                partial = pstats.get("partial_hits", 0)
                lookups = pstats["hits"] + partial + pstats["misses"]
                if lookups:
                    self._prefix_gauge.set(
                        pstats["hits"] / lookups, model=self.model_name
                    )
                    self._prefix_partial_gauge.set(
                        partial / lookups, model=self.model_name
                    )
                cache = getattr(self.runner, "_prefix_cache", None)
                if cache is not None:
                    self._prefix_entries_gauge.set(
                        len(cache), model=self.model_name
                    )
            if entry is not None:
                self.journal.finish(entry)
            return out
        except Exception as exc:
            if record is not None:
                record.note_error(exc)
            if entry is not None:
                # keep the record: a recovery-interrupted request is
                # re-admitted from exactly this entry (resume path)
                self.journal.interrupt(entry, f"{type(exc).__name__}: {exc}")
            self._requests.inc(model=self.model_name, op="generate", status="error")
            raise
        finally:
            if journal_token is not None:
                activate_journal_entry(None)

    def generate_stream(
        self, tokens: list[int], max_new_tokens: int = 32,
        sampler: Optional[Any] = None,
        stop_tokens: Optional[Any] = None,
        adapter: Optional[str] = None,
        logprobs: bool = False,
        resume_from: int = 0,
        cancel: Optional[Any] = None,
    ) -> Any:
        """Iterator of decoded token ids, yielded as they decode — the shared
        bridge for SSE and gRPC streaming transports. With ``logprobs=True``
        each item is a (token, raw_logprob) pair instead of a bare id.
        Closing the iterator (client disconnect) cancels the background
        decode instead of letting it run to completion unread. The
        iterator is a ``TokenStream``: its ``ready()`` says a token is
        there to be had without waiting, so that the SSE transport sends
        a decode chunk's tokens together (``Stream.ready``).

        ``cancel`` (a ``threading.Event``) is an EXTERNALLY-trippable
        stop: the SSE responder's client-abort hook sets it the moment
        a write fails, so an abandoned stream frees its decode slot and
        paged-KV blocks within one chunk — without having to close a
        generator that may be mid-``next`` on a pool thread. Omitted,
        the stream creates its own private event (the old behavior).

        ``resume_from=k`` resumes an INTERRUPTED deterministic stream at
        token position k (the client already holds tokens 0..k-1):
        tokens the journal recorded before the interruption replay
        instantly, and the continuation teacher-forces a prefill over
        prompt+emitted through the paged-KV path (block aliasing makes
        the re-prefill nearly copy-free). Without a journal entry — a
        different replica, or the journal evicted it — the request
        regenerates from scratch and the first k emissions are
        suppressed; either way the resumed stream is bit-identical to
        the uninterrupted run's positions k.. for greedy and seeded
        requests. Non-deterministic (unseeded sampled) requests refuse
        resume with a 400-class error."""
        adapter_params = None
        if adapter is not None:
            # validate EAGERLY (this wrapper is not a generator, so the
            # check runs before the transport commits a 200): an unknown
            # adapter must 400 exactly like the non-streaming path. The
            # resolved TREE is pinned and passed down — a concurrent
            # runtime unload between this check and the background
            # decode thread must not turn the committed 200 into an
            # error frame
            self.wait_ready(600.0)
            adapter_params = getattr(self.runner, "adapters", {}).get(adapter)
            if adapter_params is None:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(
                    f"adapter '{adapter}' (loaded: "
                    f"{sorted(getattr(self.runner, 'adapters', {}))})"
                )
        if sampler is not None and getattr(sampler, "logit_bias", None):
            # same eager rule for logit_bias: an out-of-vocab id must 400
            # before the stream commits, not surface as an error frame
            # after a 200
            self.wait_ready(600.0)
            from gofr_tpu.ops.sampling import check_bias_ids

            try:
                cfg = getattr(self.runner, "cfg", None)
                if cfg is not None:
                    check_bias_ids(sampler.logit_bias, cfg.vocab_size)
            except ValueError as exc:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(str(exc)) from None
        if resume_from:
            if resume_from < 0:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError("resume offset must be >= 0")
            if logprobs:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(
                    "resume is not supported with logprobs (the journal "
                    "records token ids only)"
                )
            if sampler is not None and not sampler.greedy and not sampler.seeded:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(
                    "resume requires a deterministic request (greedy or "
                    "seeded) — an unseeded sampled stream cannot be "
                    "reproduced"
                )
            self.wait_ready(600.0)
            if isinstance(tokens, str):
                tokens = self._detokenize(tokens)["tokens"]
        import contextvars

        # snapshot NOW, in the handler thread: the generator body below
        # first runs on the SSE pull thread, where the caller's span and
        # flight record are no longer current — the snapshot carries them
        # into the background generation thread
        snapshot = contextvars.copy_context()
        return self._stream_iter(
            tokens, max_new_tokens, sampler, stop_tokens, adapter, logprobs,
            adapter_params, snapshot, resume_from, cancel,
        )

    def _resume_producer(
        self, ids, max_new_tokens, sampler, stop_tokens, adapter,
        adapter_params, resume_from,
    ) -> Any:
        """Build the producer for a RESUMED stream: returns
        ``fn(put, stop)`` emitting items for positions >= resume_from.

        Two modes (gofr_tpu_journal_resumes_total{mode}):
        - ``teacher_forced``: a journal entry survived — replay its
          suffix, then continue by prefilling prompt+emitted (echo: the
          runner's native ``resume_from``; transformer greedy: a plain
          generate over the concatenation — the paged prefix cache
          aliases the prompt's blocks, so the re-prefill moves almost
          no KV bytes).
        - ``replayed``: no usable entry — regenerate the whole stream
          (deterministic by precondition) and suppress the first
          ``resume_from`` emissions.
        """
        composed_stops = frozenset(stop_tokens or ()) | self.default_stop_ids
        key = self._journal_key(
            ids, max_new_tokens, sampler, composed_stops, adapter
        )
        native = getattr(self.runner, "supports_resume", False)
        greedy = sampler is None or sampler.greedy
        entry = None
        if self.journal is not None and (native or greedy):
            # seeded non-greedy continuations cannot rebuild the chunk-
            # aligned RNG schedule mid-stream — they take the replay
            # path, so the entry stays unclaimed for forensics
            entry = self.journal.claim(key, resume_from)
        if self.journal is not None:
            self.journal.note_resume(
                "teacher_forced" if entry is not None else "replayed"
            )

        if entry is not None:
            emitted = list(entry.tokens)

            def produce(put: Any, stop: Any) -> None:
                for token in emitted[resume_from:]:
                    if stop is not None and stop.is_set():
                        return
                    put(token)
                remaining = max_new_tokens - len(emitted)
                if remaining <= 0:
                    return
                if native:
                    self.generate(
                        ids, max_new_tokens, on_token=put, stop=stop,
                        sampler=sampler, stop_tokens=stop_tokens,
                        adapter=adapter, adapter_params=adapter_params,
                        journal_key=key, journal_prior=emitted,
                        resume_from=len(emitted),
                    )
                else:
                    self.generate(
                        list(ids) + emitted, remaining, on_token=put,
                        stop=stop, sampler=sampler, stop_tokens=stop_tokens,
                        adapter=adapter, adapter_params=adapter_params,
                        journal_key=key, journal_prior=emitted,
                    )

            return produce

        def produce(put: Any, stop: Any) -> None:
            skip = resume_from

            def emit(item: Any) -> None:
                nonlocal skip
                if skip > 0:
                    skip -= 1
                    return
                put(item)

            self.generate(
                ids, max_new_tokens, on_token=emit, stop=stop,
                sampler=sampler, stop_tokens=stop_tokens, adapter=adapter,
                adapter_params=adapter_params, journal_key=key,
            )

        return produce

    def _stream_iter(
        self, tokens, max_new_tokens, sampler, stop_tokens, adapter, logprobs,
        adapter_params=None, snapshot=None, resume_from=0, cancel=None,
    ) -> Any:
        import queue as queue_mod
        import threading
        from collections import deque

        out: "queue_mod.SimpleQueue" = queue_mod.SimpleQueue()
        taken: deque = deque()  # off the queue by ready(), not yet yielded
        done = object()
        failure: list[BaseException] = []
        # the caller's cancel event (SSE abort hook) doubles as the
        # producer's stop event so a tripped abort reaches the decode
        # loop without touching this (possibly mid-next) generator
        stop = cancel if cancel is not None else threading.Event()
        if resume_from:
            produce = self._resume_producer(
                tokens, max_new_tokens, sampler, stop_tokens, adapter,
                adapter_params, resume_from,
            )
        else:
            def produce(put: Any, stop_evt: Any) -> None:
                self.generate(
                    tokens, max_new_tokens, on_token=put, stop=stop_evt,
                    sampler=sampler, stop_tokens=stop_tokens, adapter=adapter,
                    logprobs=logprobs, adapter_params=adapter_params,
                )

        def run() -> None:
            try:
                produce(out.put, stop)
            except BaseException as exc:
                failure.append(exc)
            finally:
                out.put(done)

        def ready() -> bool:
            # the consumer's own thread, between two of its next()s: what
            # the producer has put by now is what was ready together (a
            # chunk's burst is put token by token in one go)
            while not out.empty():  # one consumer: what is there stays there
                taken.append(out.get_nowait())
            return bool(taken) and taken[0] is not done

        def tokens_iter() -> Any:
            target = (lambda: snapshot.run(run)) if snapshot is not None else run
            threading.Thread(
                target=target, daemon=True, name="gofr-stream-producer"
            ).start()
            try:
                while True:
                    item = taken.popleft() if taken else out.get()
                    if item is done:
                        break
                    yield item
                if failure:
                    raise failure[0]
            finally:
                stop.set()

        return TokenStream(tokens_iter(), ready)

    # -- internals -----------------------------------------------------------
    def _prepare(self, payload: Any) -> Any:
        return self.runner.prepare(self._detokenize(payload))

    def _detokenize(self, payload: Any) -> Any:
        """Text payloads ({"text": ...} or a bare str) become token ids via
        the configured tokenizer (TOKENIZER_PATH / TOKENIZER=byte)."""
        text = None
        if isinstance(payload, str):
            text = payload
        elif isinstance(payload, dict) and isinstance(payload.get("text"), str):
            text = payload["text"]
        if text is None:
            return payload
        if self.tokenizer is None:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError(
                'text (no tokenizer configured — set TOKENIZER=byte or '
                "TOKENIZER_PATH, or send token ids)"
            )
        return {"tokens": self.tokenizer.encode(text)}

    def _run_batch(self, payloads: list[Any]) -> list[Any]:
        start = time.perf_counter()
        # the batcher opened (and activated) the per-dispatch tpu-batch
        # span, parented to the enqueuing request's span — this callback
        # only decorates it with device-side tags (SURVEY.md §5 profiling
        # hooks — the always-on cheap signal; full XLA traces via
        # /admin/profiler)
        span = current_span()
        try:
            results = self.runner.run_batch(payloads)
        finally:
            elapsed = time.perf_counter() - start
            if span is not None:
                span.set_tag("tpu.batch_size", len(payloads))
                span.set_tag("tpu.device_time_us", int(elapsed * 1e6))
                span.set_tag("tpu.model", self.model_name)
        self.logger.debug(
            TPULog(self.model_name, "batch", len(payloads), int(elapsed * 1e6))
        )
        # real (un-padded) prompt tokens; payloads are prepared id rows
        tokens = sum(int(getattr(p, "size", 0)) for p in payloads)
        drec = current_dispatch()  # the batcher activated this dispatch
        if drec is not None:
            drec.tokens = tokens
        if tokens:
            self._tokens_counter.inc(tokens, model=self.model_name, op="prefill")
        return results

    def _note_cache_event(self, cache: str, event: str) -> None:
        """Runner callback: one prefix/executable cache lookup resolved
        as ``event`` (hit | partial_hit | miss)."""
        self._cache_events.inc(cache=cache, event=event)

    def _observe(self, op: str, status: str, start: float) -> None:
        self._requests.inc(model=self.model_name, op=op, status=status)
        if status == "ok":
            self._ttft.observe(time.perf_counter() - start, model=self.model_name, op=op)

    def engine_snapshot(self) -> dict[str, Any]:
        """One-call engine introspection snapshot (``GET /admin/engine``):
        state machine + history, boot timeline (per-stage/per-compile
        wall times), watchdog state, dispatch counts, queue depth,
        decode-pool slot occupancy, scheduler defer state, cache
        hit/miss counts, and HBM usage. Never blocks on device work —
        every field reads host-side state, so the endpoint answers even
        while the engine is wedged."""
        from gofr_tpu.postmortem import runtime_versions
        from gofr_tpu.telemetry import BOOT_ID

        snap: dict[str, Any] = {
            "engine": self.engine.snapshot(),
            # process identity: changes exactly when the PROCESS was
            # replaced (supervisor restart), not when the engine rebuilt
            "boot_id": BOOT_ID,
            "model": self.model_name,
            "platform": self.platform,
            "device_kind": str(self.device_kind),
            # versions ride the snapshot (and every postmortem bundle
            # embedding it): "which jax was this wedge on" is the first
            # question a stalled-runtime triage asks
            "versions": runtime_versions(),
            # where this process keeps its persistent XLA compile cache
            "compile_cache_dir": self.compile_cache_dir,
            # live serving-mesh shape (None = single chip): axes with
            # their sizes plus the device count the mesh spans
            "mesh": (
                {"axes": self.mesh_axes, "devices": self.mesh.size}
                if self.mesh is not None else None
            ),
            # disaggregated serving: the role this replica advertises
            # (FLEET_ROLE — the router's tier routing keys on it) and
            # the cross-replica KV-transfer ledger (receiver outcomes +
            # donor-side serves), scraped by the fleet prober onto
            # /admin/fleet
            "role": self.role,
            "kv_transfer": self.kv_transfer_snapshot(),
            "boot": dict(self.boot_status),
            "boot_timeline": [dict(stage) for stage in self.boot_timeline],
            "watchdog": self.watchdog.snapshot(),
            # wedge-recovery incident state (attempts, backoff deadline,
            # last outcome, MTTR) — the /admin/engine half of the
            # gofr_tpu_engine_recoveries_total counter
            "recovery": self.recovery.snapshot(),
            # generation-journal accounting: entries retained, currently
            # interrupted (resumable), resume outcomes
            "journal": self.journal.stats() if self.journal is not None else None,
            "dispatches": self.timeline.stats(),
            # overload-brownout state: live level, the signals behind
            # it, thresholds, shed count (deadline-aware serving)
            "brownout": self.brownout.snapshot(),
        }
        batcher = getattr(self, "batcher", None)
        snap["queue_depth"] = batcher._depth() if batcher is not None else None
        pool = getattr(self, "decode_pool", None)
        snap["decode_pool"] = pool.occupancy() if pool is not None else None
        # paged-KV block accounting (free-list/refcount/eviction state,
        # budget utilization) — host-side reads off the BlockPool, so
        # block starvation is diagnosable even while the engine is wedged
        kv = getattr(self, "kv_pool", None)
        snap["kv_blocks"] = kv.stats() if kv is not None else None
        sched = getattr(self, "scheduler", None)
        snap["scheduler"] = sched.snapshot() if sched is not None else None
        caches: dict[str, Any] = {}
        pstats = getattr(getattr(self, "runner", None), "prefix_stats", None)
        if pstats:
            caches["prefix"] = dict(pstats)
        caches["executable"] = {
            "hits": self._cache_events.value(cache="executable", event="hit"),
            "misses": self._cache_events.value(
                cache="executable", event="miss"
            ),
        }
        snap["caches"] = caches
        snap["compiles"] = {
            kind: self._compiles.value(kind=kind)
            for kind in sorted(
                {s["kind"] for s in snap["boot_timeline"] if s["kind"]}
            )
        }
        hbm = None
        try:
            stats = self.devices[0].memory_stats() or {}
            hbm = {
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
        except Exception:
            # gofrlint: disable=GFL006 — memory_stats unsupported
            # (CPU PJRT, echo runs); hbm stays None
            pass
        snap["hbm"] = hbm
        return snap

    def describe(self) -> str:
        return (
            f"model={self.model_name} platform={self.platform} "
            f"devices={len(self.devices)} kind={self.device_kind}"
            + (f" quant={self.quant}" if self.quant else "")
            + (f" mesh={dict(self.mesh.shape)}" if self.mesh is not None else "")
            + (
                f" tokenizer={self.tokenizer.backend}"
                if self.tokenizer is not None
                else ""
            )
        )

    # -- failure recovery (SURVEY.md §5: re-init on device loss) -------------
    def reinit(self) -> None:
        """Tear down and rebuild the device stack (runner, batcher, decode
        pool) — the recovery path after device loss. In-flight requests on
        the old stack fail with an error (never a silently-truncated 200);
        params re-load from MODEL_PATH (or re-seed) exactly as at startup."""
        with self._reinit_lock:
            self._reinit_locked()

    def recover(self, detail: str = "") -> None:
        """Wedge-recovery rebuild (tpu/recovery.py): the same teardown +
        re-probe + rebuild as :meth:`reinit`, but walking the engine
        explicitly through ``warming`` before ``serving`` so the
        incident's state history reads recovering → warming → serving.
        Requests pinned to the wedged stack fail at teardown (their
        journal entries stay, marked interrupted, for resume); the
        rebuilt stack reuses whatever jax's compile caches kept warm for
        surviving shapes, so a healthy-device recovery costs re-trace
        time, not a cold boot's optimization time.

        ``_ready`` clears for the duration: a resume request landing
        mid-rebuild PARKS on ``wait_ready`` until the stack is back
        (that is the router's resume-to-the-recovering-replica path)
        instead of racing the teardown. A failed rebuild sets the boot
        error and re-sets the event so parked waiters fail fast rather
        than sleeping out their full timeout."""
        with self._reinit_lock:
            self._ready.clear()
            # truthful readiness body during the rebuild: the 503 must
            # never claim "ready" (the probe stage flips it to warming)
            self.boot_status = {
                "state": "recovering", "detail": detail or "recovery rebuild"
            }
            try:
                self._reinit_locked(
                    detail=detail or "recovered", via_recovery=True
                )
            except BaseException as exc:
                self._boot_error = exc
                self.boot_status = {"state": "failed", "detail": repr(exc)}
                self._ready.set()
                raise

    def _reinit_locked(self, detail: str = "reinitialized",
                       via_recovery: bool = False) -> None:
        self.logger.warnf(
            "reinitializing TPU device stack (model=%s)", self.model_name
        )
        # stamp FIRST: a rebuild that fails because the device is still
        # gone must also hold off the next attempt (no rebuild storms)
        self._last_reinit = time.monotonic()
        self._teardown_stack()  # the old stack may be wedged; rebuild regardless
        del self.boot_timeline[:]  # the rebuild writes a fresh timeline
        # re-probe ALWAYS: a boot that failed during the probe stage left
        # devices/mesh/peak unset, and a device-loss reinit wants fresh
        # runtime state anyway (jax caches make this cheap when healthy)
        try:
            self._probe_devices()
            if via_recovery:
                # the incident's history must read recovering -> warming
                # -> serving, mirroring a boot (ISSUE 9 contract)
                self.engine.transition("warming", "recovery rebuild")
            self._build_stack()
        except BaseException:
            self._close_boot_stage(status="error")
            raise
        self._close_boot_stage()
        if self._closed:
            # the device was closed while this rebuild ran (recovery
            # racing shutdown): tear the fresh stack down instead of
            # leaking its threads, and never overwrite `closed` with
            # `serving` — the same guard the background boot has
            self._boot_error = RuntimeError("device closed during rebuild")
            self.boot_status = {"state": "closed", "detail": ""}
            self.engine.transition("closed")
            self._teardown_stack()
            self._ready.set()
            return
        # a successful rebuild recovers a failed background boot too:
        # requests unblock and /.well-known/ready flips to 200
        self._boot_error = None
        self._boot_error_permanent = False
        self.boot_status = {"state": "ready", "detail": ""}
        self.engine.transition("serving", detail)
        self._ready.set()

    def _maybe_auto_reinit(self) -> bool:
        """At most one automatic rebuild per 30s window — whether the last
        attempt succeeded or not (a dead device must not trigger a rebuild
        storm). Permanent config errors (ValueError from mesh/bucket
        validation) never retry: rebuilding cannot fix a typo, and a 30s
        error loop for the process lifetime helps nobody. The lock acquire
        is NON-blocking: if a rebuild (or a probe hung on an unanswering
        runtime) is already in flight, this health probe reports DOWN immediately
        instead of queueing behind it — /.well-known/health must never
        stop answering. Returns True on a successful rebuild."""
        if self._boot_error_permanent:
            return False
        if not self._reinit_lock.acquire(blocking=False):
            return False  # rebuild already in progress; don't pile up
        try:
            if time.monotonic() - self._last_reinit < 30.0:
                return False
            try:
                self._reinit_locked()
                return True
            except ValueError as exc:  # config-class: retrying cannot help
                self._boot_error_permanent = True
                self.logger.errorf("device reinit failed permanently: %r", exc)
                return False
            except Exception as exc:
                self.logger.errorf("device reinit failed: %r", exc)
                return False
        finally:
            self._reinit_lock.release()

    # -- health (north star: device liveness on /.well-known/health) ---------
    def health_check(self) -> Health:
        details: dict[str, Any] = {
            "platform": self.platform,
            "device_kind": str(self.device_kind),
            "device_count": len(self.devices),
            "model": self.model_name,
        }
        if not self._ready.is_set():
            # still booting: the device is alive (liveness UP) but not
            # serving yet — readiness is the /.well-known/ready gate
            return Health(UP, {**details, "boot": dict(self.boot_status)})
        if self._boot_error is not None:
            # failed boot: the same rate-limited rebuild path as device
            # loss (a transient init failure must not be terminal)
            if self._maybe_auto_reinit():
                return Health(UP, {**details, "reinitialized": True})
            return Health(DOWN, {**details, "boot": dict(self.boot_status)})
        try:
            stats = self.devices[0].memory_stats() or {}
            used = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit")
            if used is not None:
                details["memory_bytes_in_use"] = used
                self._mem_gauge.set(used, kind="in_use")
            if limit is not None:
                details["memory_bytes_limit"] = limit
                self._mem_gauge.set(limit, kind="limit")
        except Exception:
            # gofrlint: disable=GFL006 — memory_stats unsupported on
            # some backends; health proceeds without it
            pass
        try:
            ok = self._probe()
        except Exception as exc:
            # device loss: attempt one rebuild (rate-limited) and re-probe
            if self._maybe_auto_reinit():
                try:
                    if self._probe():
                        return Health(UP, {**details, "reinitialized": True})
                except Exception:
                    # gofrlint: disable=GFL006 — re-probe after reinit:
                    # failure falls through to DOWN below
                    pass
            return Health(DOWN, {**details, "error": str(exc)})
        return Health(UP if ok else DOWN, details)

    @staticmethod
    def _probe() -> bool:
        # tiny device round-trip proves the runtime is alive
        probe = jnp.zeros((8,), jnp.float32) + 1.0
        return bool(np.asarray(probe).sum() == 8.0)

    def score(self, tokens: Any, adapter: Optional[str] = None) -> list[float]:
        """Teacher-forcing prompt scoring: log p(t_i | t_<i) per position
        (the loglikelihood primitive; see the runner's ``score``)."""
        self.wait_ready(600.0)
        if not hasattr(self.runner, "score"):
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError(
                "scoring needs an autoregressive transformer model"
            )
        if isinstance(tokens, str):
            tokens = self._detokenize(tokens)["tokens"]
        try:
            out = self.runner.score(tokens, adapter=adapter)
            self._requests.inc(model=self.model_name, op="score", status="ok")
            return out
        except Exception:
            self._requests.inc(model=self.model_name, op="score",
                               status="error")
            raise

    # -- runtime multi-LoRA management (admin surface) -----------------------
    def _refresh_pool_lora(self) -> None:
        """(Re)build the decode pool's stacked adapter bank from the
        runner's named adapters so adapter traffic shares the
        continuous-batching pool. Mesh deployments and rank/target-
        mismatched adapter sets fall back to solo adapter decode
        (logged) — never an error: solo is always correct."""
        pool = self.decode_pool
        runner = self.runner
        if pool is None or getattr(runner, "adapters", None) is None:
            return
        if not runner.adapters:
            pool.disable_lora()
            return
        if getattr(runner, "_cache_shardings", None) is not None:
            # documented degrade, not an error: solo adapter decode is
            # always correct; the counter makes the capacity loss visible
            self._mesh_degrade.inc(feature="pooled_lora")
            self.logger.warnf(
                "pooled multi-LoRA unavailable under a serving mesh — "
                "adapter requests decode solo (gofr_tpu_mesh_degrade_total"
                "{feature=\"pooled_lora\"})"
            )
            return
        from gofr_tpu.models.lora import build_lora_stack

        try:
            stack = build_lora_stack(runner.params, runner.adapters)
        except ValueError as exc:
            self.logger.warnf(
                "pooled multi-LoRA disabled: %s — adapter requests decode "
                "solo", exc,
            )
            pool.disable_lora()
            return
        index = {name: i + 1 for i, name in enumerate(runner.adapters)}
        pool.enable_lora(stack, index)

    def list_adapters(self) -> list[str]:
        self.wait_ready(600.0)
        return sorted(getattr(self.runner, "adapters", None) or {})

    def load_adapter(self, name: str, path: str) -> list[str]:
        """Load a LoRA adapter artifact over the serving base at RUNTIME
        (same artifact format as the boot-time ``LORA_ADAPTERS`` spec).
        The swap is one dict assignment: in-flight requests keep the tree
        they resolved, new requests see the new adapter immediately.
        Returns the loaded-adapter names."""
        from gofr_tpu.errors import InvalidParamError

        self.wait_ready(600.0)
        runner = self.runner
        if not isinstance(name, str) or not name:
            raise InvalidParamError('"name" must be a non-empty string')
        if name == self.model_name:
            # the OpenAI surface routes by model name: a collision would
            # make the adapter unselectable and the listing ambiguous
            raise InvalidParamError(
                f"adapter name '{name}' collides with the base model name"
            )
        if not isinstance(path, str) or not path:
            raise InvalidParamError('"path" must be a non-empty string')
        if getattr(runner, "adapters", None) is None:
            raise InvalidParamError(
                "adapters need a transformer model (MODEL_NAME)"
            )
        mesh = getattr(runner, "mesh", None)
        if mesh is not None and (
            mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1) > 1
        ):
            # the same gate the boot-time LORA_ADAPTERS path enforces
            raise InvalidParamError(
                "adapters serve single-row (solo) requests — use a "
                "tp-only TPU_MESH or no mesh"
            )
        from gofr_tpu.models.lora import apply_adapter
        from gofr_tpu.training.checkpoint import restore_params

        try:
            wrapped = apply_adapter(runner.params, restore_params(path))
        except Exception as exc:
            # a bad path/artifact is a caller error, not a server fault
            raise InvalidParamError(
                f"cannot load adapter from {path!r}: {exc}"
            ) from exc
        # record in the BOOT SPEC too: a device reinit (auto-rebuild on
        # probe failure) reconstructs the runner from _lora_adapters, and
        # a runtime-loaded adapter must survive that — and if a reinit
        # replaced the runner mid-load, the spec is what heals the set
        with self._adapter_lock:
            self._lora_adapters[name] = path
            self.runner.adapters[name] = wrapped
            # rebuild the pool's adapter bank (one pool-shape compile; an
            # admin load pays it here so request paths never do — and a
            # swap never interrupts in-flight adapter slots, which keep
            # their bank)
            self._refresh_pool_lora()
            loaded = sorted(self.runner.adapters)
        self.logger.info(f"adapter '{name}' loaded from {path}")
        return loaded

    def unload_adapter(self, name: str) -> list[str]:
        """Drop a named adapter. In-flight requests that already resolved
        it finish on the tree they hold; new requests get a 400."""
        from gofr_tpu.errors import InvalidParamError

        self.wait_ready(600.0)
        with self._adapter_lock:
            adapters = getattr(self.runner, "adapters", None) or {}
            if adapters.pop(name, None) is None:
                raise InvalidParamError(
                    f"adapter '{name}' (loaded: {sorted(adapters)})"
                )
            self._lora_adapters.pop(name, None)  # keep the reinit spec in sync
            self._refresh_pool_lora()  # shrink (or disable) the pool bank
            remaining = sorted(adapters)
        self.logger.info(f"adapter '{name}' unloaded")
        return remaining

    def close(self) -> None:
        self._closed = True  # an in-flight background boot self-tears-down
        self.recovery.close()
        self.watchdog.close()
        self.engine.transition("closed")
        self._teardown_stack()
        if self.journal_wal is not None:
            self.journal_wal.close()


def new_device(config: Any, logger: Any, metrics: Any) -> TPUDevice:
    """Container wiring entry (parity with redis.new_client / sql.new_sql)."""
    return TPUDevice(config, logger, metrics)


def _parse_mesh_request(topology: str) -> Optional[dict[str, int]]:
    """Device-free parse/validation of ``TPU_MESH`` ("tp=4", "tp=4,dp=4",
    "fsdp=2,tp=2"); empty/unset -> None (single chip). Values without "="
    (e.g. the "1x1"/"2x4" physical-grid strings TPU VMs export as
    TPU_TOPOLOGY) are not mesh requests -> None. Raises on malformed
    entries and unsupported axes — called eagerly at construction so a
    config typo fails at startup, not minutes later behind a background
    boot."""
    topology = topology.strip()
    if not topology or "=" not in topology:
        return None
    kwargs: dict[str, int] = {}
    for part in topology.split(","):
        key, _, val = part.strip().partition("=")
        if key not in ("dp", "fsdp", "tp"):
            raise ValueError(
                f"TPU_MESH axis '{key}' not supported for serving — use "
                "dp, fsdp, tp (sp/pp/ep are training-side axes)"
            )
        try:
            kwargs[key] = int(val)
        except ValueError:
            raise ValueError(
                f"TPU_MESH entry '{part.strip()}' is malformed — expected "
                "axis=int, e.g. 'tp=4,dp=2'"
            ) from None
    return kwargs


def _mesh_from_topology(topology: str, devices: list) -> Optional[Any]:
    """Build the serving mesh for a parsed ``TPU_MESH`` request over the
    local devices (the device-count check lives here, with the probe)."""
    kwargs = _parse_mesh_request(topology)
    if kwargs is None:
        return None
    from gofr_tpu.parallel.mesh import make_mesh, mesh_shape_for

    dp = kwargs.pop("dp", 1)
    n = dp * kwargs.get("fsdp", 1) * kwargs.get("tp", 1)
    if n > len(devices):
        raise ValueError(
            f"TPU_MESH '{topology.strip()}' needs {n} devices, have {len(devices)}"
        )
    return make_mesh(mesh_shape_for(n, **kwargs), devices=devices[:n])


def _validate_mesh_fit(cfg: Any, mesh: Optional[Any], max_batch: int) -> None:
    """Model-shape/mesh divisibility, validated BEFORE params load: every
    failure is a ``ValueError`` naming the offending axis, raised at boot
    — never a GSPMD shape error (or a wedge) at first dispatch."""
    if mesh is None:
        return
    tp = mesh.shape.get("tp", 1)
    if cfg.n_kv_heads % tp:
        raise ValueError(
            f"n_kv_heads={cfg.n_kv_heads} not divisible by "
            f"tp={tp} — KV cache shards its head axis over tp"
        )
    rows = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    padded = next_pow2(max_batch)
    if padded % rows:
        raise ValueError(
            f"padded batch {padded} (next_pow2 of BATCH_MAX_SIZE="
            f"{max_batch}) not divisible by dp*fsdp={rows} — token "
            "batches shard their row axis over (dp, fsdp); raise "
            "BATCH_MAX_SIZE or shrink the dp/fsdp axes of TPU_MESH"
        )


# -- model runners ------------------------------------------------------------

class _EchoRunner:
    """No-JAX loopback runner (``MODEL_NAME=echo``): "generates" by
    cycling the prompt ids. Exists so the full serving stack — routing,
    middleware, dynamic batcher, spans, flight records, SSE streaming —
    can be driven end-to-end in milliseconds, with no checkpoint and no
    XLA compiles (transport/observability tests, local protocol work,
    load-harness smoke runs). ``ECHO_STEP_MS`` adds a per-token delay to
    mimic a real decode cadence."""

    name = "echo"
    # synthetic bucket ladder: echo pads nothing itself, but exposing the
    # transformer ladder lets the batcher form bucket cohorts and account
    # padded tokens on the compile-free path — the scheduler/cohort
    # machinery is then fully exercisable without XLA (tier-1 tests)
    buckets = (64, 128, 256, 512, 1024, 2048, 4096, 8192)
    # journal-resume contract: echo continues a generation natively at
    # ``resume_from`` (its decode is position-indexed), the compile-free
    # analogue of the transformer's teacher-forced prefill
    supports_resume = True
    # the device hands the runner its decode pool (echo has none), so a
    # prefill can record how many pool chunks it was issued behind
    decode_pool: Any = None

    def __init__(self, max_batch: int = 8, step_ms: float = 0.0,
                 mesh_axes: Optional[dict] = None, metrics: Any = None):
        self.max_batch = max_batch
        self.step_s = step_ms / 1000.0
        # deadline-aware serving counters (one registration home:
        # gofr_tpu/deadline.py — the registry dedupes with the
        # batcher/pool registrations): the echo decode loop is the
        # compile-free mirror of the pool's admission gate and
        # per-chunk expiry check
        from gofr_tpu.deadline import (
            cancellations_counter,
            deadline_exceeded_counter,
            pool_reject_counter,
        )

        self._deadline_counter = (
            deadline_exceeded_counter(metrics)
            if metrics is not None else None
        )
        self._cancel_counter = (
            cancellations_counter(metrics)
            if metrics is not None else None
        )
        self._pool_reject = (
            pool_reject_counter(metrics)
            if metrics is not None else None
        )
        # host-mesh mode (TPU_MESH on the echo runner): the parsed axis
        # dict; the device wires the paged host arena with tp shards so
        # mesh code paths run compile-free in tier-1
        self.mesh_axes = mesh_axes
        # injectable stall hook (tests): called at the top of every
        # run_batch, so a test can wedge a "device" dispatch on the
        # compile-free path and drive the watchdog/engine state machine
        # end to end (tests/test_engine_obs.py)
        self.stall_hook: Optional[Any] = None
        # host-side paged KV (tpu/kv_blocks.py HostPagedKV, attached by
        # the device when KV_PAGED=on): echo "KV" is the token ids
        # themselves, so block reservation, prefix aliasing, COW, LRU
        # eviction, and kv_exhausted admission all run compile-free —
        # the tier-1 proof of the paged path
        self.paged: Optional[Any] = None
        self.kv_pool: Optional[Any] = None
        self._kv_reject: Optional[Any] = None
        # recovery poison: a torn-down runner must BREAK its in-flight
        # generate loops (the compile-free mirror of the decode pool's
        # PoolFailure), so a wedge-recovery rebuild interrupts streams
        # instead of leaving them emitting beside the new stack
        self._closed = False
        # pooled speculative decoding (SPEC_POOLED): attached by the
        # device via enable_pooled_spec — the compile-free mirror of the
        # decode pool's spec cycles (draft, one verify "dispatch" per
        # burst, paged-KV rollback, adaptive k), so the whole control
        # flow runs in tier-1. spec_stats shares the transformer
        # runner's shape so the device's acceptance gauge reads both.
        self.spec_pooled: Optional[Any] = None
        self.spec_stats = {"cycles": 0, "drafted": 0, "accepted": 0}
        self._spec_lock = threading.Lock()

    def enable_pooled_spec(self, cfg: Any) -> None:
        """Arm pooled speculative decoding (a
        :class:`~gofr_tpu.tpu.spec_pool.PoolSpecConfig`): generate()
        then decodes in verify cycles — k drafted tokens verified per
        per-cycle "dispatch" (one ``ECHO_STEP_MS`` sleep models the
        target forward; zero-weight drafting costs nothing), rejected
        tokens rolled back through the paged-KV length contract."""
        self.spec_pooled = cfg

    def close(self) -> None:
        self._closed = True

    def enable_paged_kv(self, engine: Any, reject_counter: Any = None) -> None:
        """Attach a host paged-KV engine; the runner then decodes off
        block tables (reading the prompt back THROUGH the arena) and
        the device's prefix-cache gauges read this engine's stats."""
        self.paged = engine
        self.kv_pool = engine.pool
        self._kv_reject = reject_counter
        # same attribute surface as the transformer runner, so the
        # device's hit-ratio/entries gauges work unchanged
        self.prefix_stats = engine.prefix_stats
        self._prefix_cache = engine.pool  # len() = live cached entries

    def bucket_for_payload(self, ids: np.ndarray) -> int:
        n = int(getattr(ids, "size", 0) or 0)
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def prepare(self, payload: Any) -> np.ndarray:
        if isinstance(payload, dict):
            payload = payload.get("tokens", [])
        ids = np.asarray(payload, dtype=np.int32).reshape(-1)
        if ids.size == 0:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError("tokens must be a non-empty list of ids")
        return ids

    def run_batch(self, payloads: list[np.ndarray]) -> list[dict]:
        # the same phase marks as the transformer runner's run_batch, so
        # the record's split is exercisable compile-free
        drec = current_dispatch()
        with phase(PREFILL_ISSUE, drec, end="t_issued"):
            if self.stall_hook is not None:
                self.stall_hook()
            if self._closed:
                raise RuntimeError("echo runner closed (engine recovering)")
            if drec is not None:
                _note_queue_ahead(drec, self)
        with phase(PREFILL_FETCH_WAIT, drec, start="t_fetch", end="t_fetched"):
            if self.step_s:
                time.sleep(self.step_s)
        return [
            {"next_token": int(ids[0]), "length": int(ids.size)}
            for ids in payloads
        ]

    def warmup(self, progress: Any = None) -> None:
        if progress:
            progress("echo runner ready (nothing to compile)")

    def generate(
        self,
        tokens: Any,
        max_new_tokens: int,
        on_token: Any = None,
        stop: Any = None,
        sampler: Any = None,
        stop_tokens: Any = None,
        decode_pool: Any = None,
        prefill_batcher: Any = None,
        ttft_cb: Any = None,
        logprobs: bool = False,
        top_logprobs: bool = False,
        adapter: Optional[str] = None,
        adapter_params: Optional[Any] = None,
        scheduler: Any = None,
        resume_from: int = 0,
    ) -> Any:
        if adapter is not None:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError(
                f"adapter '{adapter}' (the echo runner serves no adapters)"
            )
        ids = self.prepare(tokens)
        stop_tokens = frozenset(stop_tokens or ())
        # prefill rides the REAL dynamic batcher so queue wait, batch
        # cohort, and the tpu-batch span behave exactly as on a device
        # (and its dequeue-time deadline shed fires here, stage=queue)
        if prefill_batcher is not None:
            prefill_batcher.infer(ids)
        else:
            self.run_batch([ids])
        if ttft_cb:
            ttft_cb()
        record = telemetry_record()
        # deadline admission gate — the compile-free mirror of
        # DecodePool._admit_deadline: a request whose remaining budget
        # cannot cover even one decode step at the observed cadence is
        # shed with the ``deadline`` pool-reject reason and a 504,
        # before it reserves KV blocks or decodes a single token
        from gofr_tpu.deadline import current_deadline

        deadline = current_deadline()
        if deadline is not None:
            remaining = deadline.remaining()
            if remaining <= 0 or remaining < self.step_s:
                if self._pool_reject is not None:
                    self._pool_reject.inc(reason="deadline")
                if self._deadline_counter is not None:
                    self._deadline_counter.inc(stage="admission")
                if record is not None:
                    record.note_pool_reject("deadline")
                    record.note_shed("admission")
                from gofr_tpu.errors import DeadlineExceeded

                raise DeadlineExceeded(
                    f"remaining deadline budget {max(remaining, 0) * 1000:.0f} "
                    f"ms cannot cover one decode step (cadence "
                    f"{self.step_s * 1000:.0f} ms)", stage="admission",
                )
        # paged-KV admission (decode side, mirroring the real pool's
        # submit timing): reserve the request's block budget, aliasing
        # cached prefix blocks copy-free; exhaustion falls back to the
        # block-free path with the kv_exhausted reject accounted —
        # exactly the solo-fallback contract of DecodePool.submit
        seq = None
        src = ids
        if self.paged is not None:
            from gofr_tpu.tpu.kv_blocks import KVExhausted

            try:
                seq = self.paged.admit(ids, max_new_tokens)
            except KVExhausted:
                if self._kv_reject is not None:
                    self._kv_reject.inc(reason="kv_exhausted")
                if record is not None:
                    record.note_pool_reject("kv_exhausted")
            if seq is not None:
                # decode off the BLOCK TABLES, not the request buffer:
                # aliasing/COW fidelity is load-bearing for the output
                src = self.paged.prompt_tokens(seq)
                if record is not None:
                    record.note_kv(
                        len(seq.table.blocks), seq.aliased_blocks
                    )
        out: list[int] = []
        lps: list[float] = []
        tops: list = []
        try:
            if self.spec_pooled is not None:
                self._generate_spec(
                    src, seq, out, lps, tops, max_new_tokens, resume_from,
                    stop, stop_tokens, on_token, logprobs, deadline, record,
                )
            else:
                self._generate_plain(
                    src, seq, out, lps, tops, max_new_tokens, resume_from,
                    stop, stop_tokens, on_token, logprobs, deadline, record,
                )
        except BaseException:
            if seq is not None:
                self.paged.abort(seq)
            raise
        if seq is not None:
            if stop is not None and stop.is_set():
                # cancelled (client abort): release EVERYTHING — the
                # free-block count returns to its pre-request baseline
                # within this very step, and an abandoned partial
                # generation never becomes a cache entry (mirroring the
                # pool's cancelled path, which skips the KV hand-back)
                self.paged.abort(seq)
            else:
                # trim the unused reservation (freed blocks admit the
                # next request immediately) and store the conversation
                # copy-free — the request's table BECOMES the cache entry
                self.paged.finish(seq)
        if top_logprobs:
            return out, lps, tops
        return (out, lps) if logprobs else out

    def _shed_decode(self, deadline: Any, record: Any, emitted: int):
        """Mid-decode deadline expiry (plain step or spec cycle): same
        accounting as the pool's per-chunk row check, then the
        504-mapped raise — it unwinds through the abort path, releasing
        the sequence's KV blocks within this very step."""
        if self._deadline_counter is not None:
            self._deadline_counter.inc(stage="decode")
        if self._cancel_counter is not None:
            self._cancel_counter.inc(cause="deadline")
        if record is not None:
            record.note_shed("decode")
        from gofr_tpu.errors import DeadlineExceeded

        raise DeadlineExceeded(
            f"request deadline exceeded mid-decode (after "
            f"{emitted} tokens)", stage="decode",
        )

    def _generate_plain(
        self, src: np.ndarray, seq: Any, out: list, lps: list, tops: list,
        max_new_tokens: int, resume_from: int, stop: Any,
        stop_tokens: frozenset, on_token: Any, logprobs: bool,
        deadline: Any, record: Any,
    ) -> None:
        """One token per "dispatch" (``ECHO_STEP_MS`` sleep) — the
        pre-spec decode loop, and the baseline pooled-spec must stay
        bit-identical to. resume_from > 0: a journal-resumed request —
        emission starts at that position (echo decode is
        position-indexed, so positions resume_from.. are bit-identical
        to an uninterrupted run's)."""
        for i in range(resume_from, max_new_tokens):
            if stop is not None and stop.is_set():
                break
            if self._closed:
                raise RuntimeError(
                    "echo runner closed mid-generation (engine "
                    "recovering)"
                )
            if deadline is not None and deadline.expired():
                # per-step expiry — the echo mirror of the pool's
                # per-chunk row check
                self._shed_decode(deadline, record, len(out))
            token = int(src[i % src.size])
            if token in stop_tokens:
                break
            out.append(token)
            if seq is not None:
                # each decoded token lands in the sequence's KV
                # (COW first if the boundary block is shared)
                self.paged.append(seq, token)
            if logprobs:
                lps.append(0.0)
                tops.append([(token, 0.0)])
            if record is not None and len(out) > 1:
                record.note_delivered(1)  # the first was mark_first_token's
            if on_token:
                on_token((token, 0.0) if logprobs else token)
            if self.step_s:
                time.sleep(self.step_s)

    def _generate_spec(
        self, src: np.ndarray, seq: Any, out: list, lps: list, tops: list,
        max_new_tokens: int, resume_from: int, stop: Any,
        stop_tokens: frozenset, on_token: Any, logprobs: bool,
        deadline: Any, record: Any,
    ) -> None:
        """Pooled-spec decode cycles, compile-free (the tier-1 mirror of
        ``DecodePool``'s spec mode): per cycle the request's draft
        source proposes k tokens (zero-weight n-gram over its own
        prompt+emitted context, or the deterministic ``SPEC_FAKE_ACCEPT``
        schedule), the drafts land SPECULATIVELY in the paged KV (COW on
        shared boundaries — the write-then-maybe-reject shape is the
        point), ONE verify "dispatch" (one ``ECHO_STEP_MS`` sleep, vs
        the plain loop's one per token) accepts the longest matching
        prefix plus the bonus token, and the rejected tail rolls back
        through the block-table length contract. Emission is
        position-indexed off ``src`` exactly like the plain loop, so the
        output is bit-identical whatever the drafts proposed — draft
        quality moves only tokens-per-dispatch. Adaptive k: per-request
        acceptance EMA, clamped by brownout level and the remaining
        deadline budget (deadline.clamp_spec_k)."""
        from gofr_tpu.deadline import clamp_spec_k

        cfg = self.spec_pooled
        # draft context = prompt + whatever a prior (interrupted)
        # incarnation already emitted: a journal resume must draft from
        # the same stream state an uninterrupted run would have
        ctx = [int(t) for t in src] + [
            int(src[j % src.size]) for j in range(resume_from)
        ]
        state = cfg.new_state(ctx[:-1], ctx[-1])
        i = resume_from
        while i < max_new_tokens:
            if stop is not None and stop.is_set():
                break
            if self._closed:
                raise RuntimeError(
                    "echo runner closed mid-generation (engine recovering)"
                )
            if deadline is not None and deadline.expired():
                self._shed_decode(deadline, record, len(out))
            k = clamp_spec_k(
                state.adaptive.current(), cfg.level(), deadline,
                self.step_s,
            )
            # room for k drafts + the bonus within the request budget
            k = min(k, max_new_tokens - i - 1)
            truth = [int(src[(i + j) % src.size]) for j in range(k + 1)]
            drafts = state.propose(k, truth=truth[:k]) if k > 0 else []
            k_eff = len(drafts)
            base_len = seq.table.length if seq is not None else 0
            if seq is not None:
                for t in drafts:
                    # speculative KV writes: the drafts land BEFORE the
                    # verify (COW fires here if the boundary is shared);
                    # rejection rolls them back below
                    self.paged.append(seq, t)
            # ONE verify dispatch for the whole burst — this sleep vs
            # the plain loop's per-token sleep IS the spec win
            if self.step_s:
                time.sleep(self.step_s)
            n_acc = 0
            while n_acc < k_eff and drafts[n_acc] == truth[n_acc]:
                n_acc += 1
            # accepted drafts + the bonus token, stop-token truncated
            # (the stop token ends the stream and is not emitted)
            burst = truth[: n_acc + 1]
            stopped = False
            for j, t in enumerate(burst):
                if t in stop_tokens:
                    burst = burst[:j]
                    stopped = True
                    break
            if seq is not None:
                # rollback: keep only the accepted prefix of the
                # speculative writes (blocks stay reserved — see
                # HostPagedKV.rollback), then land the bonus token
                self.paged.rollback(
                    seq, base_len + min(len(burst), n_acc)
                )
                if len(burst) > n_acc:
                    self.paged.append(seq, burst[-1])
            cancelled = False
            if record is not None:
                # the stream's first token was mark_first_token's delivery
                record.note_delivered(len(burst) - (0 if out else 1))
            for t in burst:
                out.append(t)
                if logprobs:
                    lps.append(0.0)
                    tops.append([(t, 0.0)])
                if on_token:
                    on_token((t, 0.0) if logprobs else t)
                if stop is not None and stop.is_set():
                    cancelled = True
                    break
            state.commit(burst, k_eff, n_acc)
            cfg.note_cycle(k_eff, n_acc, len(burst))
            with self._spec_lock:
                self.spec_stats["cycles"] += 1
                self.spec_stats["drafted"] += k_eff
                self.spec_stats["accepted"] += n_acc
            if record is not None:
                record.note_spec(k_eff, n_acc, len(burst))
            i += len(burst)
            if stopped or cancelled:
                break


class _MLPRunner:
    name = "mlp"

    def __init__(self, quant: bool, model_path: Optional[str], max_batch: int = 8):
        self.max_batch = max_batch
        from gofr_tpu.models.mlp import MLPConfig, init_mlp, mlp_forward

        self.cfg = MLPConfig()
        self.params = _load_or_init(
            model_path, lambda: init_mlp(jax.random.key(0), self.cfg)
        )
        self._fwd = jax.jit(mlp_forward)

    def prepare(self, payload: Any) -> np.ndarray:
        x = np.asarray(payload, dtype=np.float32).reshape(-1)
        if x.shape[0] != self.cfg.in_dim:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError(f"input must have {self.cfg.in_dim} features")
        return x

    def run_batch(self, payloads: list[np.ndarray]) -> list[np.ndarray]:
        n = len(payloads)
        batch = pad_rows(payloads, next_pow2(n))
        out = np.asarray(self._fwd(self.params, jnp.asarray(batch)))
        return [out[i] for i in range(n)]

    def warmup(self, progress: Any = None) -> None:
        b = 1
        while b <= next_pow2(self.max_batch):
            if progress:
                progress(f"compiling mlp forward (batch {b})", kind="forward")
            self._fwd(self.params, jnp.zeros((b, self.cfg.in_dim))).block_until_ready()
            b *= 2

    def generate(self, *a: Any, **k: Any) -> list[int]:
        raise NotImplementedError("generate() requires a transformer model")


class _BertRunner:
    def __init__(self, name: str, quant: bool, model_path: Optional[str], max_batch: int = 8):
        self.max_batch = max_batch
        from gofr_tpu.models.bert import BertConfig, bert_embed, init_bert
        from gofr_tpu.models.quant import quantize_params

        self.name = name
        if name == "bert-tiny":
            self.cfg = BertConfig(vocab_size=30522, dim=128, n_layers=2, n_heads=2,
                                  hidden_dim=512, max_seq=128)
        else:
            self.cfg = BertConfig()
        self.bucket = 128 if self.cfg.max_seq >= 128 else self.cfg.max_seq
        params = _load_or_init(model_path, lambda: init_bert(jax.random.key(0), self.cfg))
        self.params = quantize_params(params, quant)
        cfg = self.cfg
        self._embed = jax.jit(lambda p, t, m: bert_embed(p, t, m, cfg))

    def prepare(self, payload: Any) -> np.ndarray:
        if isinstance(payload, dict):
            tokens = payload.get("tokens", [])
        else:
            tokens = payload
        ids = np.asarray(tokens, dtype=np.int32).reshape(-1)[: self.bucket]
        if ids.size == 0:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError("tokens must be a non-empty list of ids")
        return ids

    def run_batch(self, payloads: list[np.ndarray]) -> list[np.ndarray]:
        n = len(payloads)
        width = self.bucket
        batch = np.zeros((next_pow2(n), width), np.int32)
        mask = np.zeros((next_pow2(n), width), np.int32)
        for i, ids in enumerate(payloads):
            batch[i, : ids.size] = ids
            mask[i, : ids.size] = 1
        mask[n:, 0] = 1  # padded rows need >=1 valid token for the pooler
        out = np.asarray(self._embed(self.params, jnp.asarray(batch), jnp.asarray(mask)))
        return [out[i] for i in range(n)]

    def warmup(self, progress: Any = None) -> None:
        b = 1
        while b <= next_pow2(self.max_batch):
            if progress:
                progress(f"compiling bert embed (batch {b})", kind="embed")
            t = jnp.zeros((b, self.bucket), jnp.int32)
            m = jnp.ones((b, self.bucket), jnp.int32)
            self._embed(self.params, t, m).block_until_ready()
            b *= 2

    def generate(self, *a: Any, **k: Any) -> list[int]:
        raise NotImplementedError("generate() requires a transformer model")


class _TransformerRunner:
    """Decoder serving: batched bucketed prefill + per-request decode.

    With a serving ``mesh`` (TPU_TOPOLOGY): params are placed in their
    Megatron tp/fsdp layout (parallel/sharding.py), the KV cache shards its
    head axis over tp and its batch axis over dp, and token batches are
    pinned to dp — the jitted prefill/decode then compile as SPMD programs
    with GSPMD-inserted ICI collectives. Without a mesh: single chip."""

    # ladder reaches the model family's full context: a ladder capped
    # short of max_seq would silently truncate long prompts to the top
    # bucket (prepare() keeps the LAST tokens). MODEL_BUCKETS restricts
    # this when a deployment only serves shorter prompts.
    SEQ_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096, 8192)
    # the device hands the runner its decode pool once built, so a
    # prefill or solo chunk can record how many pool chunks it was
    # issued behind (DispatchRecord.chunks_ahead)
    decode_pool: Any = None

    def __init__(
        self,
        name: str,
        quant: bool,
        model_path: Optional[str],
        max_batch: int = 8,
        mesh: Optional[Any] = None,
        decode_chunk: int = 8,
        max_seq: Optional[int] = None,
        buckets: Optional[tuple[int, ...]] = None,
        kv_dtype: Optional[Any] = None,
        draft_name: str = "",
        draft_tokens: int = 4,
        draft_path: Optional[str] = None,
        attn_impl: Optional[str] = None,
        prefix_cache: int = 0,
        prefix_lcp_min: int = 0,
        lora_adapters: Optional[dict] = None,
        prefill_chunk_tokens: int = 0,
        timeline: Any = None,
        watchdog: Any = None,
        cache_events: Any = None,
        kv_paged: bool = False,
        kv_block_tokens: int = 64,
        kv_blocks: int = 0,
        kv_budget_bytes: int = 0,
        kv_reserve_seqs: int = 8,
        metrics: Any = None,
    ):
        self.max_batch = max_batch
        # engine introspection: the dispatch timeline + stall watchdog
        # (chunked-prefill slices report through them) and the device's
        # cache-event counter callback; all optional (bare test runners)
        self.timeline = timeline
        self.watchdog = watchdog
        self.metrics = metrics  # deadline-shed counters (solo decode)
        self._cache_events = cache_events or (lambda cache, event: None)
        # compiled-shape cache accounting: keys this runner has already
        # paid a compile for (seeded by warmup); a serving-path first-use
        # is a miss — the compile the operator sees as a latency spike
        self._exec_seen: set = set()
        self._exec_lock = threading.Lock()
        from gofr_tpu.models.llama import CONFIGS
        from gofr_tpu.models.transformer import (
            decode_step,
            init_cache,
            prefill,
        )

        self.name = name
        self.cfg = CONFIGS[name]
        overrides: dict[str, Any] = {}
        if max_seq is not None and max_seq < self.cfg.max_seq:
            # serving-side cache bound: a single chip can hold llama3-8b
            # int8 only with a smaller KV allocation than the model's full
            # context (MODEL_MAX_SEQ config key)
            overrides["max_seq"] = max_seq
        # of a model whose every layer is power retention: a model with
        # layers of two kinds keeps its state float32 and MODEL_KV_DTYPE is
        # what its K and V take
        retention = self.cfg.kinds_present == ("retention",)
        if kv_dtype is not None and (retention or kv_dtype != jnp.bfloat16):
            # MODEL_KV_DTYPE=bf16 has always meant "a K/V cache in the
            # model's type"; only a retention state (float32 when
            # unset) takes it as a type of its own
            overrides["kv_dtype"] = kv_dtype
        if attn_impl:
            overrides["attn_impl"] = attn_impl
        if mesh is not None:
            overrides["mesh"] = mesh
        if overrides:
            import dataclasses

            self.cfg = dataclasses.replace(self.cfg, **overrides)
        self.decode_chunk_size = decode_chunk
        # asked of the cache's leaves, whatever kinds of layer made them:
        # a row that holds a large fixed-size state (Brumby's 0.27 GB; a
        # state-space row's 9 MB is none) has its chunked prefills gated
        # (``_chunked_prefill``)
        from gofr_tpu.models.transformer import latent_token_bytes, state_row_bytes

        one_row = jax.eval_shape(lambda: init_cache(self.cfg, 1))
        row_state = state_row_bytes(one_row)
        # what a token's latent takes over all its places (0: no latent cache)
        self._latent_token_bytes = latent_token_bytes(one_row)
        self._state_prefill_gate = (
            threading.BoundedSemaphore(2) if row_state >= _STATE_GATE_BYTES else None
        )
        # mesh-fit validation BEFORE the params exist: a tp axis that
        # cannot divide the head count (or a dp/fsdp product the padded
        # batch cannot shard over) must fail in milliseconds with the
        # axis named, not after a checkpoint load / param init
        _validate_mesh_fit(self.cfg, mesh, max_batch)
        self._load_params(model_path, quant, mesh)
        self._init_mesh(mesh, max_batch)
        self._build_entry_points(init_cache, prefill, decode_step)
        cfg = self.cfg
        bucket_source = buckets if buckets else self.SEQ_BUCKETS
        self.buckets = [b for b in bucket_source if b <= cfg.max_seq] or [cfg.max_seq]
        # PREFILL_CHUNK_TOKENS: prompts whose bucket would exceed the
        # budget prefill CHUNKED through the largest compiled bucket
        # inside it (chunks must reuse a warmed executable, so the
        # budget resolves to a bucket; a budget below the smallest
        # bucket clamps to it — one bucket's compute is the floor)
        # gated on _can_chunk_prefill: chunked prefill needs the cache's
        # batch axis unsharded, so under a dp/fsdp mesh the budget cannot
        # apply — the attribute stays None and the device warns at boot
        self.prefill_chunk_bucket: Optional[int] = None
        if prefill_chunk_tokens and self._can_chunk_prefill():
            fitting = [b for b in self.buckets if b <= prefill_chunk_tokens]
            self.prefill_chunk_bucket = fitting[-1] if fitting else self.buckets[0]
        # multi-LoRA serving: named adapter sets over the SHARED base
        # arrays (n adapters cost n x adapter bytes, not n x model bytes);
        # requests pick one per call — prefill runs solo with the wrapped
        # tree, decode joins the pool via its stacked adapter bank
        self.adapters: dict[str, Any] = {}
        if lora_adapters:
            if mesh is not None and (
                mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1) > 1
            ):
                raise ValueError(
                    "LORA_ADAPTERS serve single-row (solo) requests — use a "
                    "tp-only TPU_MESH or no mesh"
                )
            from gofr_tpu.models.lora import apply_adapter
            from gofr_tpu.training.checkpoint import restore_params

            for a_name, a_path in lora_adapters.items():
                self.adapters[a_name] = apply_adapter(
                    self.params, restore_params(a_path)
                )
        # speculative decoding: draft engine + target-side verify/reset
        self.spec = (
            _SpecEngine(cfg, quant, draft_name, draft_tokens, draft_path)
            if draft_name
            else None
        )
        self.spec_stats = {"cycles": 0, "drafted": 0, "accepted": 0}
        # guards spec_stats like _prefix_lock guards prefix_stats:
        # concurrent speculative requests increment from their own handler
        # threads, and unlocked += would lose updates (metrics-only skew)
        self._spec_lock = threading.Lock()
        # prefix cache: prompt bytes -> (cache_row, length, next_token).
        # Rows are shared read-only: neither the solo decode chunk nor the
        # pool's write_slot donates/mutates its row input, so one stored
        # row can seed any number of later generations. Beyond exact
        # repeats, a prompt sharing a long-enough common prefix with a
        # stored entry resumes from that entry's KV and prefills only the
        # tail (shared system prompts with differing user turns — the
        # dominant real-traffic shape; no reference equivalent).
        from collections import OrderedDict

        self._prefix_cache: Optional[OrderedDict] = (
            OrderedDict() if prefix_cache > 0 else None
        )
        self._prefix_cache_size = prefix_cache
        # minimum shared-prefix length worth a partial hit: below this the
        # row copy + rolled-back tail prefill costs more than it saves.
        # Default = the smallest compiled bucket (one bucket's worth of
        # prefill skipped); PREFIX_LCP_MIN overrides for short-prompt
        # deployments
        # -1 disables LCP entirely (exact-only cache: no scan on miss, no
        # tail-prefill warmup); 0 defaults to the smallest compiled bucket
        self._prefix_lcp_min = (
            prefix_lcp_min if prefix_lcp_min != 0 else self.buckets[0]
        )
        self._prefix_lock = threading.Lock()
        self.prefix_stats = {"hits": 0, "partial_hits": 0, "misses": 0}
        self._init_paged_kv(
            kv_paged, kv_block_tokens, kv_blocks, kv_budget_bytes,
            kv_reserve_seqs, prefix_cache, metrics,
        )
        if self.spec is not None:
            from gofr_tpu.models.transformer import (
                verify_chunk,
                verify_chunk_sampled,
            )

            self._verify = jax.jit(lambda p, t, c: verify_chunk(p, t, c, cfg))
            # speculative SAMPLING verify (temperature > 0): warmed in
            # warmup() next to the greedy verify
            self._verify_sampled = jax.jit(
                lambda p, t, c, d, q, key, temp, tk, tp, mp:
                verify_chunk_sampled(
                    p, t, c, cfg, d, q, key, temp, tk, tp, mp
                )
            )
            self._set_cache_len = _cache_with_len
        # shared key for greedy decode (temperature 0 ignores it): skips a
        # per-chunk split op, which is its own dispatch
        self._greedy_key = jax.random.key(0)
        # device-side row copy for prefix-cache entries: stored rows must
        # survive any later donation of the live row (and vice versa)
        self._copy_row = jax.jit(lambda c: jax.tree.map(jnp.copy, c))
        # preallocated zero caches per batch size: prefill never mutates its
        # input cache, so one shared zero cache per bsz removes per-batch
        # allocation dispatches
        self._zero_caches: dict[int, Any] = {}
        # teacher-forcing scoring (echo+logprobs / max_tokens=0): ONE
        # jitted callable — jax.jit's own shape-keyed cache handles the
        # per-bucket executables; compiles lazily on first use
        from gofr_tpu.models.transformer import score_tokens as _score_tokens

        self._score_fn = jax.jit(lambda p, t: _score_tokens(p, t, cfg))


    def _init_paged_kv(
        self, kv_paged: bool, block_tokens: int, kv_blocks: int,
        kv_budget_bytes: int, reserve_seqs: int, prefix_cache: int,
        metrics: Any,
    ) -> None:
        """Build the paged-KV layer (tpu/kv_blocks.py) when enabled: one
        shared :class:`BlockPool` over a device arena backs BOTH the
        prefix cache (block-aliased entries, LRU-evicted under the
        budget) and the decode pool's admission ledger — one HBM ledger,
        so cached prefixes yield to live traffic block by block.

        A tensor-parallel serving mesh composes: the arena shards its
        kv-head axis over tp exactly like the compute caches
        (:class:`~gofr_tpu.tpu.kv_blocks.JaxKVArena` ``mesh=``), so
        aliasing, COW, eviction, and ledger admission run unchanged —
        block bookkeeping is host-side and mesh-agnostic. Disabled
        (with the reason recorded for the boot log, and
        ``gofr_tpu_mesh_degrade_total{feature="kv_paged"}`` counted by
        the device) under a dp/fsdp mesh — gather/scatter build [1]-row
        caches, which need the batch axis unsharded, the same bound
        chunked prefill has — or when ``block_tokens`` does not tile
        ``max_seq``. With neither a prefix cache nor an explicit arena
        size there is nothing to page — the slot model is already
        exact."""
        self.kv_pool = None
        self._paged_prefix = None
        self.kv_paged_disabled = ""
        self.kv_paged_mesh_degraded = False
        if not kv_paged or not (prefix_cache > 0 or kv_blocks or kv_budget_bytes):
            return
        if not self._can_chunk_prefill():
            self.kv_paged_disabled = (
                "KV_PAGED degrades to the slot/row model under a dp/fsdp "
                "serving mesh (block gather/scatter needs an unsharded "
                "cache batch axis; tp-only meshes compose)"
            )
            self.kv_paged_mesh_degraded = True
            return
        cfg = self.cfg
        if cfg.max_seq % block_tokens:
            self.kv_paged_disabled = (
                f"KV_BLOCK_TOKENS={block_tokens} does not divide "
                f"max_seq={cfg.max_seq}"
            )
            return
        from gofr_tpu.tpu.kv_blocks import BlockPool, JaxKVArena

        blocks_per_seq = cfg.max_seq // block_tokens
        block_bytes = (
            2 * cfg.n_layers * block_tokens * cfg.n_kv_heads
            * cfg.head_dim * np.dtype(cfg.cache_dtype).itemsize
        )
        # the physical arena backs the PREFIX CACHE's blocks (entries
        # share blocks, so this is a ceiling: +1 seq of headroom for the
        # transient store-side table); in-flight decode KV lives in the
        # pool's slot cache and claims the LEDGER only
        data_blocks = (max(prefix_cache, 0) + 1) * blocks_per_seq
        if kv_blocks:
            ledger = kv_blocks
        elif kv_budget_bytes:
            ledger = int(kv_budget_bytes // block_bytes)
        else:
            # auto: every decode slot + the whole arena fit the ledger —
            # non-binding by default (no admission behavior change
            # without explicit sizing); the at-rest layout is still
            # paged, so entries share blocks and stores shrink
            ledger = data_blocks + reserve_seqs * blocks_per_seq
        if ledger < blocks_per_seq:
            self.kv_paged_disabled = (
                f"KV budget of {ledger} blocks cannot hold one "
                f"{cfg.max_seq}-token sequence ({blocks_per_seq} blocks)"
            )
            return
        data_blocks = min(data_blocks, ledger)
        self.kv_pool = BlockPool(
            data_blocks + 1, block_tokens,  # +1 scratch
            block_bytes=block_bytes,
            hbm_budget_bytes=kv_budget_bytes or ledger * block_bytes,
            cache_entries=prefix_cache,
            metrics=metrics, scratch=True,
            ledger_blocks=ledger,
        )
        if prefix_cache > 0:
            # the physical arena (device buffers + scatter/gather
            # compiles) exists only for the prefix cache's blocks —
            # ledger-only mode (PREFIX_CACHE=0 + an explicit budget) is
            # pure admission accounting and must not pay HBM for it.
            # Under a tp mesh the arena shards its head axis with the
            # compute caches (mesh=), so stores/gathers stay collective-
            # free along tp and rows land pre-placed for the executables
            arena = JaxKVArena(
                cfg, data_blocks + 1, block_tokens, mesh=self.mesh
            )
            self._paged_prefix = _PagedPrefixStore(
                self.kv_pool, arena, self._prefix_lcp_min
            )
            # the paged store answers for the legacy attributes the
            # device's gauges (and tests) read: stats dict + len()
            self.prefix_stats = self._paged_prefix.stats
            self._prefix_cache = self._paged_prefix

    def _load_params(self, model_path: Optional[str], quant: Any,
                     mesh: Optional[Any] = None) -> None:
        """Load/initialize serving weights (HF safetensors, orbax, or
        seeded init), quantizing with the peak-memory contract each
        path documents. Seeded init places each weight on ``mesh`` as
        it is created; checkpoint loads are placed by ``_init_mesh``."""
        from gofr_tpu.models.quant import quantize_params
        from gofr_tpu.models.transformer import init_transformer

        from gofr_tpu.models.ingest import is_safetensors_path, load_llama_params

        if model_path and is_safetensors_path(model_path):
            # HF checkpoint: quantization happens DURING load (one layer in
            # flight), same peak-memory contract as quantize-during-init
            self.params = load_llama_params(model_path, self.cfg, quantize=quant)
        elif model_path:
            params = _load_or_init(
                model_path, lambda: init_transformer(jax.random.key(0), self.cfg)
            )
            self.params = quantize_params(params, quant)
        elif quant:
            # quantize-during-init: peak memory = packed model + ONE bf16
            # weight (init-then-quantize would peak ~3x and OOM 8B on 16GB)
            self.params = init_transformer(
                jax.random.key(0), self.cfg, quantize=quant, mesh=mesh
            )
        else:
            self.params = init_transformer(jax.random.key(0), self.cfg, mesh=mesh)

    def _init_mesh(self, mesh: Optional[Any], max_batch: int) -> None:
        """Serving-mesh placement: Megatron tp/fsdp param layout, KV
        head axis over tp, token batches over (dp, fsdp). Divisibility
        was validated by :func:`_validate_mesh_fit` before the params
        were even loaded."""
        self.mesh = mesh
        self._token_sharding = None
        self._cache_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from gofr_tpu.parallel.sharding import cache_specs, shard_params

            self.params = shard_params(self.params, mesh)
            self._token_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
            self._row_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
            self._cache_shardings = {
                k: NamedSharding(mesh, s) for k, s in cache_specs(None).items()
            }

    def _build_entry_points(self, init_cache: Any, prefill: Any,
                            decode_step: Any) -> None:
        """Build the jitted serving entry points: prefill (+on-device
        argmax), the single decode step, and the parameterized family
        of decode-chunk executables keyed by (penalized, logprobs)."""
        cfg = self.cfg
        self._init_cache = init_cache
        # prefill also argmaxes on device: the hot /infer path fetches [B]
        # int32 next-token ids, never the [B, V] logits
        def _prefill_fn(p, t, c, l):
            if cfg.routed:
                logits, new_cache, aux = prefill(p, t, c, cfg, l, with_aux=True)
            else:
                logits, new_cache = prefill(p, t, c, cfg, l)
            with jax.named_scope("sample"):
                next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if cfg.routed:
                # what routing did rides the ids: one fetch (_note_routing)
                next_ids = pack_expert_counts(next_ids, aux["expert_counts"])
            return logits, next_ids, new_cache

        self._prefill = jax.jit(_prefill_fn)
        self._decode = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))
        from gofr_tpu.models.transformer import decode_chunk, pack_expert_counts

        # ONE parameterized family of decode-chunk executables keyed by
        # (penalized, logprobs). Penalized chunks thread a [1, V] presence
        # mask (such requests run solo — the pool stays presence-free);
        # logprob chunks also return the chosen tokens' raw log-softmax.
        # Only the plain (False, False) variant is warmed at boot; the
        # opt-in variants compile on first use (same policy as remainder
        # chunk sizes) — but every variant is built HERE from one helper,
        # so a decode_chunk signature change cannot silently miss one.
        def _make_chunk_fn(pen: bool, lp: bool) -> Any:
            if pen:
                return jax.jit(
                    lambda p, t, c, key, temp, tk, tp, mp, pres, rp, cnt,
                    pp, fp, bias, n:
                    decode_chunk(
                        p, t, c, cfg, n, key, temp, tk, tp, mp, pres, rp,
                        cnt, pp, fp, bias, with_logprobs=lp,
                    ),
                    static_argnums=(14,),
                )
            return jax.jit(
                lambda p, t, c, key, temp, tk, tp, mp, n: decode_chunk(
                    p, t, c, cfg, n, key, temp, tk, tp, mp, with_logprobs=lp
                ),
                static_argnums=(8,),
            )

        self._chunk_fns = {
            (pen, lp): _make_chunk_fn(pen, lp)
            for pen in (False, True) for lp in (False, True)
        }
        self._decode_chunk = self._chunk_fns[(False, False)]

    def _bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def bucket_for_payload(self, ids: Any) -> int:
        """Compiled bucket a prepared payload lands in — the batcher's
        cohort key and padded-token accounting basis."""
        return self._bucket_for(max(int(getattr(ids, "size", 0) or 0), 1))

    def _note_exec(self, key: tuple) -> None:
        """Executable-shape cache accounting: first use of a (shape)
        key is a MISS (jit compiles), later uses are hits. Warmup seeds
        the set without counting — serving-path numbers stay clean."""
        with self._exec_lock:
            if key in self._exec_seen:
                hit = True
            else:
                self._exec_seen.add(key)
                hit = False
        self._cache_events("executable", "hit" if hit else "miss")

    def _seed_exec(self, key: tuple) -> None:
        with self._exec_lock:
            self._exec_seen.add(key)

    def score(self, tokens: Any, adapter: Optional[str] = None) -> list[float]:
        """log p(t_i | t_<i) for every prompt position i >= 1 — the
        teacher-forcing loglikelihood primitive (completions
        echo+logprobs / max_tokens=0 scoring). The executable compiles
        lazily per bucket on first use (a rare opt-in variant, by the
        repo's compile policy); only the [S-1] chosen values are
        fetched. ``adapter`` scores with that LoRA tree — an eval measuring
        an adapter's loglikelihood must never silently get base-model
        scores."""
        from gofr_tpu.errors import InvalidParamError

        # length check BEFORE prepare: prepare clips to the last max_seq
        # tokens (the generation recency policy), which would silently
        # misalign scores against the caller's full prompt
        raw = tokens.get("tokens", tokens) if isinstance(tokens, dict) else tokens
        if len(raw) > self.buckets[-1]:
            raise InvalidParamError(
                f"prompt of {len(raw)} tokens exceeds the largest "
                f"compiled bucket ({self.buckets[-1]}) — scoring needs "
                "one full-sequence forward"
            )
        prm = self.params
        if adapter is not None:
            prm = self.adapters.get(adapter)
            if prm is None:
                raise InvalidParamError(
                    f"adapter '{adapter}' (loaded: {sorted(self.adapters)})"
                )
        ids = self.prepare(tokens)
        n = int(ids.size)
        if n < 2:
            return []  # position 0 has no conditional
        row = np.zeros((1, self._bucket_for(n)), np.int32)
        row[0, :n] = ids
        out = np.asarray(self._score_fn(prm, jnp.asarray(row)))[0, : n - 1]
        return [float(x) for x in out]

    def prepare(self, payload: Any) -> np.ndarray:
        if isinstance(payload, dict):
            tokens = payload.get("tokens", [])
        else:
            tokens = payload
        ids = np.asarray(tokens, dtype=np.int32).reshape(-1)
        if ids.size == 0:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError("tokens must be a non-empty list of ids")
        if ids.min() < 0 or ids.max() >= self.cfg.vocab_size:
            from gofr_tpu.errors import InvalidParamError

            raise InvalidParamError(
                f"token ids must be in [0, {self.cfg.vocab_size}) for "
                f"model '{self.name}' (tokenizer vocab larger than model?)"
            )
        return ids[-self.cfg.max_seq :]

    def _zero_cache(self, bsz: int) -> Any:
        cache = self._zero_caches.get(bsz)
        if cache is None:
            cache = self._init_cache(self.cfg, bsz, max_seq=self.cfg.max_seq)
            if self._cache_shardings is not None:
                cache = {
                    k: jax.device_put(v, self._cache_shardings[k])
                    for k, v in cache.items()
                }
            self._zero_caches[bsz] = cache
        return cache

    def run_batch(self, payloads: list[np.ndarray]) -> list[Any]:
        """Batched prefill over a shared sequence bucket -> per-request
        (next_token_logits, cache_row) results.

        The batch dim is always padded to max_batch: ONE compiled shape per
        sequence bucket, all warmed at startup — no compile on the serving
        path (north star: p50 TTFT < 200ms)."""
        n = len(payloads)
        drec = current_dispatch()  # the batcher activated this dispatch
        # issue: host preparation (pack, zero cache, H2D) and the enqueue
        # of the program; the jitted call returns before the device ran it
        with phase(PREFILL_ISSUE, drec, end="t_issued"):
            # prompts longer than the largest bucket keep their LAST tokens
            # (consistent with prepare(): recency wins for next-token
            # prediction)
            bucket = self._bucket_for(max(int(p.size) for p in payloads))
            bsz = next_pow2(max(len(payloads), self.max_batch))
            self._note_exec(("prefill", bucket, bsz))
            tokens, lengths = pack_token_rows(payloads, bsz, bucket)
            full_lengths = np.maximum(lengths, 1)  # padded rows need length>=1
            cache = self._zero_cache(bsz)
            if self.cfg.routed:
                # a row the batch was padded with holds no request: its
                # tokens go to no expert (models/transformer.py::_run_cached)
                cache = {**cache, "live": jnp.asarray(lengths > 0, jnp.int32)}
            tokens_dev, lengths_dev = jnp.asarray(tokens), jnp.asarray(full_lengths)
            if self._token_sharding is not None:
                tokens_dev = jax.device_put(tokens_dev, self._token_sharding)
                lengths_dev = jax.device_put(lengths_dev, self._row_sharding)
            if drec is not None:
                _note_queue_ahead(drec, self)
            logits, next_ids, cache = self._prefill(
                self.params, tokens_dev, cache, lengths_dev
            )
        # ONE tiny fetch ([bsz] int32) synchronizes the batch; logits stay
        # on device (row views fetch lazily if a handler reads them) and
        # cache rows slice lazily (only generate() needs them). The wait
        # holds the device queue ahead of the program, its compute and
        # the D2H copy.
        with phase(PREFILL_FETCH_WAIT, drec, start="t_fetch", end="t_fetched"):
            next_ids = np.asarray(next_ids)
        next_ids = _note_routing(drec, next_ids, bsz, self.cfg)
        if drec is not None and self._latent_token_bytes:
            drec.latent_bytes = int(lengths.sum()) * self._latent_token_bytes
        return [
            _PrefillState(
                cache, logits, i,
                next_token=int(next_ids[i]), length=int(full_lengths[i]),
            )
            for i in range(n)
        ]

    def generate(
        self,
        tokens: list[int],
        max_new_tokens: int,
        on_token: Any = None,
        stop: Any = None,
        sampler: Any = None,
        stop_tokens: Any = None,
        decode_pool: Any = None,
        prefill_batcher: Any = None,
        ttft_cb: Any = None,
        logprobs: bool = False,
        top_logprobs: bool = False,
        adapter: Optional[str] = None,
        adapter_params: Optional[Any] = None,
        scheduler: Any = None,
    ) -> "list[int] | tuple[list[int], list[float]] | tuple":
        if top_logprobs:
            logprobs = True  # alternatives imply the chosen-token values
        if sampler is None:
            from gofr_tpu.ops.sampling import Sampler

            sampler = Sampler()  # greedy
        # a request that will ask the decode pool for a seat holds one of
        # the pool's places from before its prefill to its end: past the
        # slots and the standing room it waits here, with nothing on the
        # device (DecodePool.gate)
        asks_pool = (
            decode_pool is not None and not sampler.seeded
            and max_new_tokens > 1
            and not self._spec_solo(sampler, logprobs, adapter, decode_pool)
        )
        with decode_pool.gate(stop) if asks_pool else contextlib.nullcontext():
            return self._generate(
                tokens, max_new_tokens, on_token, stop, sampler, stop_tokens,
                decode_pool, prefill_batcher, ttft_cb, logprobs,
                top_logprobs, adapter, adapter_params, scheduler,
            )

    def _spec_solo(self, sampler: Any, logprobs: bool,
                   adapter: Optional[str], decode_pool: Any) -> bool:
        """Whether a request takes the solo draft-and-verify path:
        requests with a configured draft do (DRAFT_MODEL_NAME opts the
        deployment into latency mode, so these requests bypass the
        throughput pool). Greedy emits exactly the target's argmax;
        sampled (unseeded, k >= 2) uses canonical speculative sampling —
        the emitted sequence is distributed exactly as the target's
        warped distribution, whatever the draft proposes. SPEC_POOLED
        opts the deployment into pooled speculation instead: the solo
        latency mode stands down and eligible requests speculate THROUGH
        the pool (which builds their n-gram draft state from spec_ctx)."""
        return (
            self.spec is not None and not sampler.penalized
            and not logprobs and adapter is None
            and getattr(decode_pool, "spec_cfg", None) is None
            and (sampler.greedy or (not sampler.seeded and self.spec.k >= 2))
        )

    def _generate(
        self, tokens: list[int], max_new_tokens: int, on_token: Any,
        stop: Any, sampler: Any, stop_tokens: Any, decode_pool: Any,
        prefill_batcher: Any, ttft_cb: Any, logprobs: bool,
        top_logprobs: bool, adapter: Optional[str],
        adapter_params: Optional[Any], scheduler: Any,
    ) -> "list[int] | tuple[list[int], list[float]] | tuple":
        """``generate`` behind the pool's gate: prefill, the first token,
        then the decode path the request's kind takes."""
        stop_tokens = frozenset(stop_tokens or ())
        ids = self.prepare(tokens)
        prm = self.params
        if adapter is not None:
            # ONE dict read: adapters can be unloaded at runtime, so a
            # membership check followed by a second lookup could race.
            # The streaming bridge passes the tree it pinned at its eager
            # pre-commit check (adapter_params) — a concurrent unload
            # must not fail a stream the transport already accepted.
            prm = (
                adapter_params if adapter_params is not None
                else self.adapters.get(adapter)
            )
            if prm is None:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(
                    f"adapter '{adapter}' (loaded: {sorted(self.adapters)})"
                )
            # adapter weights differ from the batch's: prefill solo (one
            # [1, bucket] row, bucket sized to the prompt but never past
            # the chunk budget) and skip the shared prefix cache/spec;
            # decode joins the pool below via its per-slot adapter bank
            a_bucket = self._bucket_for(int(ids.size))
            if self.prefill_chunk_bucket is not None:
                a_bucket = min(a_bucket, self.prefill_chunk_bucket)
            state = self._chunked_prefill(
                ids, prm, bucket=a_bucket, scheduler=scheduler
            )
        else:
            state = (
                self._prefix_lookup(
                    ids,
                    need_logits=(
                        logprobs or sampler.penalized or not sampler.greedy
                    ),
                )
                if self._prefix_cache is not None else None
            )
            if state is None:
                chunk_b = self.prefill_chunk_bucket
                if self._can_chunk_prefill() and (
                    ids.size > self.buckets[-1]
                    or (chunk_b is not None and ids.size > chunk_b)
                ):
                    # longer than the largest compiled bucket (slice
                    # through it instead of truncating — run_batch's
                    # batched path keeps the recency clip), or past the
                    # PREFILL_CHUNK_TOKENS budget: bounded-compute
                    # chunks through one warmed bucket executable,
                    # interleaved with decode by the scheduler
                    width = self.buckets[-1]
                    if chunk_b is not None:
                        width = min(width, chunk_b)
                    state = self._chunked_prefill(
                        ids, bucket=width, scheduler=scheduler
                    )
                elif prefill_batcher is not None:
                    state = prefill_batcher.infer(ids)
                else:
                    state = self.run_batch([ids])[0]
                if self._prefix_cache is not None:
                    self._prefix_store(ids, state)
        out: list[int] = []
        lps: list[float] = []
        tops: list = []  # per token: [(alt_id, alt_lp) x TOP_LOGPROBS]
        presence = counts = bias_row = None
        if sampler.penalized:
            token, presence, counts, bias_row = self._penalized_first(
                sampler, ids, state
            )
        elif sampler.greedy:
            token = state["next_token"]  # device-argmaxed; no logits fetch
        else:
            token = sampler.pick(state["logits"])
        if ttft_cb:
            ttft_cb()

        def _done():
            if top_logprobs:
                return out, lps, tops
            return (out, lps) if logprobs else out

        if token in stop_tokens:
            return _done()
        out.append(token)
        if logprobs:
            self._first_logprobs(state, token, top_logprobs, lps, tops)
        if on_token:
            # with logprobs, streaming consumers receive (token, logprob)
            on_token((token, lps[-1]) if logprobs else token)
        if max_new_tokens <= 1:
            return _done()

        pool_spec = getattr(decode_pool, "spec_cfg", None) is not None
        spec_solo = self._spec_solo(sampler, logprobs, adapter, decode_pool)
        # seed the prefix cache with the finish-time conversation KV (base
        # requests on an unsharded-batch cache): a follow-up turn then
        # reuses the WHOLE conversation's KV. ONE predicate for the
        # pooled, solo, AND speculative paths — they must never drift
        seed_kv = (
            self._prefix_cache is not None and adapter is None
            and self._can_chunk_prefill()
        )
        if spec_solo and sampler.greedy:
            out, spec_cache = self._spec_generate(
                state, ids, out, token, max_new_tokens, on_token, stop,
                stop_tokens,
            )
            if seed_kv:
                self._prefix_store_generation(ids, out, spec_cache, sampler)
            return out
        if spec_solo:
            out, spec_cache = self._spec_generate_sampled(
                state, ids, out, token, max_new_tokens, on_token, stop,
                stop_tokens, sampler,
            )
            if seed_kv:
                self._prefix_store_generation(ids, out, spec_cache, sampler)
            return out

        # continuous batching: unseeded requests decode in the shared pool
        # (seeded ones need the exact per-request key sequence — solo
        # path). Penalized requests join too (their presence/counts/bias
        # rows ride per-slot pool state; the pool raises Full while that
        # machinery is off or still building, and they solo below), and
        # so do logprobs requests — the chosen tokens' logprobs ride
        # every pool chunk, so best_of candidates and logprob evals share
        # the batch instead of decoding solo. ADAPTER requests join via
        # the pool's stacked bank (per-slot adapter selection); the pool
        # rejects them — and they solo — while the bank is off,
        # rebuilding, mesh-disabled, or a penalized slot is active. A
        # FULL pool rejects nobody: submit returns once a finishing
        # request has left this one a seat (its first token is out
        # already; the stream pauses until then).
        if decode_pool is not None and not sampler.seeded:
            import queue as queue_mod

            penalty = None
            if presence is not None:
                penalty = (
                    presence, counts, bias_row,
                    sampler.repetition_penalty, sampler.presence_penalty,
                    sampler.frequency_penalty,
                )
            try:
                slot_q = decode_pool.submit(
                    state["cache"], state["length"], token,
                    max_new_tokens - 1, sampler, stop,
                    stop_tokens=stop_tokens, penalty=penalty,
                    want_logprobs=logprobs, want_top_logprobs=top_logprobs,
                    adapter=adapter, want_kv=seed_kv,
                    spec_ctx=ids if pool_spec else None,
                )
            except (queue_mod.Full, RuntimeError):
                # an executable the pool does not run beside its rows,
                # or a closed pool -> solo decode below (the reason is on
                # the FlightRecord and gofr_tpu_pool_reject_total)
                slot_q = None
            record = telemetry_record()
            if record is not None:
                # a slot, after whatever wait for one, or the refusal
                record.mark_pool_admit()
            if slot_q is not None:
                state = None
                kv_row = self._consume_pool(
                    slot_q, out, lps, tops, logprobs, top_logprobs,
                    on_token, stop,
                )
                if kv_row is not None:
                    self._prefix_store_generation(ids, out, kv_row, sampler)
                return _done()
        cache = state["cache"]
        # cache holds exactly the prompt; each decode step writes one more
        # position, so the write head sits at cache_len
        cache_len = state["length"]
        state = None  # release the full-batch prefill buffers
        cache = self._solo_decode(
            prm, cache, cache_len, token, out, lps, tops, max_new_tokens,
            sampler, stop, stop_tokens, on_token, logprobs, top_logprobs,
            presence, counts, bias_row,
        )
        if seed_kv:
            # same conversation-KV seeding as the pooled path (the solo
            # final cache is private and no longer needed — donated)
            self._prefix_store_generation(ids, out, cache, sampler)
        return _done()

    def _solo_decode(
        self, prm: Any, cache: Any, cache_len: int, token: int, out: list,
        lps: list, tops: list, max_new_tokens: int, sampler: Any,
        stop: Any, stop_tokens: frozenset, on_token: Any, logprobs: bool,
        top_logprobs: bool, presence: Any, counts: Any, bias_row: Any,
    ) -> Any:
        """The solo chunked-decode tail of generate(): pipelined
        N-step dispatches with on-device sampling, host-side stop
        handling, and optional penalties/logprobs state threading.
        Mutates out/lps/tops in place (the caller drops its prefill
        state BEFORE calling, so the full-batch buffers release) and
        returns the final cache (every dispatched chunk's writes landed
        — the caller may seed the prefix cache from it).

        Chunked decode: N steps + on-device sampling per dispatch, one
        [1, N] fetch per chunk. Length is tracked on the
        HOST (prompt length + emitted count): reading cache["lengths"]
        back every step would cost a round trip per token.

        PIPELINED: the feed-forward token stays on device (the next
        chunk's input is this chunk's last sampled column), so chunk N+1
        dispatches before chunk N's tokens are fetched — the fetch
        overlaps the next chunk's compute instead of idling the device
        one round trip per chunk. Stop conditions lag by at most one
        speculative chunk, whose results are simply abandoned."""
        from collections import deque

        from gofr_tpu.deadline import current_deadline

        # the solo path honors the per-chunk decode expiry too: a
        # pool-rejected (adapter-mix, penalties off) request must not
        # decode unmetered past its budget just because it fell out of
        # the pool — same stage=decode contract as the pooled rows
        deadline = current_deadline()
        max_len = _cache_max_len(cache, self.cfg)
        temp, tk, tp = sampler.temperature, sampler.top_k, sampler.top_p
        mp = sampler.min_p
        pen = sampler.repetition_penalty
        ppen, fpen = sampler.presence_penalty, sampler.frequency_penalty
        pending: "deque" = deque()  # (toks_dev, ..., n_steps, drec)
        token_dev = jnp.asarray([[token]], jnp.int32)
        steps_in_flight = 0
        stopped = False
        # one DispatchRecord (kind decode_solo) per chunk: these chunks
        # share the device with the pool's, and without a record their
        # time shows in no program record at all
        timeline = self.timeline
        record = telemetry_record()
        issued = fetched = None  # the records being issued / delivered
        left_open = "error"  # how records a raise leaves open are closed
        try:
            while not stopped:
                while (
                    not (stop is not None and stop.is_set())
                    and len(pending) < 2
                    and steps_in_flight < max_new_tokens - len(out)
                    and cache_len + steps_in_flight < max_len
                ):
                    # always run the WARMED full chunk unless the cache
                    # boundary forces a short one — a max_new_tokens remainder
                    # must not compile a fresh scan length mid-request;
                    # surplus sampled tokens are simply discarded
                    n = min(self.decode_chunk_size, max_len - cache_len - steps_in_flight)
                    key = self._greedy_key if sampler.greedy else sampler.take_key()
                    fn = self._chunk_fns[(presence is not None, logprobs)]
                    # jit caches per (variant, scan length): a first use of
                    # an opt-in variant or remainder length compiles here
                    self._note_exec(
                        ("decode_chunk", presence is not None, logprobs, n)
                    )
                    if timeline is not None:
                        issued = timeline.begin(
                            "decode_solo", batch_size=1, tokens=n,
                        )
                        _note_queue_ahead(issued, self)
                        if record is not None:
                            record.note_dispatch_id(issued.dispatch_id)
                    with phase(SOLO_ISSUE, issued, end="t_issued"):
                        if presence is None:
                            result = fn(prm, token_dev, cache, key, temp,
                                        tk, tp, mp, n)
                        else:
                            result = fn(prm, token_dev, cache, key, temp,
                                        tk, tp, mp, presence, pen, counts,
                                        ppen, fpen, bias_row, n)
                    toks_dev, cache = result[0], result[1]
                    rest = list(result[2:])
                    if presence is not None:
                        presence = rest.pop(0)
                        counts = rest.pop(0)
                    if logprobs:
                        lps_dev, tvals_dev, tids_dev = rest[:3]
                    else:
                        lps_dev = tvals_dev = tids_dev = None
                    token_dev = toks_dev[:, -1:]
                    pending.append(
                        (toks_dev, lps_dev, tvals_dev, tids_dev, n, issued)
                    )
                    steps_in_flight += n
                if not pending:
                    break
                toks_dev, lps_dev, tvals_dev, tids_dev, n, fetched = (
                    pending.popleft()
                )
                with phase(
                    SOLO_FETCH_WAIT, fetched, start="t_fetch", end="t_fetched"
                ):
                    chunk = [int(t) for t in np.asarray(toks_dev)[0]]
                    chunk_lps = (
                        [float(x) for x in np.asarray(lps_dev)[0]]
                        if lps_dev is not None else None
                    )
                    chunk_tops = None
                    if top_logprobs:
                        tv = np.asarray(tvals_dev)[0]
                        ti = np.asarray(tids_dev)[0]
                        chunk_tops = [
                            [(int(ti[j, m]), float(tv[j, m]))
                             for m in range(ti.shape[-1])]
                            for j in range(ti.shape[0])
                        ]
                steps_in_flight -= n
                cache_len += n
                if deadline is not None and deadline.expired():
                    self._shed_solo_decode(deadline, len(out))
                take = min(n, max_new_tokens - len(out))
                for j, t in enumerate(chunk[:take]):
                    if t in stop_tokens:  # the request ends before it
                        take, stopped = j, True
                        break
                if record is not None:
                    record.note_delivered(take)  # what is handed on, no more
                for j, t in enumerate(chunk[:take]):
                    out.append(t)
                    if chunk_lps is not None:
                        lps.append(chunk_lps[j])
                    if chunk_tops is not None:
                        tops.append(chunk_tops[j])
                    if on_token:
                        on_token((t, chunk_lps[j]) if logprobs else t)
                    if stop is not None and stop.is_set():
                        stopped = True  # on_token may set stop mid-burst
                        break
                if len(out) >= max_new_tokens:
                    stopped = True
                if timeline is not None and fetched is not None:
                    timeline.finish(fetched)  # tokens handed on: delivered
            left_open = "abandoned"  # the speculative chunk past a stop
        finally:
            if timeline is not None:
                # finish() is idempotent: delivered chunks stay "ok"
                for drec in [issued, fetched] + [e[-1] for e in pending]:
                    if drec is not None:
                        timeline.finish(drec, status=left_open)
        return cache

    def _shed_solo_decode(self, deadline: Any, emitted: int) -> None:
        """Mid-flight expiry for the solo decode loop: same accounting
        as the pooled per-chunk check (stage ``decode``, cause
        ``deadline``, shed stage on the FlightRecord), then the
        504-mapped raise — pending speculative chunks are abandoned
        with the request."""
        from gofr_tpu.deadline import (
            cancellations_counter,
            deadline_exceeded_counter,
        )
        from gofr_tpu.errors import DeadlineExceeded

        if self.metrics is not None:
            deadline_exceeded_counter(self.metrics).inc(stage="decode")
            cancellations_counter(self.metrics).inc(cause="deadline")
        record = telemetry_record()
        if record is not None:
            record.note_shed("decode")
        raise DeadlineExceeded(
            f"deadline expired mid-decode after {emitted} tokens "
            f"(budget {deadline.budget_s * 1000:.0f} ms, solo path)",
            stage="decode",
        )

    def _can_chunk_prefill(self) -> bool:
        """Chunked prefill builds a [1]-row cache; under a mesh that only
        works when the cache's batch axis is unsharded (tp-only meshes)."""
        if self.mesh is None:
            return True
        return self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1) == 1

    def _chunked_prefill(
        self, ids: np.ndarray, params: Any = None,
        bucket: Optional[int] = None, scheduler: Any = None,
    ) -> dict:
        """Prefill a prompt LONGER than the largest compiled bucket (or
        the PREFILL_CHUNK_TOKENS budget) by running it through a bucket
        in slices, each writing into the same [1]-row cache at its ragged
        start offset — the exact cached forward decode already uses. One
        compiled [1, bucket] shape serves any prompt length up to
        max_seq, so a deployment can restrict MODEL_BUCKETS (fast cold
        boot) without truncating long prompts, and no single dispatch
        occupies the device longer than one bucket's compute. ONE host
        fetch at the end (the last chunk's argmax). ``bucket`` overrides
        the chunk width (adapter requests size it to the prompt so short
        prompts never pay top-bucket FLOPs). ``scheduler`` interleaves
        each chunk with pooled decode turns (tpu/scheduler.py) and the
        chunk count/defer land on the request's FlightRecord."""
        if self._state_prefill_gate is not None:
            # a state row is the same size whatever the prompt (Brumby: 0.27
            # GB, and a slice holds the state it came from beside the one it
            # makes): the device runs slices one after another anyway, so
            # letting only two prompts hold rows at once costs no time and
            # bounds what a burst of long prompts can take
            with self._state_prefill_gate:
                return self._chunked_prefill_slices(ids, params, bucket, scheduler)
        return self._chunked_prefill_slices(ids, params, bucket, scheduler)

    def _chunked_prefill_slices(
        self, ids: np.ndarray, params: Any, bucket: Optional[int],
        scheduler: Any,
    ) -> dict:
        bucket = bucket or self.buckets[-1]
        # the shared zero cache: prefill never mutates its input, so every
        # chunked request can start from the same [1]-row allocation
        cache = self._zero_cache(1)
        logits = next_ids = issued_before = None
        routed: list = []  # (record, ids) of the slices before the last
        total = 0
        prm = self.params if params is None else params
        record = telemetry_record()
        if record is not None:
            # the chunked path has no batcher queue, but the spine marks
            # must not go null for exactly the requests the budget
            # targets: enqueue/dispatch are stamped here (queue_wait ~ 0;
            # scheduler waits land in sched_defer_s, same split as the
            # batched path)
            record.mark_enqueue()
            record.mark_dispatch(1)
        drec = None
        try:
            for tokens, lengths, size in _prompt_chunks(ids, bucket):
                if scheduler is not None:
                    wait = scheduler.admit_prefill(
                        bucket, program=("prefill_chunk", bucket))
                    if record is not None and wait:
                        record.note_sched_defer(wait)
                if self.timeline is not None:
                    # dispatch timeline: one record per slice. Marks are
                    # host/dispatch-side (jax dispatch is async): each
                    # slice closes when the next dispatches; the LAST
                    # stays "running" through the blocking fetch below,
                    # so a wedge shows as that slice stuck on
                    # /admin/dispatches.
                    if drec is not None:
                        self.timeline.finish(drec)
                        routed.append((drec, next_ids))
                    drec = self.timeline.begin(
                        "prefill_chunk", bucket=bucket, batch_size=1,
                        tokens=size,
                    )
                    _note_queue_ahead(drec, self)
                    drec.carried = total > 0
                    if self._latent_token_bytes:
                        # the carried latent and this slice's own
                        drec.latent_bytes = (total + size) * self._latent_token_bytes
                    if record is not None:
                        record.note_dispatch_id(drec.dispatch_id)
                with phase(PREFILL_ISSUE, drec, end="t_issued"):
                    logits, next_ids, cache = self._prefill(
                        prm, tokens, cache, lengths
                    )
                if self._state_prefill_gate is not None:
                    # a slice's output is allocated when it is enqueued, so
                    # a 12-slice prompt issued at once holds 12 states (3.3
                    # GB of Brumby's). Wait for the slice BEFORE the one
                    # just issued: one runs, one is queued, three states live
                    if issued_before is not None:
                        issued_before.block_until_ready()
                    issued_before = cache["lengths"]
                if record is not None:
                    record.note_prefill_chunk(bucket=bucket)
                total += size
            # ONE blocking fetch synchronizes every dispatched slice —
            # the point a wedged device manifests, so it runs under the
            # watchdog
            watch = (
                self.watchdog.watch(
                    "prefill_chunk",
                    drec.dispatch_id if drec is not None else 0,
                )
                if self.watchdog is not None else _NULLCTX
            )
            with watch, phase(
                PREFILL_FETCH_WAIT, drec, start="t_fetch", end="t_fetched"
            ):
                next_ids = np.asarray(next_ids)
            next_token = int(_note_routing(drec, next_ids, 1, self.cfg)[0])
            if self.cfg.routed:
                # the earlier slices are done: their ids are there to read
                for earlier, ids in routed:
                    _note_routing(earlier, np.asarray(ids), 1, self.cfg)
        except BaseException:
            # a raising slice dispatch (or fetch) must not leak the open
            # record as a phantom "running" dispatch
            if self.timeline is not None and drec is not None:
                self.timeline.finish(drec, status="error")
            raise
        if self.timeline is not None and drec is not None:
            self.timeline.finish(drec)
        return {
            "cache": cache,
            "length": total,
            "next_token": next_token,
            "logits": logits[0],
        }

    def _penalized_first(
        self, sampler: Any, ids: np.ndarray, state: Any
    ) -> tuple:
        """First-token pick under penalties -> (token, presence, counts,
        bias_row). Context presence penalizes the FIRST token too (greedy
        argmax included), so the device-argmaxed id is not usable; the
        additive presence/frequency penalties count GENERATED tokens only,
        so their counts row starts at zero here — logit_bias, by contrast,
        applies to every step including this first one."""
        from gofr_tpu.ops.sampling import (
            apply_penalties,
            bias_row_from_map,
            presence_from_tokens,
            update_counts,
            update_presence,
        )

        presence = presence_from_tokens(ids, self.cfg.vocab_size)
        counts = jnp.zeros(presence.shape, jnp.float32)
        if sampler.logit_bias:
            try:
                bias_row = bias_row_from_map(
                    sampler.logit_bias, self.cfg.vocab_size
                )
            except ValueError as exc:
                from gofr_tpu.errors import InvalidParamError

                raise InvalidParamError(str(exc)) from None
        else:
            bias_row = jnp.zeros(presence.shape, jnp.float32)
        logits_pen = apply_penalties(
            jnp.asarray(state["logits"])[None, :], presence,
            sampler.repetition_penalty, counts,
            sampler.presence_penalty, sampler.frequency_penalty,
            bias_row,
        )
        token = sampler.pick(logits_pen)
        first = jnp.asarray([token])
        return (
            token, update_presence(presence, first),
            update_counts(counts, first), bias_row,
        )

    def _first_logprobs(
        self, state: Any, token: int, top_logprobs: bool,
        lps: list, tops: list,
    ) -> None:
        """Append the first token's RAW model logprob (and, opt-in, its
        top-k alternatives). Chosen-only requests index on DEVICE and move
        one scalar (the [V] row transfer would sit on the TTFT path); only
        top_logprobs pays the full-row fetch, and argpartition beats a
        full sort for 5."""
        row_dev = jax.nn.log_softmax(
            jnp.asarray(state["logits"]).astype(jnp.float32)
        )
        if top_logprobs:
            from gofr_tpu.models.transformer import TOP_LOGPROBS

            row = np.asarray(row_dev)
            lps.append(float(row[token]))
            part = np.argpartition(row, -TOP_LOGPROBS)[-TOP_LOGPROBS:]
            top_ids = part[np.argsort(row[part])[::-1]]
            tops.append([(int(i), float(row[i])) for i in top_ids])
        else:
            lps.append(float(row_dev[token]))

    def _consume_pool(
        self, slot_q: Any, out: list, lps: list, tops: list,
        logprobs: bool, top_logprobs: bool, on_token: Any, stop: Any,
    ) -> Optional[dict]:
        """Drain a decode-pool slot queue into out/lps/tops, re-raising a
        worker failure and honoring caller cancellation (emission stops
        immediately; the pool frees the slot at its next delivery — it
        checks stop too). Returns the finish-time KV row when the submit
        asked for one (("kv", row) precedes DONE), else None."""
        from gofr_tpu.tpu.decode_pool import DEADLINE, DONE, PoolFailure

        kv_row = None
        while True:
            item = slot_q.get()
            if item is DONE:
                return kv_row
            if item is DEADLINE:
                # the pool expired this row mid-decode (slot + KV
                # already freed); surface the 504, never a silently
                # truncated "ok" stream
                from gofr_tpu.errors import DeadlineExceeded

                raise DeadlineExceeded(
                    "request deadline exceeded mid-decode "
                    f"(after {len(out)} tokens)", stage="decode",
                )
            if isinstance(item, PoolFailure):
                raise item.exc
            if isinstance(item, tuple) and item and item[0] == "kv":
                kv_row = item[1]
                continue
            for t in item:  # one burst list per decoded chunk
                if logprobs:
                    t, lp, t_tops = t
                    lps.append(lp)
                    if top_logprobs and t_tops is not None:
                        tops.append(t_tops)
                out.append(t)
                if on_token:
                    on_token((t, lps[-1]) if logprobs else t)
                if stop is not None and stop.is_set():
                    return None  # cancelled: the row may still be mid-write

    def _prefix_lookup(
        self, ids: np.ndarray, need_logits: bool = False
    ) -> Optional[dict]:
        """Prompt lookup -> a private state (copied cache row; shared
        read-only logits) or None. Exact match skips prefill entirely;
        otherwise the entry sharing the longest common token prefix (of at
        least ``_prefix_lcp_min``) seeds a tail-only prefill. LRU order
        updates on either kind of hit. ``need_logits``: the caller samples
        or scores from the final-position logits — stored GENERATION
        entries carry none, so they divert to the LCP tail-prefill (which
        re-derives the logits) instead of exact-hitting."""
        if self._paged_prefix is not None:
            return self._paged_lookup(ids, need_logits)
        key = ids.tobytes()
        with self._prefix_lock:
            entry = self._prefix_cache.get(key)
            if entry is not None and (
                (entry[3] is None and need_logits)
                or entry[2] is None  # no trustworthy next_token stored
            ):
                entry = None
            if entry is not None:
                self._prefix_cache.move_to_end(key)
                self.prefix_stats["hits"] += 1
            else:
                shared, row = (
                    self._lcp_scan(ids)
                    if self._prefix_lcp_min >= 0 and self._can_chunk_prefill()
                    else (0, None)
                )
                if row is None:
                    self.prefix_stats["misses"] += 1
                    self._cache_events("prefix", "miss")
                    return None
                self.prefix_stats["partial_hits"] += 1
        self._cache_events("prefix", "hit" if entry is not None else "partial_hit")
        if entry is not None:  # device work outside the lock
            row, length, next_token, logits = entry
            return {
                "cache": self._copy_row(row),
                "length": length,
                "next_token": next_token,
                "logits": logits,
            }
        return self._tail_prefill(
            ids,
            _cache_with_len(self._copy_row(row), jnp.asarray(shared, jnp.int32)),
            shared,
        )

    def _paged_lookup(
        self, ids: np.ndarray, need_logits: bool
    ) -> Optional[dict]:
        """Block-table prefix lookup (KV_PAGED): exact hits GATHER the
        entry's blocks into a fresh compute row (the blocks stay shared
        — no stored-row duplicate exists to copy); LCP partial hits
        gather only the shared prefix and resume with the same tail
        prefill as the row path. Divert rules (need_logits, untrusted
        next_token) are identical to the row store's."""
        hit = self._paged_prefix.lookup(ids, need_logits)
        if hit is None:
            self._cache_events("prefix", "miss")
            return None
        kind, payload, shared = hit
        if kind == "hit":
            self._cache_events("prefix", "hit")
            return payload
        self._cache_events("prefix", "partial_hit")
        return self._tail_prefill(ids, payload, shared)

    def _lcp_scan(self, ids: np.ndarray) -> tuple:
        """Under ``_prefix_lock``: find the entry with the longest common
        token prefix. The shared length is capped at ``ids.size - 1`` so
        the tail always keeps >= 1 token — the final-position logits and
        next_token come from prefilling the tail, never from the entry
        (whose continuation belongs to a DIFFERENT prompt). Linear scan:
        the cache holds PREFIX_CACHE (tens of) entries and one numpy
        compare per entry is nanoseconds against the prefill it saves."""
        from gofr_tpu.tpu.kv_blocks import lcp_scan

        shared, key, entry = lcp_scan(
            list(self._prefix_cache.items()), ids, int(ids.size) - 1,
            self._prefix_lcp_min,
        )
        if entry is None:
            return 0, None
        self._prefix_cache.move_to_end(key)
        return shared, entry[0]

    def _tail_prefill(self, ids: np.ndarray, cache: Any, shared: int) -> dict:
        """Resume prefill from a shared-prefix cache: ``cache`` is a
        PRIVATE [1]-row cache whose write head sits at ``shared`` (the
        row path passes a rolled-back copy of the stored row; the paged
        path passes a gathered block-table row), and only the tail runs
        through the bucketed prefill at its ragged offset — the same
        mechanics as chunked prefill. Stale KV past ``shared`` is
        masked by attention (lengths bounds the valid prefix) and
        overwritten as the tail lands. The completed full-prompt state is
        stored for future exact hits."""
        tail = ids[shared:]
        bucket = self._bucket_for(int(tail.size))
        logits = next_ids = None
        total = shared
        # same observability contract as _chunked_prefill: the tail
        # prefill is a device dispatch too — one timeline record for the
        # tail, the blocking fetch under the watchdog, so a wedge on the
        # prefix-cache partial-hit path is diagnosed, not silent
        drec = None
        if self.timeline is not None:
            drec = self.timeline.begin(
                "prefill_chunk", bucket=bucket, batch_size=1,
                tokens=int(tail.size),
                detail=f"tail prefill after {shared} shared",
            )
            rec = telemetry_record()
            if rec is not None:
                rec.note_dispatch_id(drec.dispatch_id)
        try:
            for tokens, lengths, size in _prompt_chunks(tail, bucket):
                logits, next_ids, cache = self._prefill(
                    self.params, tokens, cache, lengths
                )
                total += size
            watch = (
                self.watchdog.watch(
                    "prefill_chunk",
                    drec.dispatch_id if drec is not None else 0,
                )
                if self.watchdog is not None else _NULLCTX
            )
            with watch:
                next_token = int(np.asarray(next_ids)[0])
        except BaseException:
            if self.timeline is not None and drec is not None:
                self.timeline.finish(drec, status="error")
            raise
        if self.timeline is not None and drec is not None:
            self.timeline.finish(drec)
        state = {
            "cache": cache,
            "length": total,
            "next_token": next_token,
            "logits": logits[0],
        }
        self._prefix_store(ids, state)
        return state

    def _prefix_store_generation(
        self, ids: np.ndarray, out: list, row: Any, sampler: Any
    ) -> None:
        """Seed the prefix cache with the WHOLE conversation (prompt +
        generated reply): a follow-up turn (prompt + reply + new message)
        then LCP-hits everything already computed instead of re-prefilling
        the conversation — the multi-turn chat shape. The final generated
        token's KV may not be written yet (it was sampled but possibly
        never fed back), so the entry covers prompt + out[:-1] with
        out[-1] as its next_token — but ONLY when out[-1] is the plain
        greedy continuation (unpenalized argmax): a sampled or
        bias-warped token exact-served to a later greedy request would
        break its bit-exactness vs a cache-off device, so such entries
        store next_token=None and exact hits divert to the LCP
        tail-prefill (KV reuse is token-content-determined and stays
        valid either way). Stored generations carry no logits;
        logits-needing lookups divert the same way. ``row`` must be
        private (pool hand-back copy or the solo final cache) — its
        write head is rolled back in place (donated)."""
        if len(out) < 2 or self._prefix_cache is None:
            return
        full = np.concatenate(
            [ids, np.asarray(out[:-1], np.int32)]
        )
        if full.size > self.cfg.max_seq:
            return
        exactable = sampler.greedy and not sampler.penalized
        if self._paged_prefix is not None:
            # block-table store: alias the whole blocks of the longest
            # cached prefix this conversation extends (typically the
            # prompt's own prefill entry) and scatter only the new tail
            # — the at-rest copy collapses from a max_seq row to the
            # reply's blocks
            self._paged_prefix.store_generation(full, row, exactable, out)
            return
        entry_row = _cache_with_len(
            row, jnp.asarray(int(full.size), jnp.int32)
        )
        entry = (
            entry_row, int(full.size),
            int(out[-1]) if exactable else None, None,
        )
        with self._prefix_lock:
            self._prefix_cache[full.tobytes()] = entry
            while len(self._prefix_cache) > self._prefix_cache_size:
                self._prefix_cache.popitem(last=False)

    def _prefix_store(self, ids: np.ndarray, state: Any) -> None:
        """Store this prompt's prefill result (copied row — the live row
        continues into decode); evict least-recently-used beyond the
        configured size."""
        if self._paged_prefix is not None:
            # scatter only the prompt's blocks into the arena — the
            # ~max_seq-row copy (and residency) of the row store is the
            # exact cost this path deletes
            self._paged_prefix.store(ids, state)
            return
        entry = (
            self._copy_row(state["cache"]),
            state["length"],
            state["next_token"],
            state["logits"],
        )
        with self._prefix_lock:
            self._prefix_cache[ids.tobytes()] = entry
            while len(self._prefix_cache) > self._prefix_cache_size:
                self._prefix_cache.popitem(last=False)

    def _spec_emit_fn(
        self, out: list[int], on_token: Any, stop: Any,
        stop_tokens: frozenset, max_new_tokens: int,
    ) -> Any:
        """The one emit helper both spec paths share: append tokens,
        honoring stop tokens / budget / cancellation; True = keep going."""

        record = telemetry_record()

        def emit(tokens_host: list[int]) -> bool:
            if record is not None:
                # what is handed on, no more: up to a stop token or the budget
                ends = [j for j, t in enumerate(tokens_host) if t in stop_tokens]
                record.note_delivered(
                    min(ends[0] if ends else len(tokens_host), max_new_tokens - len(out))
                )
            for t in tokens_host:
                if t in stop_tokens:
                    return False
                out.append(t)
                if on_token:
                    on_token(t)
                if len(out) >= max_new_tokens:
                    return False
                if stop is not None and stop.is_set():
                    return False
            return True

        return emit

    def _spec_prefill_draft(self, ids: np.ndarray) -> dict:
        """Draft-cache prefill mirroring the target's chunk/clip policy."""
        chunked = ids.size > self.buckets[-1] and self._can_chunk_prefill()
        return self.spec.prefill_prompt(
            ids,
            self.buckets[-1] if chunked else self._bucket_for(int(ids.size)),
            chunked,
        )

    def _spec_tail(
        self, cache: Any, cache_len: int, max_len: int, token: int,
        out: list[int], max_new_tokens: int, emit: Any, stop: Any,
        key_fn: Any, temp: float, tk: int, tp_: float, mp: float,
    ) -> Any:
        """Capacity-tail fallback both spec paths share: the cache got
        too full for a verify but budget remains — finish with plain
        single-step decodes through the already-warmed n=1 chunk (the
        sampling knobs are dynamic operands, so greedy and sampled use
        the same executable). Returns the FINAL cache — _set_cache_len
        donates its input, so the caller's reference dies here and the
        conversation-KV store needs the live one."""
        if not (
            len(out) < max_new_tokens
            and not (stop is not None and stop.is_set())
            and cache_len < max_len
        ):
            return cache
        cache = self._set_cache_len(cache, cache_len)
        while (
            len(out) < max_new_tokens
            and not (stop is not None and stop.is_set())
            and cache_len < max_len
        ):
            toks, cache = self._decode_chunk(
                self.params, jnp.asarray([[token]], jnp.int32), cache,
                key_fn(), temp, tk, tp_, mp, 1,
            )
            token = int(np.asarray(toks)[0, 0])
            cache_len += 1
            if not emit([token]):
                break
        return cache

    def _spec_generate(
        self,
        state: Any,
        ids: np.ndarray,
        out: list[int],
        token: int,
        max_new_tokens: int,
        on_token: Any,
        stop: Any,
        stop_tokens: frozenset,
    ) -> tuple:
        """Greedy speculative decode: per cycle, ONE draft chunk proposes
        k tokens, ONE target forward verifies all of them, ONE [k+2] fetch
        returns the target's argmaxes plus the on-device accepted count —
        so an accepted prefix of n tokens costs the target a single
        weight stream instead of n. Every emitted token is the target's
        own argmax under the verify computation (accepted drafts equal it
        by construction), so output never depends on draft quality; with
        matched numerics this reproduces plain greedy decode exactly
        (asserted in tests — note the verify matmuls run at [B, k+1]
        shapes, so near-tie bf16 logits can in principle flip an argmax
        vs the [B, 1] decode shapes). Acceptance is capped at k-1 so the
        draft cache always contains the committed prefix (its chunk
        writes k positions)."""
        spec = self.spec
        k = spec.k
        cache = state["cache"]
        cache_len = state["length"]
        state = None
        max_len = _cache_max_len(cache, self.cfg)
        dcache = self._spec_prefill_draft(ids)
        stats = self.spec_stats
        emit = self._spec_emit_fn(out, on_token, stop, stop_tokens,
                                  max_new_tokens)

        while (
            len(out) < max_new_tokens
            and not (stop is not None and stop.is_set())
            and cache_len + k + 1 <= max_len
        ):
            token_dev = jnp.asarray([[token]], jnp.int32)
            draft_toks, dcache = spec.propose(token_dev, dcache)  # [1, k]
            verify_in = jnp.concatenate([token_dev, draft_toks], axis=1)
            next_ids, cache = self._verify(self.params, verify_in, cache)
            # on-device acceptance count: leading draft tokens equal to the
            # target's argmax at the same position; packed with the ids so
            # the cycle costs ONE host fetch
            matches = (next_ids[:, :k] == draft_toks).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
            packed = np.asarray(jnp.concatenate([next_ids, n_acc[:, None]], axis=1))
            a = packed[0, : k + 1]
            # the UNCLAMPED on-device match count feeds the acceptance
            # gauge (the budget clamp below would bias it low on short
            # generations — it reflects emission room, not draft quality)
            n_match = int(packed[0, k + 1])
            # cap at k-1: the draft chunk wrote k positions, so the draft
            # cache can hold at most k committed tokens (t + k-1 drafts)
            n_use = min(n_match, k - 1, max_new_tokens - len(out) - 1)
            n_use = max(n_use, 0)
            with self._spec_lock:
                stats["cycles"] += 1
                stats["drafted"] += k
                stats["accepted"] += n_match
            # emitted tokens a[0..n_use]: n_use accepted drafts + the bonus
            keep_going = emit([int(t) for t in a[: n_use + 1]])
            cache_len += 1 + n_use  # t plus the accepted drafts are committed
            if not keep_going:
                break
            cache = self._set_cache_len(cache, cache_len)
            dcache = spec.reset_len(dcache, cache_len)
            token = int(a[n_use])  # bonus token: emitted, not yet in cache
        else:
            # natural exhaustion only (a break above means a stop
            # condition already fired)
            cache = self._spec_tail(
                cache, cache_len, max_len, token, out, max_new_tokens,
                emit, stop, lambda: self._greedy_key, 0.0, 0, 1.0, 0.0,
            )
        return out, cache

    def _spec_generate_sampled(
        self,
        state: Any,
        ids: np.ndarray,
        out: list[int],
        token: int,
        max_new_tokens: int,
        on_token: Any,
        stop: Any,
        stop_tokens: frozenset,
        sampler: Any,
    ) -> tuple:
        """Speculative SAMPLING (temperature > 0): per cycle the draft
        proposes k sampled tokens with their warped distributions q, the
        target verifies k-1 of them in one forward with the canonical
        accept test (u < p/q) and residual resampling — every emitted
        token is distributed exactly as sampling the target's warped p,
        whatever the draft proposes (draft quality only sets acceptance).
        Cache accounting mirrors the greedy path: the draft chunk writes
        k positions (pending + k-1 drafts), so at most k-1 drafts commit
        per cycle and the correction/bonus becomes the next pending
        token."""
        spec = self.spec
        kd = spec.k - 1  # drafts tested per cycle
        cache = state["cache"]
        cache_len = state["length"]
        state = None
        max_len = _cache_max_len(cache, self.cfg)
        dcache = self._spec_prefill_draft(ids)
        stats = self.spec_stats
        temp, tk, tp_ = sampler.temperature, sampler.top_k, sampler.top_p
        mp = sampler.min_p
        # independent keys for draft and verify: the acceptance math is
        # exact for ANY draft randomness, and unseeded requests carry no
        # reproducibility contract (seeded ones decode solo)
        import secrets

        dkey = jax.random.key(secrets.randbits(63))
        vkey = jax.random.key(secrets.randbits(63))
        emit = self._spec_emit_fn(out, on_token, stop, stop_tokens,
                                  max_new_tokens)

        while (
            len(out) < max_new_tokens
            and not (stop is not None and stop.is_set())
            and cache_len + kd + 1 <= max_len
        ):
            token_dev = jnp.asarray([[token]], jnp.int32)
            draft_toks, qs, dkey, dcache = spec.propose_sampled(
                token_dev, dcache, dkey, temp, tk, tp_, mp
            )  # [1, k], [1, k, V]
            verify_in = jnp.concatenate(
                [token_dev, draft_toks[:, :kd]], axis=1
            )  # [1, kd+1]
            emitted_dev, n_acc_dev, vkey, cache = self._verify_sampled(
                self.params, verify_in, cache, draft_toks[:, :kd],
                qs[:, :kd], vkey, temp, tk, tp_, mp,
            )
            packed = np.asarray(
                jnp.concatenate([emitted_dev, n_acc_dev[:, None]], axis=1)
            )  # ONE host fetch per cycle
            row = packed[0, : kd + 1]
            n_acc = int(packed[0, kd + 1])
            n_use = max(min(n_acc, max_new_tokens - len(out) - 1), 0)
            with self._spec_lock:
                stats["cycles"] += 1
                stats["drafted"] += kd
                stats["accepted"] += n_acc
            # row[:n_use] accepted drafts + row[n_use] correction/bonus
            # (or, under the budget clamp, an accepted draft — equally a
            # sample from p); the last emitted token becomes the pending
            # one and is NOT yet in the cache
            keep_going = emit([int(t) for t in row[: n_use + 1]])
            cache_len += 1 + n_use
            if not keep_going:
                break
            cache = self._set_cache_len(cache, cache_len)
            dcache = spec.reset_len(dcache, cache_len)
            token = int(row[n_use])
        else:
            cache = self._spec_tail(
                cache, cache_len, max_len, token, out, max_new_tokens,
                emit, stop, sampler.take_key, temp, tk, tp_, mp,
            )
        return out, cache

    def warmup(self, progress: Any = None) -> None:
        # one compiled prefill per sequence bucket (batch fixed at
        # max_batch), plus the b=1 decode step — nothing compiles on the
        # serving path afterwards
        b = next_pow2(self.max_batch)
        for i, bucket in enumerate(self.buckets):
            if progress:
                progress(
                    f"compiling prefill bucket {bucket} (batch {b}, "
                    f"{i + 1}/{len(self.buckets)})",
                    kind="prefill", bucket=bucket,
                )
            self._seed_exec(("prefill", bucket, b))
            cache = self._zero_cache(b)
            tokens = jnp.zeros((b, bucket), jnp.int32)
            lengths = jnp.ones((b,), jnp.int32)
            if self._token_sharding is not None:
                # jit caches on input shardings: warm with the EXACT
                # placement run_batch uses or every bucket recompiles on
                # its first real request
                tokens = jax.device_put(tokens, self._token_sharding)
                lengths = jax.device_put(lengths, self._row_sharding)
            logits, next_ids, cache = self._prefill(self.params, tokens, cache, lengths)
            next_ids.block_until_ready()
        if self.buckets[-1] < self.cfg.max_seq and self._can_chunk_prefill():
            # prompts beyond the top bucket take the chunked-prefill path:
            # warm its [1, bucket] shape so it never compiles mid-request
            if progress:
                progress(
                    f"compiling chunked prefill ([1, {self.buckets[-1]}])",
                    kind="prefill_chunk", bucket=self.buckets[-1],
                )
            state = self._chunked_prefill(
                np.ones((self.buckets[-1] + 1,), np.int32)
            )
            del state
        chunk_b = self.prefill_chunk_bucket
        if (
            chunk_b is not None and chunk_b < self.cfg.max_seq
            and self._can_chunk_prefill()
            # the block above already warmed exactly this shape when the
            # budget resolves to the top bucket — don't pay it twice
            and not (
                chunk_b == self.buckets[-1]
                and self.buckets[-1] < self.cfg.max_seq
            )
        ):
            # the PREFILL_CHUNK_TOKENS budget routes over-budget prompts
            # through [1, chunk_b] slices — warm that shape too
            if progress:
                progress(
                    f"compiling budgeted chunked prefill ([1, {chunk_b}])",
                    kind="prefill_chunk", bucket=chunk_b,
                )
            state = self._chunked_prefill(
                np.ones((chunk_b + 1,), np.int32), bucket=chunk_b
            )
            del state
        if progress:
            progress("compiling decode step", kind="decode_step")
        one = _slice_cache(cache, 0)
        self._warmup_prefix(progress, one)
        self._warmup_adapters(progress)
        step, _ = self._decode(self.params, jnp.zeros((1, 1), jnp.int32), one)
        step.block_until_ready()
        # warm the full decode chunk (remainder sizes compile on demand)
        if progress:
            progress(
                f"compiling decode chunk ({self.decode_chunk_size} steps)",
                kind="decode_chunk",
            )
        self._seed_exec(
            ("decode_chunk", False, False, self.decode_chunk_size)
        )
        toks, _ = self._decode_chunk(
            self.params, jnp.zeros((1, 1), jnp.int32), one,
            jax.random.key(0), 0.0, 0, 1.0, 0.0, self.decode_chunk_size,
        )
        toks.block_until_ready()
        self._warmup_spec(progress, one)

    def _warmup_prefix(self, progress: Any, one: dict) -> None:
        """Prefix-cache warm stage: the row copy and, under LCP, the
        per-bucket tail prefills; probe entries purged so serving
        starts empty."""
        if self._prefix_cache is not None:
            # prefix-cache row copies must not compile on the serving path
            self._copy_row(one)["lengths"].block_until_ready()
            if self._prefix_lcp_min >= 0 and self._can_chunk_prefill():
                # partial (shared-prefix) hits tail-prefill at [1, bucket]
                # per bucket plus the 1-row length rollback — warm both so
                # the feature built to CUT TTFT never pays a mid-request
                # compile (the warmup contract above)
                for i, b_ in enumerate(self.buckets):
                    if progress:
                        progress(
                            f"compiling tail prefill bucket {b_} "
                            f"({i + 1}/{len(self.buckets)})",
                            kind="tail_prefill", bucket=b_,
                        )
                    # tail of b_-1 tokens lands in bucket b_ (> previous
                    # bucket); total stays within max_seq
                    st = self._tail_prefill(
                        np.ones((b_,), np.int32),
                        _cache_with_len(
                            self._copy_row(one), jnp.asarray(1, jnp.int32)
                        ),
                        1,
                    )
                    del st
                # the warmup probes above polluted the cache with fake
                # prompt entries — serving must start empty
                with self._prefix_lock:
                    self._prefix_cache.clear()
                    self.prefix_stats.update(hits=0, partial_hits=0, misses=0)

    def _warmup_adapters(self, progress: Any) -> None:
        """Adapter warm stage: one prefill per bucket + the decode
        chunk on a wrapped tree (shared by every adapter)."""
        if self.adapters:
            # LoRA-wrapped trees have a different pytree structure, so the
            # adapter prefill/decode executables are separate compiles —
            # ONE each, shared by every adapter (same structure)
            any_tree = next(iter(self.adapters.values()))
            for i, b_ in enumerate(self.buckets):
                if progress:
                    progress(
                        f"compiling adapter prefill bucket {b_} "
                        f"({i + 1}/{len(self.buckets)})",
                        kind="adapter_prefill", bucket=b_,
                    )
                st = self._chunked_prefill(
                    np.ones((4,), np.int32), any_tree, bucket=b_
                )
            if progress:
                progress("compiling adapter decode chunk", kind="adapter_decode")
            a_toks = self._decode_chunk(
                any_tree, jnp.zeros((1, 1), jnp.int32), st["cache"],
                self._greedy_key, 0.0, 0, 1.0, 0.0, self.decode_chunk_size,
            )[0]
            a_toks.block_until_ready()

    def _warmup_spec(self, progress: Any, one: dict) -> None:
        """Speculative-decoding warm stage: draft prefills per bucket,
        the greedy draft chunk + verify, the n=1 capacity-tail chunk,
        and (k >= 2) the sampled draft chunk + sampled verify."""
        if self.spec is not None:
            # speculative path: draft prefill per bucket, draft chunk, and
            # the target verify — nothing compiles on the serving path
            spec = self.spec
            for i, bucket in enumerate(self.buckets):
                if progress:
                    progress(
                        f"compiling draft prefill bucket {bucket} "
                        f"({i + 1}/{len(self.buckets)})",
                        kind="draft_prefill", bucket=bucket,
                    )
                dcache = spec.prefill_prompt(np.ones((4,), np.int32), bucket, False)
            if progress:
                progress(
                    f"compiling draft chunk + verify (k={spec.k})",
                    kind="spec_verify",
                )
            dtoks, dcache = spec.propose(jnp.zeros((1, 1), jnp.int32), dcache)
            verify_in = jnp.concatenate([jnp.zeros((1, 1), jnp.int32), dtoks], axis=1)
            vids, vcache = self._verify(self.params, verify_in, one)
            vids.block_until_ready()
            spec.reset_len(dcache, 1)
            # the capacity-tail fallback decodes single steps: warm the
            # n=1 chunk shape so it never compiles on the serving path
            self._seed_exec(("decode_chunk", False, False, 1))
            t1, vcache = self._decode_chunk(
                self.params, jnp.zeros((1, 1), jnp.int32), vcache,
                self._greedy_key, 0.0, 0, 1.0, 0.0, 1,
            )
            t1.block_until_ready()
            # _cache_with_len donates: keep the RESULT for the sampled
            # warm below (the input array is deleted)
            vcache = self._set_cache_len(vcache, 1)
            if spec.k >= 2:
                # speculative SAMPLING executables (draft sampled chunk +
                # sampled verify): the first unseeded temperature>0
                # request must not pay two full-model compiles.
                # reset_len DONATES its input — rebuild the throwaway
                # draft cache rather than reuse a deleted array
                if progress:
                    progress(
                        "compiling sampled draft chunk + verify",
                        kind="spec_verify_sampled",
                    )
                dcache = spec.prefill_prompt(
                    np.ones((4,), np.int32), self.buckets[0], False
                )
                stoks, sq, _, dcache = spec.propose_sampled(
                    jnp.zeros((1, 1), jnp.int32), dcache,
                    jax.random.key(0), 1.0, 0, 1.0, 0.0,
                )
                sin = jnp.concatenate(
                    [jnp.zeros((1, 1), jnp.int32), stoks[:, : spec.k - 1]],
                    axis=1,
                )
                se, _, _, _ = self._verify_sampled(
                    self.params, sin, vcache, stoks[:, : spec.k - 1],
                    sq[:, : spec.k - 1], jax.random.key(1), 1.0, 0, 1.0, 0.0,
                )
                se.block_until_ready()


def _prompt_chunks(ids: np.ndarray, bucket: int):
    """Slice a prompt into [1, bucket] zero-padded token rows with true
    lengths — the ONE chunking used by both the target's chunked prefill
    and the draft engine's, so their caches provably hold the same prefix
    (speculative decoding verifies against exactly this alignment)."""
    for start in range(0, max(int(ids.size), 1), bucket):
        chunk = ids[start : start + bucket]
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, : chunk.size] = chunk
        yield (
            jnp.asarray(tokens),
            jnp.asarray([max(int(chunk.size), 1)], jnp.int32),
            int(chunk.size),
        )


# shared by the target runner and the draft engine: roll a KV cache's
# write head back to ``n`` (speculative decoding rejects by length — the
# garbage KV past n is masked by attention and overwritten by later steps)
_cache_with_len = jax.jit(
    lambda c, n: {**c, "lengths": jnp.zeros_like(c["lengths"]) + n},
    donate_argnums=(0,),
)


class _SpecEngine:
    """Draft side of greedy speculative decoding.

    Holds the draft model's params and its jitted entry points: a bucketed
    prefill (the draft's cache must contain the same prompt as the
    target's), a k-step greedy chunk (ONE dispatch proposes k tokens), and
    a cache-length reset (rolls back the positions a rejected draft
    wrote). Output correctness never depends on the draft — the target's
    verify pass re-derives every emitted token — so the draft may be any
    same-vocab model; its quality only sets the acceptance rate."""

    def __init__(
        self,
        target_cfg: Any,
        quant: Any,
        draft_name: str,
        k: int,
        draft_path: Optional[str] = None,
    ):
        from gofr_tpu.models.llama import CONFIGS
        from gofr_tpu.models.transformer import (
            decode_chunk,
            init_cache,
            init_transformer,
            prefill,
        )

        if draft_name not in CONFIGS:
            raise ValueError(
                f"DRAFT_MODEL_NAME '{draft_name}' unknown — expected one of "
                f"{sorted(CONFIGS)}"
            )
        cfg = CONFIGS[draft_name]
        if cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft '{draft_name}' vocab {cfg.vocab_size} != target "
                f"vocab {target_cfg.vocab_size} — speculative decoding "
                "verifies draft token ids against the target distribution"
            )
        if cfg.max_seq < target_cfg.max_seq:
            raise ValueError(
                f"draft '{draft_name}' max_seq {cfg.max_seq} < target "
                f"serving max_seq {target_cfg.max_seq}"
            )
        if k + 2 > target_cfg.max_seq:
            raise ValueError(
                f"DRAFT_TOKENS {k} cannot fit a verify (k+1 tokens) in the "
                f"serving cache (max_seq {target_cfg.max_seq}) — spec "
                "decoding would silently never engage"
            )
        import dataclasses

        self.cfg = dataclasses.replace(cfg, max_seq=target_cfg.max_seq)
        self.k = k
        from gofr_tpu.models.ingest import is_safetensors_path, load_llama_params

        if draft_path and is_safetensors_path(draft_path):
            self.params = load_llama_params(draft_path, self.cfg, quantize=quant)
        elif draft_path:
            from gofr_tpu.models.quant import quantize_params
            from gofr_tpu.training.checkpoint import restore_params

            self.params = quantize_params(restore_params(draft_path), quant)
        else:
            # seeded draft (key differs from the target's so a same-config
            # draft still exercises real accept/reject paths in tests)
            self.params = init_transformer(jax.random.key(1), self.cfg, quantize=quant)
        dcfg = self.cfg
        self._init_cache = init_cache
        self._prefill = jax.jit(lambda p, t, c, l: prefill(p, t, c, dcfg, l))
        self._chunk = jax.jit(
            lambda p, t, c: decode_chunk(
                p, t, c, dcfg, k, jax.random.key(0), 0.0, 0, 1.0
            )
        )
        from gofr_tpu.models.transformer import draft_chunk_sampled

        # sampled proposals share the greedy chunk's k-step cache-write
        # pattern (the verify side tests k-1 of them); warmed in the
        # device's warmup() next to the greedy chunk
        self._chunk_sampled = jax.jit(
            lambda p, t, c, key, temp, tk, tp, mp: draft_chunk_sampled(
                p, t, c, dcfg, k, key, temp, tk, tp, mp
            )
        )

    def propose_sampled(
        self, token_dev: Any, cache: dict, key: Any,
        temp: float, tk: int, tp: float, mp: float,
    ) -> tuple:
        """k sampled draft tokens [1, k] plus their warped distributions
        [1, k, V] and the advanced draft key."""
        return self._chunk_sampled(
            self.params, token_dev, cache, key, temp, tk, tp, mp
        )

    def prefill_prompt(self, ids: np.ndarray, bucket: int, chunked: bool) -> dict:
        """Run the prompt through the draft -> a fresh [1]-row draft cache
        holding exactly the prompt (mirrors the target-cache invariant).
        ``chunked`` mirrors the target's path for over-long prompts: slice
        through the bucket; otherwise clip to the LAST bucket tokens the
        way the target's pack_token_rows does — the two caches must hold
        the same prefix either way."""
        if not chunked:
            ids = ids[-bucket:]
        cache = self._init_cache(self.cfg, 1, max_seq=self.cfg.max_seq)
        for tokens, lengths, _ in _prompt_chunks(ids, bucket):
            _, cache = self._prefill(self.params, tokens, cache, lengths)
        return cache

    def propose(self, token_dev: Any, cache: dict) -> tuple[Any, dict]:
        """k greedy draft tokens [1, k] from the pending token; writes the
        proposed prefix into the draft cache (rolled back on rejection)."""
        return self._chunk(self.params, token_dev, cache)

    def reset_len(self, cache: dict, n: int) -> dict:
        return _cache_with_len(cache, jnp.asarray(n, jnp.int32))


class _PagedPrefixStore:
    """Block-table prefix cache for the transformer runner (KV_PAGED).

    Entries live as refcounted BLOCK TABLES in a shared
    :class:`~gofr_tpu.tpu.kv_blocks.BlockPool` arena instead of private
    ``max_seq`` rows: a stored conversation occupies only the blocks its
    tokens fill, a conversation store ALIASES the whole blocks of the
    prefix entry it extends (no duplicate residency, no copy), and the
    LRU yields blocks to decode-pool admission the moment live traffic
    needs them. Lookups still hand the executables the contiguous row
    they were compiled for (``JaxKVArena.gather_row``) — bit-identity
    with the slot model is the contract, block-native attention the
    roadmap item — so the paged win here is at-rest HBM residency and
    store-path copy volume, not hit-time gather bytes.

    Entry meta mirrors the row store's tuple: ``length``,
    ``next_token`` (None = divert to tail-prefill, the sampled-source
    rule), ``logits`` (None for generation entries — logits-needing
    lookups divert the same way). ``_lock`` serializes arena
    scatter/gather dispatch order; the pool's own lock guards block
    accounting and must nest INSIDE it."""

    def __init__(self, pool: Any, arena: Any, lcp_min: int):
        self.pool = pool
        self.arena = arena
        self.lcp_min = lcp_min  # resolved by the runner; -1 = exact-only
        self.stats = {"hits": 0, "partial_hits": 0, "misses": 0}
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.pool)

    def lookup(self, ids: np.ndarray, need_logits: bool) -> Optional[tuple]:
        """-> ("hit", state, 0) | ("partial", gathered_cache, shared) |
        None. Blocks are PINNED (increfed) across the gather so a
        concurrent admission evicting the entry cannot free them
        mid-copy."""
        from gofr_tpu.tpu.kv_blocks import BlockTable, blocks_for

        key = ids.tobytes()
        with self._lock:
            with self.pool.lock:
                entry = self.pool.cache_lookup(key)
                if entry is not None and (
                    (entry.meta["logits"] is None and need_logits)
                    or entry.meta["next_token"] is None
                ):
                    entry = None  # divert rules, identical to the row store
                if entry is not None:
                    meta = dict(entry.meta)
                    pinned = list(entry.table.blocks)
                    self.pool.incref(pinned)
                    self.stats["hits"] += 1
                    shared = 0
                else:
                    shared, donor = (
                        self._lcp_scan(ids, int(ids.size) - 1, self.lcp_min)
                        if self.lcp_min >= 0 else (0, None)
                    )
                    if donor is None:
                        self.stats["misses"] += 1
                        return None
                    pinned = list(
                        donor.table.blocks[
                            : blocks_for(shared, self.pool.block_tokens)
                        ]
                    )
                    self.pool.incref(pinned)
                    self.stats["partial_hits"] += 1
            # gather outside the pool lock (arena dispatch order still
            # serialized by _lock); the pin keeps the blocks alive
            try:
                if shared:
                    cache = self.arena.gather_row(
                        BlockTable(pinned, shared), shared
                    )
                    return ("partial", cache, shared)
                cache = self.arena.gather_row(
                    BlockTable(pinned, meta["length"]), meta["length"]
                )
            finally:
                self.pool.release_blocks(pinned)
        return ("hit", {
            "cache": cache,
            "length": meta["length"],
            "next_token": meta["next_token"],
            "logits": meta["logits"],
        }, 0)

    def _lcp_scan(self, ids: np.ndarray, limit: int, min_shared: int) -> tuple:
        """Longest-common-token-prefix donor entry (pool lock held) —
        the shared :func:`~gofr_tpu.tpu.kv_blocks.lcp_scan` loop."""
        from gofr_tpu.tpu.kv_blocks import lcp_scan

        shared, key, entry = lcp_scan(
            self.pool.cache_items(), ids, limit, min_shared
        )
        if entry is None:
            return 0, None
        self.pool.cache_touch(key)
        return shared, entry

    def store(self, ids: np.ndarray, state: Any) -> None:
        """Prompt prefill result -> blocks: scatter only
        ``ceil(length/block_tokens)`` blocks (the row store copied the
        whole max_seq row). Exhaustion skips the store — the cache must
        never fail a request."""
        from gofr_tpu.tpu.kv_blocks import KVExhausted

        length = int(state["length"])
        with self._lock:
            try:
                table = self.pool.reserve(length)
            except KVExhausted:
                return  # all blocks held by live requests: nothing to evict
            table.length = length
            self.pool.note_copied(
                self.arena.scatter_row(state["cache"], table)
            )
            self.pool.cache_put(ids.tobytes(), table, {
                "length": length,
                "next_token": state["next_token"],
                "logits": state["logits"],
            })

    def clear(self) -> None:
        """Purge every entry (blocks released) — the warmup's fake
        probe entries must not greet live traffic."""
        with self._lock:
            self.pool.cache_clear()

    def install_remote(self, ids: np.ndarray, payloads: list,
                       meta: dict) -> bool:
        """Receiving end of a cross-replica KV transfer: install the
        verified foreign blocks as a cache entry, so the imminent
        lookup of the same prompt hits copy-free. Wire checksums and
        the spec/identity checks already ran (device KV has no semantic
        read-back, so no readback verify); returns False on local
        exhaustion — that is the local arena's problem, not the
        donor's."""
        from gofr_tpu.tpu.kv_blocks import install_foreign_entry

        next_token = meta.get("next_token")
        with self._lock:
            return install_foreign_entry(
                self.pool, self.arena, ids, payloads,
                {
                    "next_token": (
                        int(next_token) if next_token is not None else None
                    ),
                    "logits": None,
                },
                verify_readback=False, count_copied=True,
            )

    def store_generation(
        self, full: np.ndarray, row: Any, exactable: bool, out: list
    ) -> None:
        """Conversation store (prompt + reply): alias the WHOLE blocks
        of the longest cached prefix this conversation extends —
        typically the prompt's own prefill entry, whose blocks then
        serve both entries — and scatter only the tail. The boundary
        block stays the donor's (scatter skips aliased blocks): writing
        "equal" KV from a different executable's row would fork the
        bit-lineage shared readers see."""
        from gofr_tpu.tpu.kv_blocks import BlockTable, KVExhausted

        bt = self.pool.block_tokens
        with self._lock:
            with self.pool.lock:
                shared, donor = self._lcp_scan(full, int(full.size), bt)
                if donor is not None:
                    table, shared_tokens = self.pool.alias_full_blocks(
                        donor.table, shared
                    )
                else:
                    table, shared_tokens = BlockTable(), 0
                try:
                    self.pool.ensure(table, int(full.size))
                except KVExhausted:
                    self.pool.release(table)
                    return
                table.length = int(full.size)
            self.pool.note_copied(
                self.arena.scatter_row(
                    row, table, skip_blocks=shared_tokens // bt
                )
            )
            self.pool.cache_put(full.tobytes(), table, {
                "length": int(full.size),
                "next_token": int(out[-1]) if exactable else None,
                "logits": None,
            })


class _PrefillState(dict):
    """Per-request prefill result with lazy fields: ``cache`` (row slice,
    computed only when generate() continues the request) and ``logits``
    (device row view — reading it is what triggers the device fetch).
    ``next_token`` and ``length`` are plain host values."""

    def __init__(self, full_cache: dict, full_logits: Any, index: int, **kw: Any):
        super().__init__(**kw)
        self._full_cache = full_cache
        self._full_logits = full_logits
        self._index = index

    def __getitem__(self, key: str) -> Any:
        if not dict.__contains__(self, key):
            # materialize once, then DROP the full-batch reference — a
            # request state must not pin the whole padded batch's cache
            # and logits in HBM for its lifetime
            if key == "cache":
                dict.__setitem__(self, key, _slice_cache(self._full_cache, self._index))
                self._full_cache = None
            elif key == "logits":
                dict.__setitem__(self, key, self._full_logits[self._index])
                self._full_logits = None
        return dict.__getitem__(self, key)

    def __contains__(self, key: object) -> bool:
        return key in ("cache", "logits") or dict.__contains__(self, key)

    def get(self, key: str, default: Any = None) -> Any:
        try:
            return self[key]
        except KeyError:
            return default


def _note_routing(drec: Any, ids: np.ndarray, rows: int, cfg: Any) -> np.ndarray:
    """A fetched prefill's ids with an expert model's routing counts behind
    them (``pack_expert_counts``) -> the ids alone; the counts go onto the
    dispatch's record."""
    from gofr_tpu.models.transformer import unpack_expert_counts

    ids, counts = unpack_expert_counts(ids, rows, getattr(cfg, "routing_width", 0))
    if drec is not None and counts is not None:
        drec.note_routing(counts, cfg.n_experts, cfg.top_k, cfg.n_shared_experts)
    return ids


# a row whose fixed-size state is at least this large has its chunked
# prefills gated: two prompts hold rows at once and a slice waits for the
# one before the one just issued (``_chunked_prefill``). What the gate
# bounds is the memory of slices issued at once, each holding a row: a
# 16-slice prompt (MODEL_MAX_SEQ 2048 in buckets of 128) of rows under 64
# MiB holds under 1 GiB ungated. The two readings behind it (PERF.md, PRs
# 28 and 36): Brumby's 0.27 GB row took 3.3 GB for one 12-slice prompt
# ungated; a state-space row of 9 MB, ungated, raised the peak by 0.18 GB
# with 66 requests at once. No row between the two has been measured.
_STATE_GATE_BYTES = 64 << 20


def _slice_cache(cache: dict, i: int) -> dict:
    """Row ``i`` of every leaf: the row axis is the second of a stack (K
    and V, a state, a tail: whatever kinds of layer the model has, each
    stacked over the layers of its kind) and the first of ``lengths``."""
    return {
        name: leaf[i : i + 1] if leaf.ndim == 1 else leaf[:, i : i + 1]
        for name, leaf in cache.items()
    }


def _cache_max_len(cache: dict, cfg: Any) -> int:
    """Positions a row can hold: the length axis of its K/V rows (a cache
    that holds a state beside them is bound by them too) or of its latent
    rows, or for a cache
    that is a state alone, which has none, the model's ``max_seq`` (the
    rotary table)."""
    if "latent" in cache:
        return int(cache["latent"].shape[2])
    return int(cache["k"].shape[3]) if "k" in cache else int(cfg.max_seq)


def _load_or_init(model_path: Optional[str], init_fn: Any) -> Any:
    if model_path:
        from gofr_tpu.training.checkpoint import restore_params

        return restore_params(model_path)
    return init_fn()


def _build_runner(
    name: str,
    quant: Any,
    model_path: Optional[str],
    max_batch: int = 8,
    mesh: Optional[Any] = None,
    decode_chunk: int = 8,
    max_seq: Optional[int] = None,
    buckets: Optional[tuple[int, ...]] = None,
    kv_dtype: Optional[Any] = None,
    draft_name: str = "",
    draft_tokens: int = 4,
    draft_path: Optional[str] = None,
    attn_impl: Optional[str] = None,
    prefix_cache: int = 0,
    prefix_lcp_min: int = 0,
    lora_adapters: Optional[dict] = None,
    echo_step_ms: float = 0.0,
    prefill_chunk_tokens: int = 0,
    timeline: Any = None,
    watchdog: Any = None,
    cache_events: Any = None,
    kv_paged: bool = False,
    kv_block_tokens: int = 64,
    kv_blocks: int = 0,
    kv_budget_bytes: int = 0,
    kv_reserve_seqs: int = 8,
    metrics: Any = None,
) -> Any:
    from gofr_tpu.models.llama import CONFIGS

    if lora_adapters and name not in CONFIGS:
        raise ValueError(
            f"LORA_ADAPTERS requires a transformer MODEL_NAME (got '{name}')"
        )
    if name == "echo":
        from gofr_tpu.parallel.mesh import mesh_axes as _axes

        return _EchoRunner(
            max_batch, step_ms=echo_step_ms, mesh_axes=_axes(mesh),
            metrics=metrics,
        )
    if name in ("mlp", "tiny-mlp"):
        return _MLPRunner(quant, model_path, max_batch)
    if name.startswith("bert"):
        return _BertRunner(name, quant, model_path, max_batch)
    if name in CONFIGS:
        return _TransformerRunner(
            name, quant, model_path, max_batch, mesh=mesh,
            decode_chunk=decode_chunk, max_seq=max_seq, buckets=buckets,
            kv_dtype=kv_dtype, draft_name=draft_name,
            draft_tokens=draft_tokens, draft_path=draft_path,
            attn_impl=attn_impl, prefix_cache=prefix_cache,
            prefix_lcp_min=prefix_lcp_min, lora_adapters=lora_adapters,
            prefill_chunk_tokens=prefill_chunk_tokens,
            timeline=timeline, watchdog=watchdog, cache_events=cache_events,
            kv_paged=kv_paged, kv_block_tokens=kv_block_tokens,
            kv_blocks=kv_blocks, kv_budget_bytes=kv_budget_bytes,
            kv_reserve_seqs=kv_reserve_seqs, metrics=metrics,
        )
    raise ValueError(
        f"unknown MODEL_NAME '{name}' — expected echo, mlp, bert-tiny, "
        f"bert-base, or one of {sorted(CONFIGS)}"
    )
