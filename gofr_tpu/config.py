"""12-factor configuration: ``./configs/.env`` file loaded into the process
environment, reads always backed by live env vars.

Parity: /root/reference/pkg/gofr/config/config.go:3-6 (the two-method Config
interface) and config/godotenv.go:9-33 (.env load then ``os.Getenv``).
Semantics preserved: the .env file never overrides variables already present
in the environment, and lookups hit the live environment so tests can inject
values with ``monkeypatch.setenv``.

TPU-native keys added on top of the reference set (SURVEY.md §2 #22):
``TPU_ENABLED``, ``TPU_MESH`` (serving mesh, e.g. "tp=4,dp=4"),
``MODEL_NAME``, ``MODEL_PATH``, ``MODEL_QUANT``, ``BATCH_MAX_SIZE``,
``BATCH_TIMEOUT_MS``. (An early ``METRICS_ENABLED`` toggle was never
wired — metrics are always on; the knob was dropped rather than left
inert. gofrlint GFL008 now guards this class of drift.)

Paged-KV keys (tpu/kv_blocks.py, see docs/advanced-guide/performance):
``KV_PAGED`` (default on) switches KV storage/admission to
block-granular paged mode; ``KV_BLOCK_TOKENS`` (default 64) is the
block size; ``KV_BLOCKS`` / ``KV_HBM_BUDGET_MB`` size the shared
block budget (0 = auto, non-binding).

Serving-mesh key (tpu/device.py + parallel/): ``TPU_MESH`` (e.g.
"tp=2" or "tp=4,dp=4") shards serving executables over a named mesh.
Paged KV, chunked prefill, the prefix cache, and the penalized pool
compose with tp-only meshes (the paged block arena shards its kv-head
axis over tp); dp/fsdp meshes degrade paged KV/chunked prefill and any
mesh degrades pooled multi-LoRA — each degrade is logged and counted
on ``gofr_tpu_mesh_degrade_total{feature}``. ``KV_BLOCK_TOKENS`` must
be divisible by tp for the echo runner's host-mesh arena, and the
model's ``n_kv_heads`` by tp for device arenas — violations fail the
boot with the axis named.

Observability keys (timebase + postmortem layer, see
docs/advanced-guide/observability.md for semantics):
``TIMEBASE_INTERVAL_S`` (default 5) / ``TIMEBASE_WINDOW_S`` (default
900) / ``TIMEBASE_ENABLED`` size and arm the metric-snapshot ring;
``POSTMORTEM_DIR`` (default ./postmortems — setting it EXPLICITLY also
arms the crash/fatal-signal hooks), ``POSTMORTEM_KEEP``,
``POSTMORTEM_MIN_INTERVAL_S``, ``POSTMORTEM_SNAPSHOTS`` govern the
black-box bundles; ``METRICS_MAX_SERIES`` (default 1000) caps
per-metric label cardinality; ``METRICS_EXEMPLARS=off`` disables
OpenMetrics histogram exemplars.

Fleet-router keys (gofr_tpu/fleet, see docs/advanced-guide/fleet.md):
``FLEET_REPLICAS`` (comma list of replica base URLs, optionally
``name=url``) turns a process into the fleet front door via
``tools/router.py``; routing: ``FLEET_RETRIES`` (2),
``FLEET_DEADLINE_S`` (30), ``FLEET_CONNECT_TIMEOUT_S`` (2),
``FLEET_READ_TIMEOUT_S`` (30), ``FLEET_AFFINITY`` (on),
``FLEET_AFFINITY_MAX_SKEW`` (4), ``FLEET_ROUTES``; health:
``FLEET_PROBE_INTERVAL_S`` (1),
``FLEET_PROBE_TIMEOUT_S`` (1), ``FLEET_PROBE_HEDGE_MS`` (0 = off),
``FLEET_OUT_AFTER`` (2), ``FLEET_PROBATION_PROBES`` (3); breaker:
``FLEET_BREAKER_THRESHOLD`` (5), ``FLEET_BREAKER_COOLDOWN_S`` (5);
admission: ``FLEET_QUOTA_RPS`` (0 = off), ``FLEET_QUOTA_BURST``,
``FLEET_TRUST_TENANT_HEADER`` (off — only behind a gateway that stamps
``X-Tenant``), ``FLEET_MAX_INFLIGHT`` (256),
``FLEET_SATURATION_QUEUE`` (64), ``FLEET_RETRY_AFTER_S`` (1); drain:
``FLEET_DRAIN_TIMEOUT_S`` (10); resumable streams: ``FLEET_RESUME``
(on — mid-stream failover for deterministic SSE), ``FLEET_MAX_RESUMES``
(4 continuation attempts per stream); HA: ``FLEET_ROUTER_ID`` (defaults
to a per-process id) labels one of N side-by-side router instances —
the router tier has no single point of failure: quota is redis-backed
(shared), affinity/KV-locality is stateless rendezvous hashing, and
the in-flight cap, route records, breaker and prober verdicts are
explicitly PER-INSTANCE (N routers = N x ``FLEET_MAX_INFLIGHT``);
tracing: ``FLEET_TRACE_SCRAPE_TIMEOUT_S`` (1 — per-replica evidence
scrape budget for ``GET /admin/fleet/trace/<id>``; replicas that miss
it show as ``evidence_gaps`` on a partial trace).

Self-healing keys (tpu/recovery.py + telemetry.py, see
docs/advanced-guide/fleet.md "Wedge-recovery runbook"):
``RECOVERY_ENABLED`` (on — a wedged engine quarantines the stuck
dispatch and rebuilds back to serving; off restores terminal wedged),
``RECOVERY_MAX_ATTEMPTS`` (3), ``RECOVERY_BACKOFF_S`` (1, doubling) /
``RECOVERY_BACKOFF_MAX_S`` (30), ``RECOVERY_ATTEMPT_TIMEOUT_S`` (300 —
a rebuild hanging past it is terminal ``failed``); ``JOURNAL`` (on —
durable generation journal: prompt hash + sampling params + emitted
token ids per request, the substrate of bit-identical stream resume),
``JOURNAL_CAPACITY`` (256 interrupted entries retained),
``JOURNAL_MAX_TOKENS`` (8192 tokens recorded per entry).

Crash-durability keys (journal_wal.py + tools/supervisor.py, see
docs/advanced-guide/fleet.md "Process-death recovery"):
``JOURNAL_DIR`` (unset = in-memory journal only) arms the disk-backed
segmented WAL behind the generation journal — a SIGKILLed replica
rehydrates its resumable entries at next boot and serves
``X-Resume-From`` for its own pre-crash streams bit-identically;
``JOURNAL_FSYNC`` (``interrupt`` — flush every record to the OS, which
survives process death, and fsync on interruption/rotation/close;
``always`` fsyncs per record for the power-loss threat model at a
measured per-token cost — see the bench's journal_wal_microbench;
``off`` never fsyncs); ``JOURNAL_SEGMENT_BYTES`` (1 MiB) rotates
segments — live entries carry across via rotation checkpoints — and
``JOURNAL_SEGMENTS`` (4) bounds retention. Recovery refuses torn and
corrupt tail records (CRC-framed, kvwire discipline) rather than
installing them. Run the replica under ``tools/supervisor.py`` (or an
equivalent init) so a crashed process respawns; the fleet prober
detects the reborn process by its changed ready ``boot_id`` and walks
it back through probation as ``restarting`` (visible on
``/admin/fleet`` and ``gofr_tpu_router_replica_restarts_total``).

Deadline-aware-serving keys (gofr_tpu/deadline.py, see
docs/advanced-guide/fleet.md "Deadlines & brownout"):
``REQUEST_DEADLINE_S`` (0 = off — the default end-to-end budget for
requests without an ``X-Request-Deadline-Ms`` header; the header
always wins, and a header of 0 opts a single request out), every
serving stage honors it: the batcher sheds expired items at dequeue
(stage ``queue``), pool/paged-KV admission rejects budgets that
cannot cover one decode chunk at the observed cadence (stage
``admission``), and the decode loop expires rows per chunk (stage
``decode``) — all 504-mapped and counted on
``gofr_tpu_deadline_exceeded_total{stage}``. ``PRIORITY_DEFAULT``
(5) is the tier requests without an ``X-Priority`` header (0
sheddable .. 9 protected, router-forwarded) serve at. Brownout:
``BROWNOUT_QUEUE_DEPTH`` (0 = off; queue depth arming level 1 at the
threshold, level 2 at 2x) and ``BROWNOUT_KV_UTIL`` (0 = off; a 0..1
KV-ledger-utilization fraction, hard level at the midpoint to full)
arm the graded controller; at level >= 1 priorities below
``BROWNOUT_SHED_PRIORITY`` (5) 429 with Retry-After, at level 2
priorities at-or-below it shed and ``BROWNOUT_CLAMP_TOKENS`` (0 =
off) clamps ``max_tokens``. The live level serves on
``/admin/engine`` and ``gofr_tpu_brownout_level``.

Disaggregated prefill/decode keys (fleet/kvwire.py + tpu/device.py,
see docs/advanced-guide/fleet.md "Disaggregated prefill/decode"):
``FLEET_ROLE`` (``mixed`` — what a replica advertises on
``/admin/engine``: ``prefill`` replicas take prefill-heavy work and
act as KV donors, ``decode`` replicas take token generation, ``mixed``
takes anything) and ``FLEET_ROLE_ROUTING`` (on, router-side — off
ignores advertised roles and stamps no donor hints) steer the tiers;
an empty or breaker-vetoed tier always degrades to mixed routing, so
role config can never shrink what the fleet serves.
``KV_TRANSFER`` (on — a replica serves its cached paged-KV block
tables on ``GET /admin/kv/<prompt_hash>`` and pulls a router-stamped
``X-KV-Donor``'s warm prefix before admission; off disarms both
directions), ``KV_TRANSFER_TIMEOUT_S`` (2 — one pull's overall budget,
also the export side's default deadline; a pull additionally never
spends more than half the request's remaining deadline),
``KV_TRANSFER_PIN_TTL_S`` (60 — the bounded lifetime of the block pins
an export holds, released by a named timer even if the serving thread
dies mid-send), ``KV_TRANSFER_TRUST_HINT`` (off — ``X-KV-Donor`` names
a URL the replica will FETCH into its shared prefix cache, so the
header is an SSRF/cache-poisoning primitive if client-minted; set
``on`` ONLY on replicas whose front door is the fleet router, exactly
the ``FLEET_TRUST_TENANT_HEADER`` contract). ``/admin/kv`` is on the
``ADMIN_TOKEN``-gated admin plane; a pull forwards the replica's own
token, so a tokened fleet (one shared token) keeps transferring.
Every pull outcome counts
on
``gofr_tpu_kv_transfer_total{outcome}``; any failure falls back to
local chunked prefill — a transfer can make a request faster, never
break it.

Fleet-scale hardening keys (fleet/replica.py + fleet/admission.py,
see docs/advanced-guide/fleet.md "Fleet simulation"):
``FLEET_PROBE_JITTER`` (0.2 — decorrelated per-replica probe jitter
as a fraction of ``FLEET_PROBE_INTERVAL_S``; 0 restores the
synchronized sweep, which at N=16 fires every probe of a round in one
burst window) and ``FLEET_QUOTA_CACHE_TTL_S`` (0.05 — short-TTL local
token-lease cache over the redis quota bucket; 0 = one redis sync
(two pipelined round trips) per request per tenant, the Zipf hot-key
tax the fleetsim measures).

Pooled-speculative-decoding keys (tpu/spec_pool.py + tpu/decode_pool.py,
see docs/advanced-guide/performance "Speculative decoding"):
``SPEC_POOLED`` (off — ``on`` routes speculation THROUGH the
continuous-batching pool: each greedy pooled request drafts k tokens
per cycle and one batched ``[slots, width]`` verify dispatch commits
the accepted prefixes, rejected tokens rolling back by length /
paged-KV refcount; the solo ``DRAFT_MODEL_NAME`` latency mode stands
down for pool-eligible requests), ``SPEC_NGRAM`` (on — zero-weight
n-gram/prompt-lookup drafting from the request's own prompt+emitted
context, no draft checkpoint), ``SPEC_K_MAX`` (4 — draft-width bound;
the per-request adaptive-k EMA degrades toward 0 = plain decode on
poor acceptance and is clamped under brownout level >= 1 and by the
remaining deadline budget), ``SPEC_FAKE_ACCEPT`` (echo runner only: a
cyclic schedule of per-cycle accept counts, e.g. "3,1,0", making
every accept/reject/rollback branch deterministic in tier-1).

SLO & tenant-metering keys (slo.py + telemetry.py TenantLedger, see
docs/advanced-guide/observability.md "SLOs, budgets & tenants"):
``SLO_TARGETS`` (default
``availability=0.999;shed_rate=0.05;tier=9:availability=0.9995``) —
semicolon-separated ``[scope:]metric=target`` objectives; metrics:
``availability`` (good fraction), ``shed_rate`` (allowed shed
fraction, global-only), ``ttft_p95_ms`` / ``ttft_p99_ms`` /
``tpot_p95_ms`` / ``tpot_p99_ms`` (millisecond percentile bounds);
scopes ``model=<name>:``, ``tier=<n>:``, ``tier>=<n>:``. Burn-rate
alerting is multi-window: the fast page fires past
``SLO_BURN_FAST_RATE`` (14.4) on BOTH ``SLO_BURN_FAST_S`` (300) and
``SLO_BURN_FAST_LONG_S`` (3600); the slow ticket past
``SLO_BURN_SLOW_RATE`` (6) on both ``SLO_BURN_SLOW_S`` (21600) and
``SLO_BURN_SLOW_LONG_S`` (259200, also the budget-ledger window);
``SLO_EVAL_INTERVAL_S`` (15) paces the evaluator thread and ``SLO``
(on) removes the layer entirely. Windows clip silently to what the
flight-record ring and ``TIMEBASE_WINDOW_S`` retain. Verdicts land in
the anomaly ring (``slo_fast_burn``/``slo_slow_burn``; bounded by
``ANOMALY_RING_SIZE``, 256, served by ``GET /admin/anomalies``), on
``gofr_tpu_slo_burn_rate{objective,window}`` /
``gofr_tpu_slo_budget_remaining{objective}`` /
``gofr_tpu_slo_burn_alerts_total``, and on ``GET /admin/slo/budget``.
``TENANT_LEDGER_SIZE`` (256) bounds the space-saving top-K sketch
behind ``GET /admin/tenants`` — per-tenant usage (requests, tokens,
sheds, deadline misses) is EXACT for the top-K heavy hitters and
aggregated into ``~other`` beyond, so 5k distinct API keys add zero
Prometheus series (only ``gofr_tpu_tenants_tracked_entries`` and
``gofr_tpu_tenant_overflow_total`` exist).

Correctness-tooling keys (devtools/sanitizer.py + tests/conftest.py,
see docs/advanced-guide/static-analysis.md): ``GOFR_SANITIZE=1`` arms
the runtime concurrency sanitizer under tests;
``GOFR_SANITIZE_HOLD_MS`` (default 150) is the lock hold-time warning
threshold; ``GOFR_SANITIZE_ALL=1`` widens lock-order tracking beyond
project-created locks; ``GOFR_SANITIZE_REPORT`` names the findings
file.

Module-level accessors :func:`get_env`, :func:`env_flag`, and
:func:`environ_snapshot` are the ONLY sanctioned raw environment reads
in package code (gofrlint rule GFL001).
"""

from __future__ import annotations

import os
from typing import Optional, Protocol

# The config-surface provenance registry (gofrlint GFL008): every env
# key package code reads must have a row here, and every row must be
# read somewhere in the tree (package, tools or tests) — an
# unreadable row is an inert knob and fails lint. Harness-only knobs
# (FLEETSIM_GATE_*, WATCH_*) belong to their scripts, not to
# the package surface, and are deliberately NOT declared. The prose
# sections of the module docstring above stay the operator-facing
# documentation; this dict is the machine-checked index of it.
DECLARED_KEYS: dict[str, str] = {
    # core serving / reference-parity surface
    "APP_NAME": "service name stamped on traces",
    "LOG_LEVEL": "root logger level",
    "HTTP_PORT": "HTTP listen port",
    "GRPC_PORT": "gRPC listen port",
    "HANDLER_THREADS": "HTTP handler thread-pool size",
    "ADMIN_TOKEN": "bearer token gating the /admin plane",
    # datasources (reference parity: sql + redis)
    "DB_DIALECT": "sql dialect (mysql/postgres/sqlite)",
    "DB_HOST": "sql host (presence arms the datasource)",
    "DB_PORT": "sql port",
    "DB_NAME": "sql database name",
    "DB_USER": "sql user",
    "DB_PASSWORD": "sql password",
    "REDIS_HOST": "redis host (presence arms the client)",
    "REDIS_PORT": "redis port",
    # TPU / model boot
    "TPU_ENABLED": "arm the TPU serving engine",
    "TPU_BOOT": "boot-mode override (echo/real)",
    "TPU_MESH": "serving mesh spec, e.g. tp=4,dp=4",
    "TPU_TOPOLOGY": "expected device topology assertion",
    "TPU_COORDINATOR": "multihost coordinator address",
    "TPU_NUM_PROCESSES": "multihost process count",
    "TPU_PROCESS_ID": "this host's multihost process id",
    "MODEL_NAME": "served model name",
    "MODEL_PATH": "checkpoint path",
    "MODEL_QUANT": "weight quantization mode",
    "MODEL_BUCKETS": "prefill padding bucket list",
    "MODEL_MAX_SEQ": "max sequence length",
    "MODEL_ATTN_IMPL": "attention implementation override",
    "MODEL_KV_DTYPE": "KV-cache dtype (e.g. f8)",
    "TOKENIZER": "tokenizer implementation override",
    "TOKENIZER_PATH": "tokenizer asset path",
    "GEN_STOP_EOS": "stop generation on EOS token",
    "GEN_STOP_TOKENS": "extra stop-token ids",
    "ECHO_STEP_MS": "echo runner per-step latency",
    "LORA_ADAPTERS": "pooled multi-LoRA adapter table",
    # batching / scheduling / decode pool
    "BATCH_MAX_SIZE": "max continuous-batch size",
    "BATCH_TIMEOUT_MS": "batch formation window",
    "BATCH_COHORT": "cohort grouping policy",
    "SCHED_POLICY": "scheduler policy (fcfs/interference)",
    "SCHED_MAX_DEFER_MS": "interference-scheduler defer bound",
    "PREFILL_CHUNK_TOKENS": "chunked-prefill chunk size",
    "DECODE_CHUNK": "decode loop chunk size",
    "DECODE_SLOTS": "decode pool slot count",
    "DECODE_POOL": "enable the continuous-batching pool",
    "DECODE_POOL_PENALTIES": "penalized-pool admission weights",
    "PREFIX_CACHE": "shared prefix cache toggle",
    "PREFIX_LCP_MIN": "min longest-common-prefix to reuse",
    # paged KV + cross-replica transfer
    "KV_PAGED": "block-granular paged KV mode",
    "KV_BLOCK_TOKENS": "tokens per KV block",
    "KV_BLOCKS": "fixed shared block budget (0 = auto)",
    "KV_HBM_BUDGET_MB": "HBM budget for the block arena",
    "KV_TRANSFER": "serve/pull warm KV across replicas",
    "KV_TRANSFER_TIMEOUT_S": "one pull's overall budget",
    "KV_TRANSFER_PIN_TTL_S": "bounded export block-pin lifetime",
    "KV_TRANSFER_TRUST_HINT": "trust client X-KV-Donor (SSRF gate)",
    # speculative decoding
    "SPEC_POOLED": "route speculation through the pool",
    "SPEC_NGRAM": "n-gram/prompt-lookup drafting",
    "SPEC_K_MAX": "draft-width bound",
    "SPEC_FAKE_ACCEPT": "echo-runner deterministic accepts",
    "DRAFT_MODEL_NAME": "solo-mode draft model name",
    "DRAFT_MODEL_PATH": "solo-mode draft checkpoint",
    "DRAFT_TOKENS": "solo-mode draft depth",
    # deadlines / brownout
    "REQUEST_DEADLINE_S": "default end-to-end request budget",
    "PRIORITY_DEFAULT": "tier for requests without X-Priority",
    "BROWNOUT_QUEUE_DEPTH": "queue depth arming brownout",
    "BROWNOUT_KV_UTIL": "KV utilization arming brownout",
    "BROWNOUT_SHED_PRIORITY": "priority floor shed under brownout",
    "BROWNOUT_CLAMP_TOKENS": "max_tokens clamp at level 2",
    # observability: metrics / timebase / postmortem / profiling
    "METRICS_MAX_SERIES": "per-metric label-cardinality cap",
    "METRICS_EXEMPLARS": "OpenMetrics histogram exemplars",
    "TIMEBASE_ENABLED": "metric-snapshot ring toggle",
    "TIMEBASE_INTERVAL_S": "snapshot cadence",
    "TIMEBASE_WINDOW_S": "snapshot retention window",
    "POSTMORTEM_DIR": "black-box bundle dir (arms crash hooks)",
    "POSTMORTEM_KEEP": "bundles retained",
    "POSTMORTEM_MIN_INTERVAL_S": "bundle rate limit",
    "POSTMORTEM_SNAPSHOTS": "timebase snapshots per bundle",
    "FLIGHT_RECORDER_SIZE": "flight-record ring capacity",
    "FLIGHT_RECORDER_KEEP": "completed records retained",
    "FLIGHT_SLOW_MS": "slow-request capture threshold",
    "PROFILE_DIR": "jax profiler trace output dir",
    "DISPATCH_TIMELINE_SIZE": "dispatch timeline ring capacity",
    # tracing
    "TRACER_HOST": "zipkin exporter host",
    "TRACER_PORT": "zipkin exporter port",
    "FLEET_TRACE_SCRAPE_TIMEOUT_S": "per-replica trace-evidence budget",
    # SLO engine + tenant metering
    "ANOMALY_RING_SIZE": "typed anomaly-event ring capacity",
    "SLO": "SLO evaluation layer toggle",
    "SLO_TARGETS": "objective spec (scope:metric=target;...)",
    "SLO_BURN_FAST_S": "fast-burn short window",
    "SLO_BURN_FAST_LONG_S": "fast-burn long window",
    "SLO_BURN_FAST_RATE": "fast-burn page threshold",
    "SLO_BURN_SLOW_S": "slow-burn short window",
    "SLO_BURN_SLOW_LONG_S": "slow-burn long window / budget ledger",
    "SLO_BURN_SLOW_RATE": "slow-burn ticket threshold",
    "SLO_EVAL_INTERVAL_S": "evaluator thread cadence",
    "TENANT_LEDGER_SIZE": "top-K tenant sketch capacity",
    # self-healing / journal / WAL
    "RECOVERY_ENABLED": "wedge-recovery state machine",
    "RECOVERY_MAX_ATTEMPTS": "rebuild attempts before failed",
    "RECOVERY_BACKOFF_S": "first rebuild backoff",
    "RECOVERY_BACKOFF_MAX_S": "backoff ceiling",
    "RECOVERY_ATTEMPT_TIMEOUT_S": "hung-rebuild terminal timeout",
    "WATCHDOG_DISPATCH_TIMEOUT_S": "dispatch watchdog threshold",
    "JOURNAL": "durable generation journal",
    "JOURNAL_CAPACITY": "interrupted entries retained",
    "JOURNAL_MAX_TOKENS": "tokens recorded per entry",
    "JOURNAL_DIR": "disk-backed WAL dir (unset = memory)",
    "JOURNAL_FSYNC": "WAL durability mode",
    "JOURNAL_SEGMENT_BYTES": "WAL segment rotation size",
    "JOURNAL_SEGMENTS": "WAL segments retained",
    # fleet router / replicas
    "FLEET_REPLICAS": "replica URL list (arms the router)",
    "FLEET_ROUTES": "extra route table entries",
    "FLEET_ROUTER_ID": "HA router instance label",
    "FLEET_RETRIES": "per-request retry budget",
    "FLEET_DEADLINE_S": "router end-to-end deadline",
    "FLEET_CONNECT_TIMEOUT_S": "upstream connect timeout",
    "FLEET_READ_TIMEOUT_S": "upstream read timeout",
    "FLEET_AFFINITY": "prefix-affinity routing",
    "FLEET_AFFINITY_MAX_SKEW": "affinity load-skew bound",
    "FLEET_PROBE_INTERVAL_S": "health probe cadence",
    "FLEET_PROBE_TIMEOUT_S": "health probe timeout",
    "FLEET_PROBE_HEDGE_MS": "hedged second probe delay",
    "FLEET_PROBE_JITTER": "decorrelated probe jitter fraction",
    "FLEET_OUT_AFTER": "failed probes before out",
    "FLEET_PROBATION_PROBES": "probes to re-admit a replica",
    "FLEET_BREAKER_THRESHOLD": "breaker error threshold",
    "FLEET_BREAKER_COOLDOWN_S": "breaker half-open cooldown",
    "FLEET_QUOTA_RPS": "per-tenant quota (redis-backed)",
    "FLEET_QUOTA_BURST": "quota bucket burst",
    "FLEET_QUOTA_CACHE_TTL_S": "local token-lease cache TTL",
    "FLEET_TRUST_TENANT_HEADER": "trust client X-Tenant",
    "FLEET_MAX_INFLIGHT": "per-instance in-flight cap",
    "FLEET_SATURATION_QUEUE": "admission queue depth",
    "FLEET_RETRY_AFTER_S": "Retry-After on shed",
    "FLEET_DRAIN_TIMEOUT_S": "graceful drain budget",
    "FLEET_RESUME": "mid-stream failover for SSE",
    "FLEET_MAX_RESUMES": "continuation attempts per stream",
    "FLEET_ROLE": "advertised replica role",
    "FLEET_ROLE_ROUTING": "router honors advertised roles",
    # openai-compat layer
    "OPENAI_ACCEPT_UNKNOWN_MODEL": "serve unknown model names",
    "OPENAI_FANOUT_WORKERS": "n>1 sampling fanout pool size",
    "CHAT_TEMPLATE": "chat template style",
    "CHAT_TEMPLATE_JINJA": "jinja template path override",
    "CHAT_TEMPLATE_OPENER": "assistant-turn opener override",
    # persistent XLA compile cache (tpu/device.py configure_compile_cache)
    "JAX_COMPILATION_CACHE_DIR": "compile cache dir (unset = <checkout>/.jax_cache)",
    # native extension loader
    "GOFR_NATIVE_LIB": "prebuilt native library path",
    "GOFR_NATIVE_CACHE": "native build cache dir",
    "GOFR_NATIVE_DISABLE": "force the pure-python fallback",
    # correctness tooling (devtools/sanitizer.py + tests/conftest.py)
    "GOFR_SANITIZE": "runtime concurrency sanitizer",
    "GOFR_SANITIZE_ALL": "track non-project locks too",
    "GOFR_SANITIZE_HOLD_MS": "lock hold-time warning threshold",
    "GOFR_SANITIZE_REPORT": "sanitizer findings file",
    "GOFR_SANITIZE_GRAPH": "observed lock-order graph JSON file",
}


class Config(Protocol):
    """Two-method config surface every component depends on."""

    def get(self, key: str) -> Optional[str]: ...

    def get_or_default(self, key: str, default: str) -> str: ...


def parse_env_file(path: str) -> dict[str, str]:
    """Parse a dotenv file: KEY=VALUE lines, ``#`` comments, optional
    single/double quotes, ``export`` prefix tolerated."""
    out: dict[str, str] = {}
    try:
        with open(path, "r", encoding="utf-8") as f:
            lines = f.readlines()
    except OSError:
        return out
    for raw in lines:
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("export "):
            line = line[len("export "):].lstrip()
        if "=" not in line:
            continue
        key, _, value = line.partition("=")
        key = key.strip()
        value = value.strip()
        if not key:
            continue
        if value[:1] in ("'", '"'):
            quote = value[0]
            closing = value.find(quote, 1)
            if closing != -1:
                value = value[1:closing]  # anything after the close quote is comment/junk
            else:
                value = value[1:]
        elif " #" in value:
            # strip trailing inline comment on unquoted values
            value = value.split(" #", 1)[0].rstrip()
        out[key] = value
    return out


def get_env(key: str, default: Optional[str] = None) -> Optional[str]:
    """THE sanctioned raw environment read (gofrlint GFL001): package
    code routes every env lookup through here (or a Config instance) so
    the config surface stays auditable in one module. Entry-point
    scripts may read the environment directly."""
    return os.environ.get(key, default)


def env_flag(key: str) -> bool:
    """True when ``key`` is set to ``1`` — the framework's debug-toggle
    idiom (``GOFR_SANITIZE``, ``GOFR_NATIVE_DISABLE``, ...)."""
    return os.environ.get(key, "") == "1"


def environ_snapshot() -> dict[str, str]:
    """A point-in-time copy of the whole environment — for consumers
    that must iterate it (postmortem config fingerprints, test
    save/restore scaffolding) without scattering raw reads."""
    return dict(os.environ)


class EnvConfig:
    """Config backed directly by the process environment."""

    def get(self, key: str) -> Optional[str]:
        return os.environ.get(key)

    def get_or_default(self, key: str, default: str) -> str:
        value = os.environ.get(key)
        return value if value not in (None, "") else default


class EnvFileConfig(EnvConfig):
    """Loads ``<configs_dir>/.env`` into the environment (non-overriding),
    then behaves like :class:`EnvConfig`.

    Parity: config/godotenv.go:18-33 — missing file is not an error; the app
    simply runs on ambient environment variables.
    """

    def __init__(self, configs_dir: str = "./configs") -> None:
        self.configs_dir = configs_dir
        env_path = os.path.join(configs_dir, ".env")
        for key, value in parse_env_file(env_path).items():
            os.environ.setdefault(key, value)


def new_env_file(configs_dir: str = "./configs") -> EnvFileConfig:
    return EnvFileConfig(configs_dir)
