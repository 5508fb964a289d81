"""Bridges the transport-agnostic handler signature onto the HTTP server,
plus the built-in routes.

Parity: /root/reference/pkg/gofr/handler.go:12-53 — the handler adapter
builds a per-request Context (:33), opens a "gofr-handler" span (:34), calls
user code (:35), and hands (result, error) to the responder; built-ins:
healthHandler (:38), faviconHandler (:42), catchAllHandler -> 404 (:51).
TPU-native addition: a /metrics endpoint (Prometheus text exposition).

Handlers may be sync (run on a worker thread so the event loop never blocks)
or ``async def`` (awaited on the loop — preferred for TPU batch enqueue).
"""

from __future__ import annotations

import asyncio
import contextvars
import inspect
import time
from typing import Any, Callable

from gofr_tpu import static
from gofr_tpu.context import Context
from gofr_tpu.errors import RouteNotFoundError
from gofr_tpu.http.request import Request
from gofr_tpu.http.responder import respond
from gofr_tpu.http.response import File, Raw, Response
from gofr_tpu.tracing import get_tracer

Handler = Callable[[Context], Any]


def make_endpoint(func: Handler, container: Any) -> Callable:
    """Adapt ``handler(ctx) -> result`` into an async router endpoint."""

    is_async = inspect.iscoroutinefunction(func)

    async def endpoint(request: Request) -> Response:
        ctx = Context(request, container)
        with get_tracer().start_span("gofr-handler"):
            try:
                if is_async:
                    result = await func(ctx)
                else:
                    loop = asyncio.get_running_loop()
                    # propagate the active span (contextvars) into the worker
                    # thread so ctx.trace_id / child spans nest correctly.
                    # The container's dedicated pool, NOT the loop default:
                    # sync handlers block (generations run seconds) and the
                    # default executor is cpu_count+4 threads — it silently
                    # serializes requests on small serving VMs.
                    call = contextvars.copy_context().run
                    result = await loop.run_in_executor(
                        container.handler_executor, call, func, ctx
                    )
                error = None
            except Exception as exc:  # handler errors -> enveloped response
                result, error = None, exc
        if error is not None and not hasattr(error, "status_code"):
            # unknown errors are 500s; log them (parity with the reference's
            # responder hiding internals behind a generic message)
            container.logger.errorf("handler error on %s %s: %r",
                                    request.method, request.path, error)
        return respond(result, error, executor=container.handler_executor)

    return endpoint


# -- built-in handlers (parity: handler.go:38-53) ---------------------------

def health_handler(ctx: Context) -> Any:
    """Aggregated datasource health (handler.go:38, container.go:26-38)."""
    return ctx.container.health()


def favicon_handler(_: Context) -> File:
    return File(content=static.favicon(), content_type="image/x-icon")


def catch_all_handler(_: Context) -> None:
    raise RouteNotFoundError()


def ready_handler(ctx: Context) -> Response:
    """Readiness probe, distinct from /.well-known/health (liveness): 503
    while the TPU stack is still booting (warmup compiles), with the current
    boot stage in the body so a slow cold boot is observable; 503 with the
    engine state AND the watchdog's evidence (which dispatch stalled, for
    how long) while the stall watchdog holds the engine degraded/wedged —
    the fleet router's probation logic and a human operator both need the
    WHY, not just the verdict; 503 while a fleet router is draining (new
    work must go to another front door); 200 once requests would be served
    without blocking. Apps without a TPU datasource are ready as soon as
    the server listens."""
    import json

    fleet = getattr(ctx.container, "fleet", None)
    if fleet is not None and fleet.draining:
        return Response(
            status=503,
            headers={"Content-Type": "application/json"},
            body=json.dumps({
                "state": "draining",
                "detail": f"router draining, {fleet.in_flight} in flight",
            }).encode("utf-8"),
        )
    from gofr_tpu.telemetry import BOOT_ID

    tpu = ctx.container.tpu
    if tpu is None:
        status, state = 200, {"state": "ready", "boot_id": BOOT_ID}
    elif not tpu.ready():
        status, state = 503, dict(tpu.boot_status)
        # a recovery rebuild clears readiness too: carry the incident
        # evidence so the prober can tell "coming back" from "cold boot"
        _attach_recovery_evidence(tpu, state)
    else:
        engine = getattr(tpu, "engine", None)
        if engine is not None and engine.state in (
            "degraded", "wedged", "recovering"
        ):
            snap = engine.snapshot()
            status = 503
            state = {"state": snap["state"], "detail": snap["detail"]}
            # the watchdog's evidence: which dispatch kinds stalled and
            # what it is still watching — the router records this as the
            # replica's leave-rotation reason
            watchdog = getattr(tpu, "watchdog", None)
            if watchdog is not None:
                wsnap = watchdog.snapshot()
                state["watchdog"] = {
                    "stalls": wsnap.get("stalls"),
                    "watching": wsnap.get("watching"),
                    "timeout_s": wsnap.get("timeout_s"),
                }
            # the recovery supervisor's evidence next to the watchdog's:
            # attempt count, backoff deadline, last outcome — the fleet
            # prober treats an engine with an ACTIVE recovery incident
            # as coming back (probation) rather than hard-out
            _attach_recovery_evidence(tpu, state)
        else:
            # boot_id rides the READY verdict: the prober detects a
            # supervisor-restarted process (new id, same address) and
            # routes it through the restarting/probation path
            status, state = 200, {"state": "ready", "boot_id": BOOT_ID}
    return Response(
        status=status,
        headers={"Content-Type": "application/json"},
        body=json.dumps(state).encode("utf-8"),
    )


def _attach_recovery_evidence(tpu: Any, state: dict) -> None:
    """Wedge-recovery incident evidence for the readiness 503 body:
    attempt count, backoff deadline, last outcome (the /admin/engine
    ``recovery`` block's probe-sized subset). Attached only while an
    incident is live or has history — a never-wedged replica's ready
    body stays unchanged."""
    recovery = getattr(tpu, "recovery", None)
    if recovery is None:
        return
    snap = recovery.snapshot()
    if snap["state"] == "idle" and not snap["incidents"]:
        return
    state["recovery"] = {
        "state": snap["state"],
        "attempts": snap["attempts"],
        "max_attempts": snap["max_attempts"],
        "backoff_in_s": snap["backoff_in_s"],
        "last_outcome": snap["last_outcome"],
    }


def metrics_handler(ctx: Context) -> Response:
    """Prometheus text exposition, content-negotiated: an
    ``Accept: application/openmetrics-text`` header gets the OpenMetrics
    1.0 body — same series, plus histogram bucket exemplars
    (trace_id/dispatch_id) and the mandatory ``# EOF`` — so dashboards
    that speak exemplars resolve a latency bucket straight to its
    flight record. Everyone else keeps classic text 0.0.4."""
    accept = ctx.request.header("Accept") or ""
    openmetrics = "application/openmetrics-text" in accept
    content_type = (
        "application/openmetrics-text; version=1.0.0; charset=utf-8"
        if openmetrics
        else "text/plain; version=0.0.4; charset=utf-8"
    )
    return Response(
        status=200,
        headers={"Content-Type": content_type},
        body=ctx.container.metrics.expose(openmetrics=openmetrics).encode("utf-8"),
    )


# -- device profiler admin surface (SURVEY.md §5: profiling hooks) -----------

def _check_admin(ctx: Context) -> None:
    """ADMIN_TOKEN (optional) gates the admin surface: when configured,
    requests need ``Authorization: Bearer <token>``. Unset keeps the
    open-by-default posture of the reference's built-in routes."""
    token = ctx.container.config.get("ADMIN_TOKEN")
    if not token:
        return
    import hmac

    header = ctx.request.header("Authorization") or ""
    # compare BYTES: compare_digest raises TypeError on non-ASCII str
    # (a mangled header must 401, not 500)
    expected = f"Bearer {token}".encode("utf-8")
    if not hmac.compare_digest(header.encode("utf-8", "replace"), expected):
        from gofr_tpu.errors import UnauthenticatedError

        raise UnauthenticatedError("admin token required")


def adapters_list_handler(ctx: Context) -> Any:
    _check_admin(ctx)
    if ctx.tpu is None:
        from gofr_tpu.errors import HTTPError

        raise HTTPError(503, "tpu not configured (set MODEL_NAME)")
    return {"adapters": ctx.tpu.list_adapters()}


def adapter_load_handler(ctx: Context) -> Any:
    """POST /admin/adapters {name, path}: load a LoRA adapter artifact
    over the serving base at runtime — no restart, no reload of the base
    weights (n adapters cost n x adapter bytes)."""
    from gofr_tpu.errors import HTTPError, InvalidParamError

    _check_admin(ctx)
    if ctx.tpu is None:
        raise HTTPError(503, "tpu not configured (set MODEL_NAME)")
    body = ctx.bind() if ctx.request.body else {}
    if not isinstance(body, dict) or "name" not in body or "path" not in body:
        raise InvalidParamError('body (expected {"name": ..., "path": ...})')
    return {"adapters": ctx.tpu.load_adapter(body["name"], body["path"])}


def adapter_unload_handler(ctx: Context) -> Any:
    from gofr_tpu.errors import HTTPError

    _check_admin(ctx)
    if ctx.tpu is None:
        raise HTTPError(503, "tpu not configured (set MODEL_NAME)")
    return {"adapters": ctx.tpu.unload_adapter(ctx.request.path_param("name"))}


def _query_flag(ctx: Context, name: str) -> Any:
    """Tri-state query flag: absent -> None; present empty or truthy
    (?slow=, ?slow=1, ?slow=true) -> True; false/0/no -> False."""
    if name not in ctx.request.query:
        return None
    return ctx.param(name).strip().lower() not in ("false", "0", "no")


def requests_admin_handler(ctx: Context) -> Any:
    """GET /admin/requests: recent flight records, newest first.
    ``?slow=``/``?errored=`` filter (the side buffer keeps flagged
    requests visible after ring eviction); ``?request_id=``/
    ``?trace_id=`` match exactly (the jump from an id in a log line or
    a router route record to the flight records that carried it);
    ``?tenant=`` filters by the hashed tenant id (the one a 429 shed
    body echoes and ``/admin/tenants`` ranks); ``?limit=`` bounds the
    page."""
    from gofr_tpu.errors import InvalidParamError

    _check_admin(ctx)
    try:
        limit = int(ctx.param("limit") or "100")
    except ValueError:
        raise InvalidParamError('"limit" must be an integer') from None
    if limit < 1:
        raise InvalidParamError('"limit" must be >= 1')
    records = ctx.container.telemetry.records(
        slow=_query_flag(ctx, "slow"),
        errored=_query_flag(ctx, "errored"),
        limit=limit,
        request_id=ctx.param("request_id") or None,
        trace_id=ctx.param("trace_id") or None,
        tenant=ctx.param("tenant") or None,
    )
    return {"requests": records, "count": len(records)}


def slo_admin_handler(ctx: Context) -> Any:
    """GET /admin/slo: rolling-window per-model p50/p95/p99 TTFT and
    TPOT computed from the flight records (exact sample percentiles).
    ``?window=`` sets the window in seconds (default 300)."""
    from gofr_tpu.errors import InvalidParamError

    _check_admin(ctx)
    try:
        window = float(ctx.param("window") or "300")
    except ValueError:
        raise InvalidParamError('"window" must be a number of seconds') from None
    if window <= 0:
        raise InvalidParamError('"window" must be > 0')
    return ctx.container.telemetry.slo(window_s=window)


def slo_budget_handler(ctx: Context) -> Any:
    """GET /admin/slo/budget: the error-budget ledger — every declared
    objective (``SLO_TARGETS``) with its windowed burn rates, remaining
    budget over the long window, latched alert states, and the most
    recent burn-alert evidence from the anomaly ring. ``/admin/slo``
    stays the raw-percentile view; this page answers "are we inside
    the promise, and how fast are we spending it"."""
    from gofr_tpu.errors import HTTPError

    _check_admin(ctx)
    slo = getattr(ctx.container, "slo", None)
    if slo is None:
        raise HTTPError(503, "slo engine disabled (set SLO=on)")
    return slo.budget()


def tenants_admin_handler(ctx: Context) -> Any:
    """GET /admin/tenants: bounded-cardinality per-tenant usage — the
    space-saving sketch's top-K heavy hitters by token volume (exact
    counts), everything beyond aggregated into ``~other``. ``?tenant=``
    looks one tenant up (404 when it is not tracked — it may have been
    folded into ``~other``); ``?limit=`` bounds the ranking (default
    50). Tenant ids are the hashed form the admission gate derives
    (``key-<sha256 prefix>``), never raw API keys."""
    from gofr_tpu.errors import EntityNotFoundError, InvalidParamError

    _check_admin(ctx)
    ledger = ctx.container.tenants
    tenant = ctx.param("tenant") or None
    if tenant is not None:
        entry = ledger.get(tenant)
        if entry is None:
            raise EntityNotFoundError(
                f"tenant '{tenant}' is not tracked (unseen, or folded "
                "into ~other by the top-K sketch)"
            )
        return {"tenant": entry, "stats": ledger.stats()}
    try:
        limit = int(ctx.param("limit") or "50")
    except ValueError:
        raise InvalidParamError('"limit" must be an integer') from None
    if limit < 1:
        raise InvalidParamError('"limit" must be >= 1')
    return ledger.snapshot(k=limit)


def engine_admin_handler(ctx: Context) -> Any:
    """GET /admin/engine: one-call engine introspection snapshot — state
    machine + transition history, boot timeline (per-stage compile wall
    times), watchdog state, dispatch counts, queue depth, decode-pool
    slot occupancy, scheduler defer state, cache hit/miss counts, HBM
    usage. Host-side reads only: it answers even while the engine is
    wedged (that is when it matters most)."""
    from gofr_tpu.errors import HTTPError

    _check_admin(ctx)
    if ctx.tpu is None:
        raise HTTPError(503, "tpu not configured (set MODEL_NAME)")
    snap = ctx.tpu.engine_snapshot()
    # SLO + tenant headlines ride the same snapshot: the fleet prober
    # piggybacks this page, so the router aggregates fleet-wide burn and
    # tenant pressure with ZERO extra scrape endpoints
    slo = getattr(ctx.container, "slo", None)
    if slo is not None:
        snap["slo"] = slo.headline()
    snap["tenants"] = ctx.container.tenants.overview()
    # the transport's own clock: how late the server's event loop runs
    # and how many token frames it has written
    snap["http"] = ctx.container.telemetry.transport()
    return snap


def dispatches_admin_handler(ctx: Context) -> Any:
    """GET /admin/dispatches: recent device dispatches (DispatchRecords),
    newest first — the layer below /admin/requests. ``?kind=`` filters
    (prefill, prefill_chunk, decode_chunk, warmup_compile, device_probe);
    ``?limit=`` bounds the page (default 100). An in-flight (or wedged)
    dispatch appears with status "running"."""
    from gofr_tpu.errors import HTTPError, InvalidParamError
    from gofr_tpu.tpu.introspect import DISPATCH_KINDS

    _check_admin(ctx)
    if ctx.tpu is None:
        raise HTTPError(503, "tpu not configured (set MODEL_NAME)")
    try:
        limit = int(ctx.param("limit") or "100")
    except ValueError:
        raise InvalidParamError('"limit" must be an integer') from None
    if limit < 1:
        raise InvalidParamError('"limit" must be >= 1')
    kind = ctx.param("kind") or None
    if kind is not None and kind not in DISPATCH_KINDS:
        raise InvalidParamError(
            f'"kind" must be one of {", ".join(DISPATCH_KINDS)}'
        )
    records = ctx.tpu.timeline.records(limit=limit, kind=kind)
    return {"dispatches": records, "count": len(records)}


def anomalies_admin_handler(ctx: Context) -> Any:
    """GET /admin/anomalies: the anomaly surface — typed events, newest
    first: the SLO engine's burn verdicts (``slo_fast_burn`` /
    ``slo_slow_burn``) from its evidence ring. ``?kind=`` / ``?cause=``
    filter; ``?limit=`` bounds the page (default 100). A healthy
    process serves an EMPTY list — every entry here is a regression
    with evidence attached."""
    from gofr_tpu.anomaly import ANOMALY_CAUSES
    from gofr_tpu.errors import HTTPError, InvalidParamError

    _check_admin(ctx)
    slo = getattr(ctx.container, "slo", None)
    if slo is None:
        raise HTTPError(503, "no anomaly ring on this process (set SLO=on)")
    ring = slo.ring
    try:
        limit = int(ctx.param("limit") or "100")
    except ValueError:
        raise InvalidParamError('"limit" must be an integer') from None
    if limit < 1:
        raise InvalidParamError('"limit" must be >= 1')
    cause = ctx.param("cause") or None
    if cause is not None and cause not in ANOMALY_CAUSES:
        raise InvalidParamError(
            f'"cause" must be one of {", ".join(ANOMALY_CAUSES)}'
        )
    kind = ctx.param("kind") or None
    events = ring.events(limit=limit, kind=kind, cause=cause)
    return {
        "anomalies": events,
        "count": len(events),
        "stats": ring.stats(),
    }


def timeseries_admin_handler(ctx: Context) -> Any:
    """GET /admin/timeseries: retained metric history from the timebase
    ring. ``?metric=`` (required) names a registered metric;
    ``?labels=k:v,k2:v2`` filters label-sets by subset match;
    ``?window=`` bounds the lookback in seconds (default: the whole
    ring). Counters and histograms carry a derived per-second ``rate``
    series next to the raw cumulative points."""
    from gofr_tpu.errors import InvalidParamError

    _check_admin(ctx)
    metric = ctx.param("metric")
    if not metric:
        raise InvalidParamError('"metric" is required (a registered metric name)')
    labels: dict[str, str] = {}
    raw_labels = ctx.param("labels") or ""
    for part in raw_labels.split(","):
        part = part.strip()
        if not part:
            continue
        sep = ":" if ":" in part else "="
        name, found, value = part.partition(sep)
        if not found or not name:
            raise InvalidParamError(
                '"labels" must be comma-separated name:value pairs'
            )
        labels[name.strip()] = value.strip()
    window = None
    raw_window = ctx.param("window")
    if raw_window:
        try:
            window = float(raw_window)
        except ValueError:
            raise InvalidParamError(
                '"window" must be a number of seconds'
            ) from None
        if window <= 0:
            raise InvalidParamError('"window" must be > 0')
    result = ctx.container.timebase.series(
        metric, labels=labels or None, window=window
    )
    if result is None:
        raise InvalidParamError(
            f'metric "{metric}" unknown to the timebase (not registered, '
            "or no snapshot taken yet)"
        )
    result["timebase"] = ctx.container.timebase.stats()
    return result


def overview_admin_handler(ctx: Context) -> Any:
    """GET /admin/overview: the one-page ops rollup — engine state,
    req/s and TTFT p95 TRENDS from the timebase ring, stall/cache/
    compile counters, the SLO snapshot, in-flight requests, and the
    postmortem inventory. One request instead of six; every field is a
    host-side read, so it answers while wedged."""
    _check_admin(ctx)
    container = ctx.container
    timebase = container.timebase
    out: dict[str, Any] = {
        "ts": time.time(),  # gofrlint: wall-clock — /admin/overview response timestamp (display)
        "timebase": timebase.stats(),
        "requests_in_flight": container.telemetry.active_count(),
        "slo": container.telemetry.slo(window_s=300.0),
        "req_per_sec": _trend(timebase.rate_total("gofr_http_requests_total")),
        "ttft_p95_s": _trend(
            timebase.hist_quantile_trend("gofr_tpu_ttft_seconds", 0.95)
        ),
        "postmortems": container.postmortem.list()[-5:],
    }
    # SLO headline: worst fast-window burn + thinnest budget + who is
    # alerting (the page's loudest line when non-empty); "slo" above
    # stays the raw-percentile view
    slo = getattr(container, "slo", None)
    out["slo_budget"] = slo.headline() if slo is not None else None
    # tenant pressure: top talkers by token volume from the bounded
    # sketch (never a full listing — that is /admin/tenants)
    out["tenants"] = container.tenants.overview()
    tpu = container.tpu
    if tpu is None:
        out["engine"] = None
        return out
    engine = tpu.engine.snapshot()
    out["engine"] = {
        "state": engine["state"],
        "detail": engine["detail"],
        "since": engine["since"],
    }
    out["model"] = tpu.model_name
    out["platform"] = tpu.platform
    out["watchdog"] = tpu.watchdog.snapshot()
    out["dispatches"] = tpu.timeline.stats()
    batcher = getattr(tpu, "batcher", None)
    out["queue_depth"] = batcher._depth() if batcher is not None else None
    pool = getattr(tpu, "decode_pool", None)
    out["decode_pool"] = pool.occupancy() if pool is not None else None
    registry = container.metrics
    out["compiles_total"] = sum(
        registry.counter(
            "gofr_tpu_compiles_total", labels=("kind",)
        ).data().values()
    )
    cache_counter = registry.counter(
        "gofr_tpu_cache_events_total", labels=("cache", "event")
    )
    out["cache_events"] = {
        "/".join(key): value for key, value in cache_counter.data().items()
    }
    return out


def _trend(points: list) -> dict[str, Any]:
    """A trend series plus its latest value (the rollup's headline)."""
    return {
        "now": points[-1][1] if points else None,
        "trend": points,
    }


def fleet_admin_handler(ctx: Context) -> Any:
    """GET /admin/fleet: the fleet front door on one page — rotation
    state + probe evidence per replica, breaker states, outstanding
    depths, quota stats, drain status, and the recent route records
    (which replica served each request, retries, shed verdicts).
    Registered by ``gofr_tpu.fleet.wire_fleet``; 503 on a process that
    is not a router."""
    from gofr_tpu.errors import HTTPError

    _check_admin(ctx)
    fleet = getattr(ctx.container, "fleet", None)
    if fleet is None:
        raise HTTPError(503, "fleet not configured (set FLEET_REPLICAS)")
    from gofr_tpu.errors import InvalidParamError

    snapshot = fleet.snapshot()
    request_id = ctx.param("request_id") or ctx.param("trace_id") or None
    try:
        limit = int(ctx.param("limit") or "0")
    except ValueError:
        raise InvalidParamError('"limit" must be an integer') from None
    if request_id:
        snapshot["routes"] = fleet.records(
            request_id=request_id, limit=limit or 50
        )
    elif limit > 0:
        # trace capture pages deeper than the default view
        snapshot["routes"] = fleet.records(limit=limit)
    return snapshot


def fleet_trace_handler(ctx: Context) -> Any:
    """GET /admin/fleet/trace/{id}: ONE causal timeline for a request id
    across every process it touched — the router's route record joined
    with each attempt's replica-side flight record (matched on the
    ``origin`` block the X-Gofr-Hop header stamped) and the KV-transfer
    ledger entries from donor and receiver, plus a latency decomposition
    (router overhead / replica queue / device TTFT / stream). A replica
    that is down or mid-restart degrades the trace to
    ``partial: true`` with the gap named — never a 500."""
    from gofr_tpu.errors import HTTPError, InvalidParamError
    from gofr_tpu.fleet import trace as fleet_trace
    from gofr_tpu.telemetry import sanitize_request_id

    _check_admin(ctx)
    fleet = getattr(ctx.container, "fleet", None)
    if fleet is None:
        raise HTTPError(503, "fleet not configured (set FLEET_REPLICAS)")
    request_id = sanitize_request_id(ctx.request.path_param("id"))
    if request_id is None:
        raise InvalidParamError(
            '"id" must be a request id ([A-Za-z0-9._-], <= 64 chars)'
        )
    routes = fleet.records(limit=10, request_id=request_id)
    if not routes:
        raise HTTPError(
            404,
            f"no route record for request id '{request_id}' "
            "(expired from the ring, or served by another router)",
        )
    route = routes[0]  # newest first: the latest routing of this id
    timeout_s = float(
        ctx.container.config.get_or_default("FLEET_TRACE_SCRAPE_TIMEOUT_S", "1")
    )
    evidence = fleet_trace.gather_evidence(
        fleet, request_id, route, timeout_s=timeout_s
    )
    return fleet_trace.assemble(request_id, route, **evidence)


def fleet_overview_handler(ctx: Context) -> Any:
    """GET /admin/fleet/overview: the fleet-wide ops rollup — one page
    built from evidence the router already holds (replica snapshots and
    the prober's piggybacked engine scrapes) plus the router's own
    timebase trends. No fan-out scrape on request: a replica that
    stopped answering shows its last-scraped state, it does not stall
    the overview. The per-process ``/admin/overview`` stays the
    deep-dive; this is the incident headline across N replicas."""
    from gofr_tpu.errors import HTTPError

    _check_admin(ctx)
    container = ctx.container
    fleet = getattr(container, "fleet", None)
    if fleet is None:
        raise HTTPError(503, "fleet not configured (set FLEET_REPLICAS)")
    states: dict[str, int] = {}
    restarts = 0
    kv_free = kv_total = 0
    kv_seen = False
    transfers: dict[str, int] = {}
    brownout_max = 0
    slo_alerting: list[dict[str, Any]] = []
    slo_worst_burn = None
    slo_worst_replica = None
    slo_budget_min = None
    slo_alerts_total = 0
    slo_seen = False
    tenant_totals: dict[str, dict[str, int]] = {}
    tenants_tracked = 0
    replicas = []
    for replica in fleet.replica_set.replicas:
        snap = replica.snapshot()
        state = snap.get("state") or "unknown"
        states[state] = states.get(state, 0) + 1
        restarts += int(snap.get("restarts") or 0)
        engine = snap.get("engine") or {}
        if isinstance(engine.get("kv_free"), int) and isinstance(
            engine.get("kv_total"), int
        ):
            kv_seen = True
            kv_free += engine["kv_free"]
            kv_total += engine["kv_total"]
        ledger = engine.get("kv_transfer") or {}
        for outcome, count in ledger.items():
            # outcome counters only: skip the recents lists and the
            # `enabled` bool (bool IS an int to isinstance)
            if isinstance(count, int) and not isinstance(count, bool):
                transfers[outcome] = transfers.get(outcome, 0) + count
        level = engine.get("brownout_level")
        if isinstance(level, int):
            brownout_max = max(brownout_max, level)
        # SLO + tenant rollup off the same piggybacked engine scrape —
        # router-side aggregation only, never a fan-out on request
        slo = engine.get("slo") or {}
        burn = slo.get("worst_burn")
        if isinstance(burn, (int, float)) and not isinstance(burn, bool):
            slo_seen = True
            if slo_worst_burn is None or burn > slo_worst_burn:
                slo_worst_burn = burn
                slo_worst_replica = snap.get("name")
        remaining = slo.get("budget_remaining_min")
        if isinstance(remaining, (int, float)) and not isinstance(
            remaining, bool
        ):
            if slo_budget_min is None or remaining < slo_budget_min:
                slo_budget_min = remaining
        for objective in slo.get("alerting") or []:
            slo_alerting.append(
                {"replica": snap.get("name"), "objective": objective}
            )
        slo_alerts_total += int(slo.get("alerts_total") or 0)
        tenants = engine.get("tenants") or {}
        tenants_tracked += int(tenants.get("tracked") or 0)
        for row in tenants.get("top") or []:
            name = row.get("tenant")
            if not name:
                continue
            agg = tenant_totals.setdefault(
                name, {"requests": 0, "tokens": 0, "sheds": 0}
            )
            for field in ("requests", "tokens", "sheds"):
                agg[field] += int(row.get(field) or 0)
        replicas.append({
            "name": snap.get("name"),
            "state": state,
            "role": snap.get("role"),
            "outstanding": snap.get("outstanding"),
            "saturated": snap.get("saturated"),
            "restarts": snap.get("restarts"),
            "queue_depth": engine.get("queue_depth"),
            "kv_free": engine.get("kv_free"),
            "kv_total": engine.get("kv_total"),
            "brownout_level": level,
            # SLO headline per replica: which box is burning its budget
            "slo_worst_burn": slo.get("worst_burn"),
            "slo_alerting": slo.get("alerting"),
            "tenants_tracked": tenants.get("tracked"),
        })
    timebase = container.timebase
    return {
        "ts": time.time(),  # gofrlint: wall-clock — overview response timestamp (display)
        "router_id": fleet.router_id,
        "replicas": replicas,
        "states": states,
        "restarts_total": restarts,
        "kv_utilization": (
            round(1.0 - kv_free / kv_total, 4)
            if kv_seen and kv_total else None
        ),
        "kv_free": kv_free if kv_seen else None,
        "kv_total": kv_total if kv_seen else None,
        "kv_transfers": transfers,
        "brownout_level_max": brownout_max,
        "slo": {
            "worst_burn": slo_worst_burn,
            "worst_replica": slo_worst_replica,
            "budget_remaining_min": slo_budget_min,
            "alerting": slo_alerting,
            "alerts_total": slo_alerts_total,
        } if slo_seen else None,
        "tenants": {
            "tracked": tenants_tracked,
            # fleet-wide top talkers: per-replica top lists merged and
            # re-ranked by token volume (exact within what each replica's
            # sketch tracked)
            "top": sorted(
                (dict(v, tenant=k) for k, v in tenant_totals.items()),
                key=lambda row: (row["tokens"], row["requests"]),
                reverse=True,
            )[:5],
        },
        "req_per_sec": _trend(
            timebase.rate_total("gofr_tpu_router_requests_total")
        ),
        "upstream_p95_s": _trend(
            timebase.hist_quantile_trend("gofr_tpu_router_upstream_seconds", 0.95)
        ),
        "in_flight": fleet.in_flight,
        "draining": fleet.draining,
    }


def kv_export_handler(ctx: Context) -> Response:
    """GET /admin/kv/{hash}: the donor side of a cross-replica paged-KV
    transfer (disaggregated prefill/decode). Serves the cached block
    table whose prompt hashes to ``{hash}`` in the kvwire format —
    versioned header, per-block CRC frames, mandatory trailer — so the
    pulling replica can detect truncation, corruption, and version
    skew and fall back to local prefill.

    Contract points the fleet depends on:

    - the entry's blocks are PINNED (increfed) for the duration of the
      stream and released when the response closes — an aborted pull
      never leaks refcounts, and a dead serving thread is covered by
      the pin's own bounded-lifetime timer (``KV_TRANSFER_PIN_TTL_S``);
    - the PR 10 deadline budget applies (``X-Request-Deadline-Ms``,
      default ``KV_TRANSFER_TIMEOUT_S``): an expired budget stops the
      stream mid-body — a deliberate truncation the receiver detects;
    - 404 when the entry was evicted between advertise and pull (or
      was never here, or transfer is off) — never a 500."""
    from gofr_tpu.deadline import parse_deadline
    from gofr_tpu.errors import HTTPError, InvalidParamError

    _check_admin(ctx)
    tpu = ctx.container.tpu
    if tpu is None:
        raise HTTPError(503, "tpu not configured (set MODEL_NAME)")
    if not getattr(tpu, "kv_transfer_enabled", False):
        raise HTTPError(404, "KV transfer disabled (KV_TRANSFER=off)")
    prompt_hash = (ctx.request.path_param("hash") or "").strip().lower()
    if not prompt_hash or len(prompt_hash) > 64 or any(
        c not in "0123456789abcdef" for c in prompt_hash
    ):
        raise InvalidParamError('"hash" must be a hex prompt hash')
    default_s = float(
        ctx.container.config.get_or_default("KV_TRANSFER_TIMEOUT_S", "2")
    )
    deadline = parse_deadline(
        ctx.request.header("X-Request-Deadline-Ms"), default_s
    )
    # the requesting id (the receiver forwards its own origin id):
    # lands in the donor's served ledger so /admin/fleet/trace/<id>
    # can show which donor streamed this request's warm blocks
    from gofr_tpu.telemetry import sanitize_request_id

    export = tpu.kv_export(
        prompt_hash,
        request_id=sanitize_request_id(
            ctx.request.header("X-Gofr-Request-Id")
        ) or "",
    )
    if export is None:
        raise HTTPError(
            404,
            f"no cached KV for {prompt_hash} (evicted between advertise "
            "and pull, never seen here, or paged KV inactive)",
        )
    spec, table, arena, pin = export
    from gofr_tpu.fleet.kvwire import (
        CONTENT_TYPE,
        encode_block,
        encode_header,
        encode_trailer,
    )

    n_blocks = int(spec["n_blocks"])

    executor = ctx.container.handler_executor

    async def frames() -> Any:
        # runs on the event loop after the handler returns; the pin is
        # released on EVERY exit — completion, client abort (the server
        # acloses the stream), or an exception — and the TTL timer
        # backstops a loop that never finalizes this generator
        import asyncio

        loop = asyncio.get_running_loop()
        try:
            yield encode_header(spec)
            for j in range(n_blocks):
                if pin.expired:
                    return  # the TTL guard took the blocks back
                if deadline is not None and deadline.expired():
                    return  # budget spent: truncate; the receiver's
                    # trailer check turns this into a clean fallback
                # a real arena's per-block export is a synchronous
                # device->host copy — off the serving loop, or every
                # concurrent stream on the donor stalls per block
                payload = await loop.run_in_executor(
                    executor, arena.export_block_payload, table, j
                )
                yield encode_block(j, payload)
            yield encode_trailer(n_blocks)
        finally:
            pin.release()

    return Response(
        status=200,
        headers={"Content-Type": CONTENT_TYPE},
        stream=frames(),
    )


def postmortem_list_handler(ctx: Context) -> Any:
    """GET /admin/postmortem: the on-disk bundle inventory."""
    _check_admin(ctx)
    store = ctx.container.postmortem
    return {"dir": store.directory, "bundles": store.list()}


def postmortem_trigger_handler(ctx: Context) -> Any:
    """POST /admin/postmortem: write a bundle NOW (operator trigger —
    bypasses the automatic-trigger rate limit). Body is optional:
    ``{"detail": "..."}`` annotates the bundle."""
    from gofr_tpu.errors import HTTPError

    _check_admin(ctx)
    detail = ""
    try:
        body = ctx.bind() if ctx.request.body else {}
        if isinstance(body, dict):
            detail = str(body.get("detail") or "")
    except Exception:
        pass  # empty/garbage body: an unannotated bundle still helps
    path = ctx.container.postmortem.write(
        reason="manual", detail=detail, force=True
    )
    if path is None:
        raise HTTPError(500, "postmortem write failed (see server log)")
    return {"path": path, "reason": "manual"}


def _profiler_gauge(ctx: Context) -> Any:
    """The profiler-activity gauge (1 while a trace is capturing) — an
    unnoticed left-running trace degrades serving latency and fills
    disk, so it must be alertable."""
    return ctx.container.metrics.gauge(
        "gofr_tpu_profiler_active",
        "1 while an XLA profiler trace is capturing (0 otherwise)",
    )


def profiler_status_handler(ctx: Context) -> Any:
    from gofr_tpu.profiling import profiler

    _check_admin(ctx)
    status = profiler().status()
    _profiler_gauge(ctx).set(1.0 if status["state"] == "tracing" else 0.0)
    return status


def profiler_start_handler(ctx: Context) -> Any:
    from gofr_tpu.errors import HTTPError
    from gofr_tpu.profiling import profiler

    _check_admin(ctx)
    body = {}
    try:
        body = ctx.bind() or {}
    except Exception:
        pass  # empty body is fine
    if not isinstance(body, dict):
        from gofr_tpu.errors import InvalidParamError

        raise InvalidParamError('body (expected {"dir": ...} or empty)')
    try:
        # an active trace REJECTS with 409 (below) instead of silently
        # restarting: restarting would discard the in-flight capture
        out = profiler().start(body.get("dir"))
    except RuntimeError as exc:
        raise HTTPError(409, str(exc)) from exc
    _profiler_gauge(ctx).set(1.0)
    return out


def profiler_stop_handler(ctx: Context) -> Any:
    from gofr_tpu.errors import HTTPError
    from gofr_tpu.profiling import profiler

    _check_admin(ctx)
    try:
        out = profiler().stop()
    except RuntimeError as exc:
        raise HTTPError(409, str(exc)) from exc
    _profiler_gauge(ctx).set(0.0)
    return out
