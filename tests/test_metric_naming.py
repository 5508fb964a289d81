"""Lint-style guard on metric naming: every metric registered through
the container's registry must follow the framework convention —
``gofr_`` prefix, snake_case, and a recognized unit/dimension suffix —
so dashboard and alert queries stay stable as metrics grow. Scans the
package source for registration calls (the registry API takes literal
names), the same way a linter would."""

import pathlib
import re

import gofr_tpu

PKG_DIR = pathlib.Path(gofr_tpu.__file__).parent

# registry.counter("name", ...) / metrics.gauge(\n    "name", ... — the
# name literal is the first argument, possibly on the next line
_REGISTRATION = re.compile(
    r'\.(counter|gauge|histogram)\(\s*\n?\s*"([^"]+)"', re.MULTILINE
)

# unit suffixes (prometheus convention) plus the framework's recognized
# dimensionless suffixes (counts of things whose unit IS the thing)
_COUNTER_SUFFIXES = ("_total",)
_HISTOGRAM_SUFFIXES = ("_seconds", "_bytes", "_size")
_GAUGE_SUFFIXES = (
    "_seconds", "_bytes", "_total", "_depth", "_ratio", "_entries",
    "_active", "_acceptance", "_state", "_blocks", "_size", "_level",
    "_per_dispatch", "_rate", "_remaining",
)


def _registrations():
    found = []
    for path in sorted(PKG_DIR.rglob("*.py")):
        source = path.read_text(encoding="utf-8")
        for kind, name in _REGISTRATION.findall(source):
            found.append((str(path.relative_to(PKG_DIR)), kind, name))
    return found


def test_scanner_sees_the_known_registrations():
    names = {name for _, _, name in _registrations()}
    # sanity that the regex actually matches the codebase's idiom — a
    # refactor that breaks the scan must fail here, not silently pass
    assert {"gofr_http_requests_total", "gofr_tpu_ttft_seconds",
            "gofr_tpu_batch_size", "gofr_tpu_queue_depth"} <= names
    # the interference-scheduler suite (tpu/scheduler.py, batcher
    # padded-FLOP accounting, pool reject reasons) stays scan-visible
    assert {"gofr_tpu_prefill_chunks_total", "gofr_tpu_sched_defer_seconds",
            "gofr_tpu_prefill_padded_tokens_total",
            "gofr_tpu_pool_reject_total"} <= names
    # the engine-introspection suite (tpu/introspect.py + device compile/
    # cache observability + the profiler-activity gauge) stays visible too
    assert {"gofr_tpu_engine_state", "gofr_tpu_device_stalls_total",
            "gofr_tpu_dispatches_total", "gofr_tpu_dispatch_seconds",
            "gofr_tpu_compile_seconds", "gofr_tpu_compiles_total",
            "gofr_tpu_cache_events_total",
            "gofr_tpu_profiler_active"} <= names
    # the paged-KV block accounting (tpu/kv_blocks.py BlockPool)
    assert {"gofr_tpu_kv_blocks", "gofr_tpu_kv_evictions_total"} <= names
    # the sharded-serving suite (TPU_MESH): live mesh shape + the
    # features a mesh shape degraded (tpu/device.py)
    assert {"gofr_tpu_mesh_axis_size",
            "gofr_tpu_mesh_degrade_total"} <= names
    # the cardinality guard's overflow ledger (metrics.py Registry)
    assert "gofr_tpu_metrics_dropped_series_total" in names
    # deadline-aware serving + overload brownout (PR 10)
    assert {"gofr_tpu_deadline_exceeded_total",
            "gofr_tpu_cancellations_total",
            "gofr_tpu_brownout_level",
            "gofr_tpu_brownout_shed_total"} <= names
    # the fleet front door (fleet/router.py FleetRouter._init_metrics):
    # every routing/retry/shed/breaker decision must stay scan-visible
    assert {"gofr_tpu_router_requests_total",
            "gofr_tpu_router_retries_total",
            "gofr_tpu_router_shed_total",
            "gofr_tpu_router_breaker_transitions_total",
            "gofr_tpu_router_breaker_state",
            "gofr_tpu_router_replica_state",
            "gofr_tpu_router_outstanding_depth",
            "gofr_tpu_router_inflight_depth",
            "gofr_tpu_router_upstream_seconds"} <= names
    # disaggregated prefill/decode (PR 11): the cross-replica KV
    # transfer ledger + the quota redis fail-open counter
    assert {"gofr_tpu_kv_transfer_total",
            "gofr_tpu_router_quota_fallback_total"} <= names
    # pooled speculative decoding (tpu/spec_pool.py): the accept-ratio
    # EMA and tokens-per-dispatch gauges stay scan-visible
    assert {"gofr_tpu_spec_accept_ratio",
            "gofr_tpu_spec_tokens_per_dispatch"} <= names
    # fleet-wide tracing (PR 16): the per-hop latency decomposition
    # histogram (router.py) and the zipkin exporter drop counter
    # (tracing.py attach_metrics)
    assert {"gofr_tpu_router_hop_seconds",
            "gofr_tpu_trace_export_failures_total"} <= names
    # SLO engine (slo.py) + bounded tenant metering (telemetry.py
    # TenantLedger): burn/budget surfaces and the sketch's OWN
    # cardinality ledger — per-tenant series are forbidden by design
    assert {"gofr_tpu_slo_burn_rate",
            "gofr_tpu_slo_budget_remaining",
            "gofr_tpu_slo_burn_alerts_total",
            "gofr_tpu_tenants_tracked_entries",
            "gofr_tpu_tenant_overflow_total"} <= names
    # the device serving core (tpu/device.py _init_metrics is the one
    # registration home — GFL007 — for request/token/memory accounting,
    # speculative acceptance and the prefix-cache surfaces)
    assert {"gofr_tpu_requests_total",
            "gofr_tpu_tokens_total",
            "gofr_tpu_device_memory_bytes",
            "gofr_tpu_spec_acceptance",
            "gofr_tpu_prefix_hit_ratio",
            "gofr_tpu_prefix_partial_hit_ratio",
            "gofr_tpu_prefix_entries"} <= names
    # continuous batching internals: queue-wait histogram (batcher.py),
    # the live decode-slot gauge and the wait for a seat in a full pool
    # (decode_pool.py)
    assert {"gofr_tpu_queue_wait_seconds",
            "gofr_tpu_decode_slots_active",
            "gofr_tpu_pool_seat_wait_seconds"} <= names
    # the transport's own clock: how late the HTTP server's event loop
    # runs (app.py wires http/server.py's LoopClock)
    assert "gofr_tpu_http_loop_lag_seconds" in names
    # crash-recovery surfaces: engine recovery outcomes (tpu/recovery.py),
    # journal resume modes (telemetry.py), and the fleet's replica
    # restart / stream-resume ledgers (fleet/router.py)
    assert {"gofr_tpu_engine_recoveries_total",
            "gofr_tpu_journal_resumes_total",
            "gofr_tpu_router_replica_restarts_total",
            "gofr_tpu_router_stream_resumes_total"} <= names
    assert len(names) >= 35


def test_suffix_tables_match_gofrlint():
    """GFL005 (tools/gofrlint.py) is the static half of this exact
    convention: the two suffix tables must stay in LOCKSTEP or a new
    metric family passes one gate and fails the other with a split
    verdict."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gofrlint_naming", PKG_DIR.parent / "tools" / "gofrlint.py"
    )
    gofrlint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gofrlint)
    assert gofrlint._COUNTER_SUFFIXES == _COUNTER_SUFFIXES
    assert gofrlint._HISTOGRAM_SUFFIXES == _HISTOGRAM_SUFFIXES
    assert gofrlint._GAUGE_SUFFIXES == _GAUGE_SUFFIXES


def test_every_metric_follows_the_naming_convention():
    problems = []
    for where, kind, name in _registrations():
        if not name.startswith("gofr_"):
            problems.append(f"{where}: {name} missing gofr_ prefix")
            continue
        if not re.fullmatch(r"[a-z][a-z0-9_]*", name) or "__" in name:
            problems.append(f"{where}: {name} is not snake_case")
            continue
        if kind == "counter" and not name.endswith(_COUNTER_SUFFIXES):
            problems.append(f"{where}: counter {name} must end in _total")
        elif kind == "histogram" and not name.endswith(_HISTOGRAM_SUFFIXES):
            problems.append(
                f"{where}: histogram {name} needs a unit suffix "
                f"{_HISTOGRAM_SUFFIXES}"
            )
        elif kind == "gauge" and not name.endswith(_GAUGE_SUFFIXES):
            problems.append(
                f"{where}: gauge {name} needs a unit/dimension suffix "
                f"{_GAUGE_SUFFIXES}"
            )
    assert not problems, "\n".join(problems)


def test_registered_names_at_runtime_match_convention():
    """Belt and braces: metrics actually registered by a wired container
    (middleware + batcher instantiation) pass the same check — catches
    dynamically composed names the source scan cannot see."""
    from gofr_tpu.http.middleware import metrics_middleware
    from gofr_tpu.metrics import Registry
    from gofr_tpu.tpu.batcher import DynamicBatcher

    registry = Registry()
    metrics_middleware(registry)
    batcher = DynamicBatcher(lambda batch: batch, metrics=registry, name="t")
    try:
        for name in registry._metrics:
            assert name.startswith("gofr_"), name
            assert re.fullmatch(r"[a-z][a-z0-9_]*", name), name
    finally:
        batcher.close()


# -- exposition validity: strict parser over the full /metrics output ---------
#
# The naming checks above guard the NAMES; these guard the WIRE FORMAT.
# A hand-rolled expositor can drift in ways Prometheus silently
# tolerates and OpenMetrics parsers reject (repr() floats, integer `le`
# values, missing # EOF, broken escaping) — so both formats are parsed
# with a STRICT reader and every structural rule is asserted.

_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\\n]|\\["\\n])*)"')
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?"
    r" (-?(?:[0-9]+(?:\.[0-9]+)?(?:e-?[0-9]+)?)|\+Inf|-Inf|NaN)$"
)
_EXEMPLAR_RE = re.compile(
    r"^\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\"\\n])*\",?)*)\}"
    r" -?[0-9]+(?:\.[0-9]+)?(?:e-?[0-9]+)?( [0-9]+\.[0-9]+)?$"
)


def _parse_labels(raw):
    """Parse `{a="b",c="d"}` strictly: every byte must be consumed by
    well-formed, correctly escaped label pairs."""
    if not raw:
        return {}
    assert raw.startswith("{") and raw.endswith("}"), raw
    inner = raw[1:-1]
    labels = {}
    pos = 0
    while pos < len(inner):
        m = _LABEL_RE.match(inner, pos)
        assert m, f"malformed label at {inner[pos:]!r} in {raw!r}"
        assert m.group(1) not in labels, f"duplicate label in {raw!r}"
        labels[m.group(1)] = m.group(2)
        pos = m.end()
        if pos < len(inner):
            assert inner[pos] == ",", f"bad label separator in {raw!r}"
            pos += 1
    return labels


def parse_exposition(text, openmetrics=False):
    """Strict structural parse of a Prometheus/OpenMetrics text body.
    Returns {family: {"kind", "help", "samples": [(name, labels, value,
    exemplar)]}} and asserts every format rule on the way."""
    assert text.endswith("\n"), "exposition must end with a newline"
    lines = text.split("\n")[:-1]
    if openmetrics:
        assert lines and lines[-1] == "# EOF", "OpenMetrics must end with # EOF"
        lines = lines[:-1]
        assert "# EOF" not in lines, "# EOF before the end of the body"
    else:
        assert "# EOF" not in lines, "# EOF is OpenMetrics-only"
    families = {}
    current = None
    for line in lines:
        assert line == line.rstrip(), f"trailing whitespace: {line!r}"
        if line.startswith("# HELP "):
            name, _, help_ = line[len("# HELP "):].partition(" ")
            assert name not in families, f"duplicate family {name}"
            families[name] = {"kind": None, "help": help_, "samples": []}
            current = name
            continue
        if line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            assert name == current, "# TYPE must directly follow its # HELP"
            assert kind in ("counter", "gauge", "histogram"), kind
            assert families[name]["kind"] is None, f"duplicate TYPE {name}"
            families[name]["kind"] = kind
            continue
        assert not line.startswith("#"), f"unexpected comment {line!r}"
        assert current is not None, f"sample before any family: {line!r}"
        kind = families[current]["kind"]
        assert kind is not None, f"sample before # TYPE: {line!r}"
        sample, sep, exemplar = line.partition(" # ")
        if sep:
            assert openmetrics and kind == "histogram", (
                f"exemplar outside an OpenMetrics histogram: {line!r}"
            )
            assert _EXEMPLAR_RE.match("# " + exemplar) or _EXEMPLAR_RE.match(
                exemplar
            ), f"malformed exemplar {exemplar!r}"
        m = _SAMPLE_RE.match(sample)
        assert m, f"malformed sample line {sample!r}"
        name, raw_labels, value = m.groups()
        labels = _parse_labels(raw_labels)
        if kind == "histogram":
            assert name in (
                current + "_bucket", current + "_sum", current + "_count"
            ), f"sample {name} not of histogram family {current}"
            if name.endswith("_bucket"):
                assert "le" in labels, f"bucket without le: {line!r}"
                if openmetrics:
                    le = labels["le"]
                    assert le == "+Inf" or "." in le, (
                        f"OpenMetrics le must be a canonical float: {line!r}"
                    )
        elif kind == "counter" and openmetrics:
            assert name == current + "_total", (
                f"OpenMetrics counter sample {name} must be "
                f"{current}_total"
            )
        else:
            assert name == current, f"sample {name} outside family {current}"
        families[current]["samples"].append(
            (name, labels, value, exemplar if sep else None)
        )
    return families


def _assert_histogram_invariants(family, data):
    """Cumulative bucket monotonicity, +Inf == _count, sum/count pairing
    — per label-set."""
    series = {}
    for name, labels, value, _ in data["samples"]:
        key = tuple(sorted(
            (k, v) for k, v in labels.items() if k != "le"
        ))
        entry = series.setdefault(key, {"buckets": [], "sum": None, "count": None})
        if name.endswith("_bucket"):
            entry["buckets"].append((labels["le"], float(value)))
        elif name.endswith("_sum"):
            entry["sum"] = float(value)
        elif name.endswith("_count"):
            entry["count"] = float(value)
    for key, entry in series.items():
        assert entry["sum"] is not None and entry["count"] is not None, (
            f"{family}{key}: missing _sum/_count"
        )
        les = [le for le, _ in entry["buckets"]]
        assert les[-1] == "+Inf", f"{family}{key}: last bucket must be +Inf"
        bounds = [float("inf") if le == "+Inf" else float(le) for le in les]
        assert bounds == sorted(bounds), f"{family}{key}: le out of order"
        counts = [c for _, c in entry["buckets"]]
        assert counts == sorted(counts), (
            f"{family}{key}: cumulative bucket counts must be monotonic"
        )
        assert counts[-1] == entry["count"], (
            f"{family}{key}: +Inf bucket != _count"
        )


def _tricky_registry():
    """A registry wired the way the container wires it (middleware +
    batcher + device-shaped metrics), then poked with the values that
    historically break expositions: label escaping, float formatting,
    exemplars, +Inf overflow."""
    from gofr_tpu.http.middleware import metrics_middleware
    from gofr_tpu.metrics import Registry
    from gofr_tpu.tpu.batcher import DynamicBatcher

    registry = Registry(
        exemplar_provider=lambda: {"trace_id": "abc123", "dispatch_id": "7"}
    )
    metrics_middleware(registry)
    batcher = DynamicBatcher(lambda batch: batch, metrics=registry, name="t")
    batcher.close()
    counter = registry.counter(
        "gofr_http_requests_total", labels=("method", "path", "status")
    )
    counter.inc(method="GET", path='/esc"ape\\me\nnow', status="200")
    counter.inc(3, method="POST", path="/v1/chat/completions", status="500")
    registry.gauge("gofr_tpu_queue_depth").set(2.5)
    hist = registry.histogram(
        "gofr_tpu_ttft_seconds", "ttft", labels=("model", "op"),
        buckets=(0.1, 1.0, 2.5),
    )
    hist.observe(0.05, model="echo", op="generate")
    hist.observe(0.7, exemplar={"trace_id": "def456"}, model="echo", op="generate")
    hist.observe(99.0, model="echo", op="generate")  # +Inf overflow
    hist.observe(0.3, model='quo"te', op="infer")  # escaped label + exemplar
    return registry


def test_prometheus_exposition_parses_strictly():
    registry = _tricky_registry()
    families = parse_exposition(registry.expose(), openmetrics=False)
    assert families["gofr_http_requests_total"]["kind"] == "counter"
    # escaping round-trips: the parsed label equals the escaped form
    paths = {
        labels["path"]
        for _, labels, _, _ in families["gofr_http_requests_total"]["samples"]
    }
    assert '/esc\\"ape\\\\me\\nnow' in paths
    for family, data in families.items():
        if data["kind"] == "histogram":
            _assert_histogram_invariants(family, data)
    # no exemplars ever leak into the classic format
    assert all(
        ex is None
        for data in families.values()
        for _, _, _, ex in data["samples"]
    )


def test_openmetrics_exposition_parses_strictly():
    registry = _tricky_registry()
    families = parse_exposition(
        registry.expose(openmetrics=True), openmetrics=True
    )
    # counter families dropped their _total suffix; samples kept it
    assert families["gofr_http_requests"]["kind"] == "counter"
    assert all(
        name == "gofr_http_requests_total"
        for name, _, _, _ in families["gofr_http_requests"]["samples"]
    )
    for family, data in families.items():
        if data["kind"] == "histogram":
            _assert_histogram_invariants(family, data)
    # exemplars present, only on buckets, correctly formed (the regex
    # asserted syntax during parsing; here: the content arrived)
    ttft = families["gofr_tpu_ttft_seconds"]["samples"]
    exemplars = [ex for name, _, _, ex in ttft if ex is not None]
    assert exemplars, "ttft histogram lost its exemplars"
    assert any('trace_id="def456"' in ex for ex in exemplars)
    assert any('trace_id="abc123"' in ex for ex in exemplars)
    assert all(name.endswith("_bucket") for name, _, _, ex in ttft if ex)


def test_full_app_metrics_output_is_openmetrics_valid():
    """The tree-wide sweep, live: a wired container's ACTUAL registry —
    every default metric the container, middleware, and recorder
    register — must expose a strictly parseable body in both formats."""
    from gofr_tpu.config import EnvConfig
    from gofr_tpu.container import Container

    container = Container(EnvConfig(), wire=False)
    try:
        container.metrics.histogram(
            "gofr_http_request_duration_seconds", labels=("path",)
        ).observe(0.2, path="/v1/x")
        parse_exposition(container.metrics.expose(), openmetrics=False)
        families = parse_exposition(
            container.metrics.expose(openmetrics=True), openmetrics=True
        )
        assert "gofr_tpu_metrics_dropped_series" in families
    finally:
        container.close()
