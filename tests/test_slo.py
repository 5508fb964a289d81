"""SLO engine (gofr_tpu/slo.py) + bounded tenant metering
(telemetry.TenantLedger): unit semantics for target parsing, record
judging, the multi-window burn-rate latch, and the space-saving sketch,
plus the end-to-end spine on the no-JAX ``echo`` model — a deadline-miss
fault burst must trip the fast-burn page on ``/admin/slo/budget``,
``/admin/anomalies``, ``/metrics``, and the postmortem bundle, while a
healthy run raises ZERO alerts; and 5000 distinct tenants through the
serving surface must leave ``/metrics`` cardinality bounded while the
ledger's heavy hitters stay exact."""

import concurrent.futures
import hashlib
import json
import socket
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu.anomaly import ANOMALY_CAUSES, AnomalyRing
from gofr_tpu.metrics import Registry
from gofr_tpu.slo import (
    DEFAULT_TARGETS,
    Objective,
    SloEngine,
    parse_targets,
)
from gofr_tpu.telemetry import (
    FlightRecorder, TenantLedger, activate_record, activate_tenant,
)


@pytest.fixture(autouse=True)
def _no_record_left_active():
    """``recorder.start()`` binds the contextvar: a record left active
    would bleed into whichever file this worker runs next."""
    yield
    activate_record(None)


# -- unit: SLO_TARGETS parsing ------------------------------------------------

def test_parse_default_targets():
    objectives = {o.id: o for o in parse_targets(DEFAULT_TARGETS)}
    assert set(objectives) == {
        "availability", "shed_rate", "tier9.availability",
    }
    assert objectives["availability"].budget == pytest.approx(0.001)
    assert objectives["shed_rate"].budget == pytest.approx(0.05)
    assert objectives["tier9.availability"].tier == 9
    assert objectives["tier9.availability"].budget == pytest.approx(0.0005)


def test_parse_scoped_and_latency_targets():
    objectives = {o.id: o for o in parse_targets(
        "model=echo:ttft_p95_ms=500; tier>=5:availability=0.99;"
        "tpot_p99_ms=40"
    )}
    assert set(objectives) == {
        "echo.ttft_p95_ms", "tier_ge5.availability", "tpot_p99_ms",
    }
    ttft = objectives["echo.ttft_p95_ms"]
    assert ttft.model == "echo"
    assert ttft.threshold_s == pytest.approx(0.5)
    assert ttft.budget == pytest.approx(0.05)  # p95 -> 5% may exceed
    assert objectives["tpot_p99_ms"].budget == pytest.approx(0.01)
    assert objectives["tier_ge5.availability"].tier_ge == 5


@pytest.mark.parametrize("spec", [
    "bogus=1",                      # unknown metric
    "availability",                 # no target
    "availability=lots",            # non-numeric target
    "availability=1.5",             # out of (0, 1)
    "ttft_p95_ms=-3",               # negative latency bound
    "tier=11:availability=0.9",     # tier out of 0..9
    "planet=mars:availability=0.9",  # unknown scope
    "model=:availability=0.9",      # empty model scope
    "tier=9:shed_rate=0.1",         # shed counters carry no scope
    "availability=0.9;availability=0.99",  # duplicate objective
])
def test_parse_rejects_malformed(spec):
    with pytest.raises(ValueError):
        parse_targets(spec)


# -- unit: Objective.judge ----------------------------------------------------

def _finished(recorder, status="ok", model="echo", priority=None,
              ttft_s=None, tokens_out=0):
    rec = recorder.start(model, "/test")
    if priority is not None:
        rec.priority = priority
    if ttft_s is not None:
        rec.t_first_token = rec.t_start + ttft_s
    rec.tokens_out = tokens_out
    recorder.finish(
        rec, status=status,
        error=RuntimeError("boom") if status == "error" else None,
    )
    return rec


def test_judge_availability_statuses_and_scopes():
    recorder = FlightRecorder(capacity=16)
    availability = Objective("availability", 0.999)
    assert availability.judge(_finished(recorder)) is False
    assert availability.judge(_finished(recorder, status="error")) is True
    assert availability.judge(
        _finished(recorder, status="deadline_exceeded")
    ) is True
    # a client hanging up is its verdict, not ours
    assert availability.judge(_finished(recorder, status="cancelled")) is None
    scoped = Objective("availability", 0.999, model="llama")
    assert scoped.judge(_finished(recorder, status="error")) is None
    tiered = Objective("availability", 0.999, tier=9)
    assert tiered.judge(_finished(recorder, status="error")) is None
    assert tiered.judge(
        _finished(recorder, status="error", priority=9)
    ) is True
    ge = Objective("availability", 0.999, tier_ge=5)
    assert ge.judge(_finished(recorder, status="error", priority=7)) is True
    assert ge.judge(_finished(recorder, status="error", priority=3)) is None


def test_judge_latency_bound_and_missing_measurement():
    recorder = FlightRecorder(capacity=16)
    bound = Objective("ttft_p95_ms", 200.0)
    assert bound.judge(_finished(recorder, ttft_s=0.05)) is False
    assert bound.judge(_finished(recorder, ttft_s=0.5)) is True
    # no first token + ok (e.g. an embeddings hit) = no sample
    assert bound.judge(_finished(recorder)) is None
    # no first token + deadline_exceeded IS a latency violation
    assert bound.judge(
        _finished(recorder, status="deadline_exceeded")
    ) is True


# -- unit: TenantLedger (space-saving sketch) ---------------------------------

def test_ledger_tracks_and_pages():
    ledger = TenantLedger(size=8)
    ledger.observe("t-a", requests=1, tokens_in=10, tokens_out=20)
    ledger.observe("t-a", requests=1, tokens_in=5, tokens_out=5)
    ledger.observe("t-b", requests=1, errors=1)
    ledger.shed("t-c")
    assert ledger.get("t-a")["tokens_out"] == 25
    assert ledger.get("t-b")["errors"] == 1
    assert ledger.get("t-c")["sheds"] == 1
    assert ledger.get("t-nope") is None
    top = ledger.top(2)
    assert top[0]["tenant"] == "t-a"  # most tokens
    totals = ledger.totals()
    assert totals["requests"] == 3
    assert totals["sheds"] == 1
    assert totals["tokens_in"] == 15


def test_ledger_eviction_conserves_sums_and_bounds_error():
    registry = Registry()
    ledger = TenantLedger(size=2, metrics=registry)
    ledger.observe("heavy", requests=5, tokens_in=50)
    ledger.observe("light", requests=1, tokens_in=2)
    ledger.observe("newcomer", requests=1)  # full table: evicts "light"
    assert ledger.get("light") is None
    assert ledger.get("heavy")["requests"] == 5  # heavy hitter untouched
    newcomer = ledger.get("newcomer")
    assert newcomer["requests"] == 1
    # classic space-saving bound: up to the evicted weight may belong
    # to ~other instead of this slot
    assert newcomer["err"] == 1
    stats = ledger.stats()
    assert stats["tracked"] == 2
    assert stats["evictions"] == 1
    assert stats["other"]["requests"] == 1
    assert stats["other"]["tokens_in"] == 2
    # sum conservation: totals never lose the evicted tenant's counts
    totals = ledger.totals()
    assert totals["requests"] == 7
    assert totals["tokens_in"] == 52
    assert registry.counter(
        "gofr_tpu_tenant_overflow_total"
    ).value() == 1.0
    assert registry.gauge(
        "gofr_tpu_tenants_tracked_entries"
    ).value() == 2.0


def test_ledger_heavy_hitters_exact_under_singleton_flood():
    """5000 distinct one-shot tenants churn a 64-slot table; the heavy
    hitters' counters must match a brute-force dict exactly (once their
    weight clears the churn floor they are never the eviction minimum)."""
    ledger = TenantLedger(size=64)
    brute: dict[str, int] = {}
    heavies = [f"heavy-{i}" for i in range(4)]
    for i in range(5000):
        if i % 10 == 0:
            tenant = heavies[(i // 10) % len(heavies)]
        else:
            tenant = f"one-shot-{i}"
        ledger.observe(tenant, requests=1, tokens_in=4, tokens_out=8)
        brute[tenant] = brute.get(tenant, 0) + 1
    stats = ledger.stats()
    assert stats["tracked"] == 64  # hard cardinality bound
    assert stats["evictions"] > 0
    top = {row["tenant"]: row for row in ledger.top(len(heavies))}
    assert set(top) == set(heavies)
    for tenant in heavies:
        assert top[tenant]["requests"] == brute[tenant]
        assert top[tenant]["tokens_out"] == brute[tenant] * 8
    # sum conservation across slots + ~other
    assert ledger.totals()["requests"] == 5000


def test_ledger_feeds_from_flight_recorder():
    ledger = TenantLedger(size=8)
    recorder = FlightRecorder(capacity=8, tenants=ledger)
    activate_tenant("key-abc")
    try:
        rec = recorder.start("echo", "/v1/completions", tokens_in=7)
        rec.tokens_out = 3
        recorder.finish(rec)
        bad = recorder.start("echo", "/v1/completions")
        recorder.finish(bad, status="deadline_exceeded")
    finally:
        activate_tenant(None)
    slot = ledger.get("key-abc")
    assert slot["requests"] == 2
    assert slot["tokens_in"] == 7
    assert slot["tokens_out"] == 3
    assert slot["deadline_misses"] == 1


# -- unit: SloEngine burn windows + latch -------------------------------------

def _engine(recorder, targets="availability=0.999", **kwargs):
    """Tiny distinct windows (1s/2s/3s/4s) so one test-local burst sits
    inside every window; alerts stay assertable without sleeps."""
    kwargs.setdefault("fast_s", 1.0)
    kwargs.setdefault("fast_long_s", 2.0)
    kwargs.setdefault("slow_s", 3.0)
    kwargs.setdefault("slow_long_s", 4.0)
    return SloEngine(recorder, targets=targets, **kwargs)


def test_engine_healthy_run_raises_zero_alerts():
    recorder = FlightRecorder(capacity=32)
    for _ in range(10):
        _finished(recorder)
    engine = _engine(recorder)
    report = engine.evaluate()
    row = report["objectives"][0]
    assert row["windows"]["1s"]["total"] == 10
    assert row["windows"]["1s"]["bad"] == 0
    assert row["windows"]["1s"]["burn"] == 0.0
    assert row["budget_remaining"] == 1.0
    assert row["alerting"] == {"fast": False, "slow": False}
    assert report["alerts_total"] == 0
    assert engine.ring.events(kind="slo") == []


def test_anomaly_ring_bounds_filters_and_stats():
    """The engine's evidence store: bounded, newest first, filtered by
    kind and cause, counted per (kind, cause)."""
    ring = _engine(FlightRecorder(capacity=4), ring=AnomalyRing(capacity=4)).ring
    for i in range(10):
        ring.record(kind="slo" if i % 2 else "other",
                    cause="slo_fast_burn", objective=f"o{i}")
    assert ring.total() == 10
    events = ring.events(limit=100)
    assert len(events) == 4  # bounded retention
    assert [e["objective"] for e in events] == ["o9", "o8", "o7", "o6"]
    assert [e["seq"] for e in events] == [10, 9, 8, 7]
    assert all(e["kind"] == "slo" for e in ring.events(kind="slo"))
    assert len(ring.events(limit=1)) == 1
    assert ring.events(cause="slo_slow_burn") == []
    stats = ring.stats()
    assert stats["total"] == 10 and stats["retained"] == 4
    assert stats["capacity"] == 4 and ring.capacity == 4
    assert stats["by"]["slo/slo_fast_burn"] == 5
    assert stats["last_ts"] == events[0]["ts"]


def test_engine_burst_latches_one_alert_per_excursion():
    registry = Registry()
    recorder = FlightRecorder(capacity=64)
    for _ in range(5):
        _finished(recorder)
    bad = [_finished(recorder, status="error") for _ in range(5)]
    engine = _engine(recorder, metrics=registry)
    report = engine.evaluate()
    row = report["objectives"][0]
    # 5 bad of 10 against a 0.001 budget: burning 500x on every window
    assert row["windows"]["1s"]["bad_fraction"] == pytest.approx(0.5)
    assert row["windows"]["1s"]["burn"] == pytest.approx(500.0)
    assert row["alerting"] == {"fast": True, "slow": True}
    assert row["budget_remaining"] == pytest.approx(1.0 - 500.0)
    events = engine.ring.events(kind="slo")
    assert {e["cause"] for e in events} == {"slo_fast_burn", "slo_slow_burn"}
    assert all(e["objective"] == "availability" for e in events)
    assert report["alerts_total"] == 2
    counter = registry.counter(
        "gofr_tpu_slo_burn_alerts_total", labels=("objective", "window")
    )
    assert counter.value(objective="availability", window="fast") == 1.0
    # still burning: the latch holds, no duplicate page
    engine.evaluate()
    assert engine.evaluate()["alerts_total"] == 2
    assert len(engine.ring.events(kind="slo")) == 2
    # the burst ages out of every window: burn clears, latch re-arms
    for rec in bad:
        rec.t_done -= 60.0
    cleared = engine.evaluate()["objectives"][0]
    assert cleared["alerting"] == {"fast": False, "slow": False}
    # a second excursion pages again
    for _ in range(5):
        _finished(recorder, status="error")
    assert engine.evaluate()["alerts_total"] == 4
    assert counter.value(objective="availability", window="fast") == 2.0
    # the gauges tracked the whole ride
    burn_gauge = registry.gauge(
        "gofr_tpu_slo_burn_rate", labels=("objective", "window")
    )
    assert burn_gauge.value(objective="availability", window="1s") > 100.0


def test_engine_no_traffic_spends_no_budget():
    engine = _engine(FlightRecorder(capacity=8))
    row = engine.evaluate()["objectives"][0]
    assert row["windows"]["4s"]["total"] == 0
    assert row["budget_remaining"] == 1.0
    assert row["alerting"] == {"fast": False, "slow": False}


def test_engine_shed_rate_from_timebase_counters():
    from gofr_tpu.timebase import TimebaseSampler

    registry = Registry()
    shed = registry.counter(
        "gofr_tpu_brownout_shed_total", labels=("priority",)
    )
    sampler = TimebaseSampler(
        registry, interval_s=0.05, window_s=30, start=False
    )
    sampler.sample_now()
    shed.inc(30, priority="0")
    sampler.sample_now()
    recorder = FlightRecorder(capacity=256)
    engine = _engine(
        recorder, targets="shed_rate=0.05", timebase=sampler,
    )
    row = engine.evaluate()["objectives"][0]
    # 30 sheds, 0 completions: shed fraction 1.0 -> burning 20x budget
    stats = row["windows"]["1s"]
    assert stats["bad"] == 30
    assert stats["total"] == 30
    assert stats["bad_fraction"] == pytest.approx(1.0)
    assert stats["burn"] == pytest.approx(20.0)
    assert row["alerting"] == {"fast": True, "slow": True}
    # completions dilute the rate: 30 sheds / (30 + 90) demand = 25%
    for _ in range(90):
        _finished(recorder)
    diluted = engine.evaluate()["objectives"][0]["windows"]["1s"]
    assert diluted["bad_fraction"] == pytest.approx(0.25)


def test_engine_headline_compacts_the_report():
    recorder = FlightRecorder(capacity=32)
    for _ in range(5):
        _finished(recorder)
    for _ in range(5):
        _finished(recorder, status="error")
    # shed_rate with no timebase wired never burns — the quiet second
    # objective the headline must NOT list as alerting
    engine = _engine(
        recorder, targets="availability=0.999;shed_rate=0.5",
    )
    engine.evaluate()
    headline = engine.headline()
    assert headline["objectives"] == 2
    assert headline["worst_objective"] == "availability"
    assert headline["worst_burn"] == pytest.approx(500.0)
    assert headline["alerting"] == ["availability"]
    assert headline["budget_remaining_min"] == pytest.approx(-499.0)
    assert headline["alerts_total"] == 2


def test_engine_rejects_bad_window_config():
    recorder = FlightRecorder(capacity=4)
    with pytest.raises(ValueError, match="windows"):
        SloEngine(recorder, fast_s=10, fast_long_s=5)
    with pytest.raises(ValueError, match="threshold"):
        SloEngine(recorder, fast_rate=0)
    with pytest.raises(ValueError, match="INTERVAL"):
        SloEngine(recorder, interval_s=0)


def test_shed_verdict_echoes_hashed_tenant():
    """A 429's error body quotes the hashed tenant id the admission
    gate derived — the key a shed client uses to find itself on
    /admin/tenants and /admin/requests?tenant=."""
    from gofr_tpu.http.responder import respond

    class Shed(Exception):
        status_code = 429
        retry_after_s = 1.0
        tenant = "key-0123456789abcdef"

    response = respond(None, Shed("brownout shed"))
    assert response.status == 429
    payload = json.loads(response.body)["error"]
    assert payload["tenant"] == "key-0123456789abcdef"
    assert "brownout" in payload["message"]
    # and an untenanted error body stays exactly as before
    class Plain(Exception):
        status_code = 400

    bare = json.loads(respond(None, Plain("nope")).body)["error"]
    assert "tenant" not in bare


# -- e2e: the echo app --------------------------------------------------------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def slo_app(tmp_path_factory):
    """Echo-model app with the OpenAI routes, a small tenant table (64
    slots — the 5k-tenant flood must churn it), and a lazy SLO thread
    (evaluation happens on every /admin/slo/budget read)."""
    import os

    import gofr_tpu
    from gofr_tpu.openai_compat import register_openai_routes

    port = _free_port()
    env = {"HTTP_PORT": str(port), "LOG_LEVEL": "FATAL",
           "MODEL_NAME": "echo", "TOKENIZER": "byte",
           "BATCH_MAX_SIZE": "8", "BATCH_TIMEOUT_MS": "1",
           "ECHO_STEP_MS": "1", "FLIGHT_SLOW_MS": "60000",
           "FLIGHT_RECORDER_SIZE": "8192",
           "TENANT_LEDGER_SIZE": "64",
           "SLO_EVAL_INTERVAL_S": "3600",
           "GRPC_PORT": str(_free_port())}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("slo_e2e"))
    try:
        app = gofr_tpu.new()
    finally:
        os.chdir(cwd)
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    register_openai_routes(app)
    app.start()
    yield app, f"http://127.0.0.1:{port}"
    app.shutdown()


def _post(base, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        base + "/v1/completions", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as resp:
        return json.loads(resp.read())["data"]


def _metrics(base):
    with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
        return resp.read().decode()


def _hashed(authorization):
    digest = hashlib.sha256(authorization.encode("utf-8")).hexdigest()
    return "key-" + digest[:16]


def test_e2e_healthy_run_zero_alerts(slo_app):
    app, base = slo_app
    for _ in range(6):
        status, _ = _post(
            base, {"prompt": [1, 2, 3], "max_tokens": 2, "temperature": 0},
            headers={"Authorization": "Bearer healthy-key"},
        )
        assert status == 200
    budget = _get(base, "/admin/slo/budget")
    assert budget["targets"] == DEFAULT_TARGETS
    assert {r["objective"] for r in budget["objectives"]} == {
        "availability", "shed_rate", "tier9.availability",
    }
    for row in budget["objectives"]:
        assert row["alerting"] == {"fast": False, "slow": False}
        assert row["budget_remaining"] == 1.0
    assert budget["alerts_total"] == 0
    assert budget["recent_alerts"] == []
    # the default window labels are the gauge's stable label values
    avail = next(r for r in budget["objectives"]
                 if r["objective"] == "availability")
    assert set(avail["windows"]) == {"5m", "1h", "6h", "3d"}
    assert avail["windows"]["5m"]["total"] >= 6
    # headline surfaces: /admin/overview + the fleet-facing snapshot
    over = _get(base, "/admin/overview")
    assert over["slo_budget"]["alerting"] == []
    assert over["slo_budget"]["objectives"] == 3
    assert over["tenants"]["tracked"] >= 1
    engine = _get(base, "/admin/engine")
    assert engine["slo"]["alerts_total"] == 0
    assert engine["tenants"]["tracked"] >= 1


def test_e2e_healthy_loop_leaves_anomalies_empty(slo_app):
    """A healthy echo loop with SLO=on serves an EMPTY /admin/anomalies
    from the container's SLO engine, its ring sized by ANOMALY_RING_SIZE."""
    app, base = slo_app
    for _ in range(4):
        status, _ = _post(
            base, {"prompt": [4, 5, 6], "max_tokens": 3, "temperature": 0})
        assert status == 200
    _get(base, "/admin/slo/budget")  # a fresh evaluation over the loop
    out = _get(base, "/admin/anomalies")
    assert out["anomalies"] == [] and out["count"] == 0
    assert out["stats"]["total"] == 0 and out["stats"]["capacity"] == 256
    assert app.container.slo.ring.total() == 0
    assert set(ANOMALY_CAUSES) == {"slo_fast_burn", "slo_slow_burn"}


def test_anomalies_endpoint_validates_params(slo_app):
    _, base = slo_app
    for path in ("/admin/anomalies?limit=0",
                 "/admin/anomalies?limit=x",
                 "/admin/anomalies?cause=nope",
                 "/admin/anomalies?cause=slow_dispatch"):
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(base, path)
        assert err.value.code == 400, path
    assert _get(base, "/admin/anomalies?cause=slo_slow_burn&limit=1")["count"] == 0


def test_e2e_tenant_metering_and_request_filter(slo_app):
    app, base = slo_app
    auth = "Bearer metered-key"
    tenant = _hashed(auth)
    for _ in range(3):
        _post(base, {"prompt": [1, 2, 3, 4], "max_tokens": 2,
                     "temperature": 0},
              headers={"Authorization": auth})
    page = _get(base, "/admin/tenants")
    assert page["size"] == 64
    mine = [r for r in page["tenants"] if r["tenant"] == tenant]
    assert mine and mine[0]["requests"] >= 3
    assert mine[0]["tokens_in"] >= 12
    assert mine[0]["tokens_out"] >= 6
    # single-tenant lookup + the hashed id never echoes the raw key
    one = _get(base, f"/admin/tenants?tenant={tenant}")["tenant"]
    assert one["requests"] >= 3
    assert "metered-key" not in json.dumps(page)
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(base, "/admin/tenants?tenant=key-ffffffffffffffff")
    assert err.value.code == 404
    # /admin/requests?tenant= ranks only this tenant's flights
    records = _get(base, f"/admin/requests?tenant={tenant}")["requests"]
    assert len(records) >= 3
    assert all(r["tenant"] == tenant for r in records)
    assert _get(
        base, "/admin/requests?tenant=key-ffffffffffffffff"
    )["requests"] == []


def test_e2e_fault_burst_pages_on_every_surface(slo_app):
    """Acceptance: one deadline-miss burst -> slo_fast_burn visible on
    /admin/slo/budget, /admin/anomalies, /metrics, and in a postmortem
    bundle, with the misses metered to the offending tenant."""
    app, base = slo_app
    auth = "Bearer bursty-key"
    tenant = _hashed(auth)
    for _ in range(10):
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base, {"prompt": [1, 2], "max_tokens": 2,
                         "temperature": 0},
                  headers={"Authorization": auth,
                           "X-Request-Deadline-Ms": "1"})
        assert err.value.code == 504
    budget = _get(base, "/admin/slo/budget")
    avail = next(r for r in budget["objectives"]
                 if r["objective"] == "availability")
    assert avail["windows"]["5m"]["bad"] >= 10
    assert avail["alerting"]["fast"] is True
    assert budget["alerts_total"] >= 2  # fast page + slow ticket
    causes = {e["cause"] for e in budget["recent_alerts"]}
    assert "slo_fast_burn" in causes
    anomalies = _get(base, "/admin/anomalies")
    assert "slo_fast_burn" in {a["cause"] for a in anomalies["anomalies"]}
    # exposition: the latched excursion counter
    text = _metrics(base)
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("gofr_tpu_slo_burn_alerts_total{")
        and 'objective="availability"' in ln and 'window="fast"' in ln
    )
    assert float(line.rsplit(" ", 1)[1]) >= 1
    # the tenant wore its deadline misses
    slot = _get(base, f"/admin/tenants?tenant={tenant}")["tenant"]
    assert slot["deadline_misses"] >= 10
    # the black-box bundle carries the whole ledger
    req = urllib.request.Request(
        base + "/admin/postmortem",
        data=json.dumps({"detail": "slo burn drill"}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        bundle_path = json.loads(resp.read())["data"]["path"]
    bundle = json.load(open(bundle_path))
    assert bundle["slo_budget"]["alerts_total"] >= 2
    assert any(r["tenant"] == tenant
               for r in bundle["tenants"]["tenants"])
    assert "slo_fast_burn" in {a["cause"] for a in bundle["anomalies"]}
    # the overview headline flips too
    over = _get(base, "/admin/overview")
    assert "availability" in over["slo_budget"]["alerting"]


def test_e2e_5k_tenants_bounded_cardinality(slo_app):
    """5000 distinct API keys through the serving surface: /metrics
    must stay bounded (no per-tenant series, no dropped-series
    pressure) while the ledger keeps the heavy hitters exact."""
    app, base = slo_app
    heavies = [f"Bearer vip-{i}" for i in range(3)]
    payload = json.dumps(
        {"prompt": [1], "max_tokens": 1, "temperature": 0}
    ).encode()

    def fire(auth):
        req = urllib.request.Request(
            base + "/v1/completions", data=payload,
            headers={"Content-Type": "application/json",
                     "Authorization": auth},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        return auth

    brute: dict[str, int] = {}
    plan = []
    for i in range(5000):
        auth = heavies[i % 3] if i % 10 == 0 else f"Bearer scan-{i}"
        plan.append(auth)
        key = _hashed(auth)
        brute[key] = brute.get(key, 0) + 1
    with concurrent.futures.ThreadPoolExecutor(max_workers=16) as pool:
        for _ in pool.map(fire, plan):
            pass
    ledger = app.container.tenants
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if ledger.totals()["requests"] >= 5000:
            break
        time.sleep(0.05)
    stats = ledger.stats()
    assert stats["tracked"] == 64  # TENANT_LEDGER_SIZE holds
    assert stats["evictions"] > 0
    top = {r["tenant"]: r for r in ledger.top(3)}
    for auth in heavies:
        key = _hashed(auth)
        assert key in top, (key, sorted(top))
        assert top[key]["requests"] == brute[key]  # exact, not approximate
    # bounded exposition: no per-tenant series ever minted, and the
    # cardinality guard never had to drop one
    text = _metrics(base)
    assert "key-" not in text
    dropped = [
        float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
        if ln.startswith("gofr_tpu_metrics_dropped_series_total")
        and not ln.startswith("#")
    ]
    assert sum(dropped) == 0
    assert _get(base, "/admin/tenants")["tracked"] == 64
