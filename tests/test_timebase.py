"""Telemetry timebase + postmortem black box (gofr_tpu/timebase.py,
gofr_tpu/postmortem.py, metrics exemplars/cardinality): unit semantics
plus the end-to-end acceptance spine over the in-process server on the
no-JAX ``echo`` model — an injected device stall must wedge the engine
AND leave a postmortem bundle on disk containing the stalling
dispatch_id, the flight records that rode it, timebase snapshots, and
every thread's stack; ``/admin/timeseries`` must serve a counter rate
series spanning the incident; the OpenMetrics exposition must carry an
exemplar resolving to a ``/admin/requests`` row."""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu.metrics import Histogram, Registry
from gofr_tpu.timebase import TimebaseSampler


# -- unit: timebase ring ------------------------------------------------------

def _sampler(registry):
    return TimebaseSampler(
        registry, interval_s=0.5, window_s=60.0, start=False
    )


def test_sampler_series_and_rate_derivation():
    registry = Registry()
    counter = registry.counter("gofr_t_total", "t", labels=("k",))
    sampler = _sampler(registry)
    counter.inc(10, k="a")
    sampler.sample_now()
    counter.inc(30, k="a")
    counter.inc(5, k="b")
    sampler.sample_now()
    out = sampler.series("gofr_t_total")
    assert out["kind"] == "counter"
    by_labels = {tuple(s["labels"].items()): s for s in out["series"]}
    a = by_labels[(("k", "a"),)]
    assert [p[1] for p in a["points"]] == [10.0, 40.0]
    assert len(a["rate"]) == 1
    snaps = sampler.snapshots()
    dt = snaps[1]["mono"] - snaps[0]["mono"]  # rate dt is monotonic
    assert a["rate"][0][1] == pytest.approx(30.0 / dt)
    # label-set b only exists in the second snapshot: one point, no rate
    b = by_labels[(("k", "b"),)]
    assert len(b["points"]) == 1 and b["rate"] == []
    # labels filter is a subset match
    only_a = sampler.series("gofr_t_total", labels={"k": "a"})
    assert len(only_a["series"]) == 1
    assert sampler.series("gofr_unknown_total") is None


def test_sampler_counter_reset_clamps_to_zero():
    registry = Registry()
    counter = registry.counter("gofr_r_total", "r")
    sampler = _sampler(registry)
    counter.inc(100)
    sampler.sample_now()
    counter._values[()] = 3.0  # simulate a process restart's fresh counter
    sampler.sample_now()
    out = sampler.series("gofr_r_total")
    assert out["series"][0]["rate"][0][1] == 0.0  # never a negative spike


def test_sampler_ring_is_bounded_and_windowed():
    registry = Registry()
    sampler = TimebaseSampler(
        registry, interval_s=1.0, window_s=3.0, start=False
    )
    for _ in range(10):
        sampler.sample_now()
    stats = sampler.stats()
    assert stats["snapshots"] <= 4  # window/interval + 1
    assert len(sampler.snapshots(last=2)) == 2
    assert sampler.snapshots(window=0.0) in ([], sampler.snapshots(window=0.0))


def test_sampler_hist_quantile_trend_is_interval_local():
    registry = Registry()
    hist = registry.histogram(
        "gofr_q_seconds", "q", buckets=(0.1, 1.0, 10.0)
    )
    sampler = _sampler(registry)
    sampler.sample_now()
    for _ in range(10):
        hist.observe(0.05)  # interval 1: everything fast
    sampler.sample_now()
    for _ in range(10):
        hist.observe(5.0)  # interval 2: everything slow
    sampler.sample_now()
    trend = sampler.hist_quantile_trend("gofr_q_seconds", 0.95)
    assert [v for _, v in trend] == [0.1, 10.0]
    # the cumulative histogram would have reported a blended p95 —
    # interval-locality is the whole point of the trend


def test_sampler_quantile_trend_survives_bucket_overflow():
    """An incident where every observation blows past the top bucket —
    exactly when the trend matters — must still produce points (clamped
    to the top bound), not go blank: overflow lives only in the series
    count, never in the finite bucket counts."""
    registry = Registry()
    hist = registry.histogram("gofr_o_seconds", "o", buckets=(0.1, 1.0))
    sampler = _sampler(registry)
    sampler.sample_now()
    for _ in range(10):
        hist.observe(50.0)  # all +Inf overflow
    sampler.sample_now()
    trend = sampler.hist_quantile_trend("gofr_o_seconds", 0.95)
    assert [v for _, v in trend] == [1.0]


def test_rate_total_sums_across_label_sets():
    registry = Registry()
    counter = registry.counter("gofr_s_total", "s", labels=("k",))
    sampler = _sampler(registry)
    counter.inc(1, k="a")
    sampler.sample_now()
    counter.inc(1, k="a")
    counter.inc(2, k="b")
    sampler.sample_now()
    rate = sampler.rate_total("gofr_s_total")
    snaps = sampler.snapshots()
    dt = snaps[1]["mono"] - snaps[0]["mono"]  # rate dt is monotonic
    assert rate[0][1] == pytest.approx(3.0 / dt)


def test_rate_total_labels_filter():
    registry = Registry()
    counter = registry.counter("gofr_x_total", "x", labels=("cause",))
    sampler = _sampler(registry)
    counter.inc(10, cause="a")
    counter.inc(100, cause="b")
    sampler.sample_now()
    counter.inc(10, cause="a")
    sampler.sample_now()
    all_rates = sampler.rate_total("gofr_x_total")
    only_a = sampler.rate_total("gofr_x_total", labels={"cause": "a"})
    only_b = sampler.rate_total("gofr_x_total", labels={"cause": "b"})
    assert all_rates[0][1] == only_a[0][1] > 0.0  # only `a` moved
    assert only_b[0][1] == 0.0


def test_sampler_validates_intervals():
    with pytest.raises(ValueError):
        TimebaseSampler(Registry(), interval_s=0, start=False)
    with pytest.raises(ValueError):
        TimebaseSampler(
            Registry(), interval_s=10.0, window_s=5.0, start=False
        )


# -- unit: metrics cardinality guard -----------------------------------------

def test_cardinality_guard_drops_overflow_series():
    registry = Registry(max_series=2)
    counter = registry.counter("gofr_c_total", "c", labels=("k",))
    counter.inc(k="a")
    counter.inc(k="b")
    counter.inc(k="c")  # third label-set: dropped
    counter.inc(5, k="a")  # existing series still updates
    assert counter.value(k="a") == 6
    assert counter.value(k="c") == 0.0
    dropped = registry.counter(
        "gofr_tpu_metrics_dropped_series_total", labels=("metric",)
    )
    assert dropped.value(metric="gofr_c_total") == 1
    gauge = registry.gauge("gofr_g_depth", "g", labels=("k",))
    gauge.set(1, k="a")
    gauge.set(1, k="b")
    gauge.set(1, k="c")
    assert dropped.value(metric="gofr_g_depth") == 1
    hist = registry.histogram("gofr_h_seconds", "h", labels=("k",))
    hist.observe(0.1, k="a")
    hist.observe(0.1, k="b")
    hist.observe(0.1, k="c")
    assert dropped.value(metric="gofr_h_seconds") == 1
    assert "gofr_tpu_metrics_dropped_series_total" in registry.expose()


# -- unit: exemplars + OpenMetrics exposition ---------------------------------

def test_histogram_exemplar_explicit_and_provider():
    provided = {"trace_id": "feedface"}
    hist = Histogram(
        "gofr_e_seconds", "e", buckets=(0.1, 1.0),
        exemplar_provider=lambda: provided,
    )
    hist.observe(0.05)  # provider exemplar
    hist.observe(0.5, exemplar={"trace_id": "cafebabe"})  # explicit wins
    hist.observe(5.0)  # +Inf overflow bucket keeps exemplars too
    text = "\n".join(hist.expose(openmetrics=True))
    assert '# {trace_id="feedface"} 0.05' in text
    assert '# {trace_id="cafebabe"} 0.5' in text
    inf_line = next(
        line for line in text.splitlines() if 'le="+Inf"' in line
    )
    assert 'trace_id="feedface"' in inf_line
    # classic Prometheus text never carries exemplars
    assert "# {" not in "\n".join(hist.expose())


def test_exemplar_label_budget_is_enforced():
    huge = {"trace_id": "a" * 200}
    hist = Histogram("gofr_b_seconds", "b", buckets=(1.0,))
    hist.observe(0.5, exemplar=huge)
    assert "# {" not in "\n".join(hist.expose(openmetrics=True))
    both = {"trace_id": "b" * 60, "dispatch_id": "c" * 100}
    hist.observe(0.5, exemplar=both)
    text = "\n".join(hist.expose(openmetrics=True))
    assert "b" * 60 in text  # first label fits
    assert "c" * 100 not in text  # second would blow the 128-rune budget


def test_openmetrics_counter_family_and_eof():
    registry = Registry()
    registry.counter("gofr_x_total", "xs", labels=("k",)).inc(k="v")
    om = registry.expose(openmetrics=True)
    assert "# TYPE gofr_x counter" in om
    assert "# HELP gofr_x xs" in om
    assert 'gofr_x_total{k="v"} 1' in om
    assert om.rstrip().endswith("# EOF")
    prom = registry.expose()
    assert "# TYPE gofr_x_total counter" in prom
    assert "# EOF" not in prom


def test_openmetrics_le_is_canonical_float():
    registry = Registry()
    registry.histogram("gofr_f_seconds", "f", buckets=(1.0, 2.5)).observe(0.5)
    om = registry.expose(openmetrics=True)
    assert 'le="1.0"' in om
    assert 'le="2.5"' in om
    prom = registry.expose()
    assert 'le="1"' in prom  # classic text keeps the terse form


def test_histogram_percentile_interpolation():
    hist = Histogram("gofr_p_seconds", "p", buckets=(1.0, 2.0, 4.0))
    for v in (0.5,) * 5 + (1.5,) * 5:
        hist.observe(v)
    assert hist.percentile(0.5) == 1.0  # upper-bound default
    # interpolated: rank 5 of 10 sits at the very top of bucket (0, 1]
    assert hist.percentile(0.5, interpolate=True) == pytest.approx(1.0)
    assert hist.percentile(0.75, interpolate=True) == pytest.approx(1.5)
    assert hist.percentile(0.25, interpolate=True) == pytest.approx(0.5)


# -- unit: postmortem store ---------------------------------------------------

class _StubContainer:
    def __init__(self, registry):
        from gofr_tpu.telemetry import FlightRecorder

        self.metrics = registry
        self.telemetry = FlightRecorder(capacity=8, keep=4)
        self.timebase = TimebaseSampler(
            registry, interval_s=0.5, window_s=60.0, start=False
        )
        self.tpu = None


def _store(tmp_path, **kw):
    from gofr_tpu.postmortem import PostmortemStore

    registry = Registry()
    container = _StubContainer(registry)
    kw.setdefault("directory", str(tmp_path / "pm"))
    return PostmortemStore(container, **kw), container


def test_postmortem_bundle_contents_and_atomic_write(tmp_path):
    store, container = _store(tmp_path)
    container.timebase.sample_now()
    container.timebase.sample_now()
    record = container.telemetry.start("m", "/v1/x", trace_id="t1", activate=False)
    container.telemetry.finish(record)
    in_flight = container.telemetry.start(  # noqa: F841 - must stay referenced
        "m", "/v1/y", trace_id="t2", activate=False
    )
    path = store.write(reason="manual", force=True)
    assert path and os.path.exists(path)
    assert not [n for n in os.listdir(store.directory) if n.endswith(".tmp")]
    bundle = json.load(open(path))
    assert bundle["schema"] == "gofr-postmortem/1"
    assert bundle["reason"] == "manual"
    assert bundle["versions"]["gofr_tpu"]
    assert len(bundle["timebase"]) == 2
    assert [r["trace_id"] for r in bundle["requests"]] == ["t1"]
    assert [r["trace_id"] for r in bundle["requests_in_flight"]] == ["t2"]
    assert any(t["stack"] for t in bundle["threads"])


def test_postmortem_rate_limit_and_retention(tmp_path):
    store, _ = _store(tmp_path, keep=2, min_interval_s=3600.0)
    # a forced (operator) write never consumes the automatic budget: a
    # drill at t=0 must not suppress the wedge bundle at t=10
    assert store.write(reason="manual", force=True) is not None
    time.sleep(0.002)  # distinct filename timestamps (ms resolution)
    first = store.write(reason="wedged")
    assert first is not None
    assert store.write(reason="wedged") is None  # rate-limited
    for _ in range(3):
        time.sleep(0.002)
        assert store.write(reason="manual", force=True) is not None
    bundles = store.list()
    assert len(bundles) == 2  # retention pruned the oldest
    assert all(b["bytes"] > 0 for b in bundles)


def test_postmortem_failed_write_refunds_the_rate_limit(tmp_path):
    store, container = _store(tmp_path, min_interval_s=3600.0)
    container.timebase = object()  # snapshots() missing -> bundle raises
    assert store.write(reason="wedged") is None
    container.timebase = TimebaseSampler(
        container.metrics, interval_s=0.5, window_s=60.0, start=False
    )
    # the failure did not burn the hour-long budget
    assert store.write(reason="wedged") is not None


def test_postmortem_config_redacts_secrets(tmp_path, monkeypatch):
    from gofr_tpu.postmortem import _config_fingerprint

    monkeypatch.setenv("ADMIN_TOKEN", "hunter2")
    monkeypatch.setenv("MODEL_NAME", "echo")
    monkeypatch.setenv("GEN_STOP_TOKENS", "1,2")  # NOT a secret
    fp = _config_fingerprint()
    assert fp["keys"]["ADMIN_TOKEN"] == "<redacted>"
    assert fp["keys"]["MODEL_NAME"] == "echo"
    assert fp["keys"]["GEN_STOP_TOKENS"] == "1,2"
    assert "hunter2" not in json.dumps(fp)
    assert len(fp["fingerprint"]) == 16


def test_postmortem_wedge_listener_writes_async(tmp_path):
    from gofr_tpu.tpu.introspect import EngineState

    store, _ = _store(tmp_path)
    engine = EngineState()
    store.watch_engine(engine)
    engine.transition("serving")
    assert store.list() == []  # only wedged/failed trigger
    engine.transition("wedged", "dispatch 7 stalled")
    deadline = time.time() + 5.0
    while not store.list() and time.time() < deadline:
        time.sleep(0.01)
    bundles = store.list()
    assert len(bundles) == 1
    bundle = json.load(
        open(os.path.join(store.directory, bundles[0]["file"]))
    )
    assert bundle["reason"] == "wedged"
    assert bundle["detail"] == "dispatch 7 stalled"


# -- end-to-end: the acceptance spine over the echo app -----------------------

@pytest.fixture(scope="module")
def echo_app(tmp_path_factory):
    """Echo-model app with an armed watchdog, a fast timebase, and a
    postmortem dir — the full timebase/postmortem spine, no XLA."""
    import gofr_tpu
    from gofr_tpu.openai_compat import register_openai_routes

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    pm_dir = str(tmp_path_factory.mktemp("postmortems"))
    env = {"HTTP_PORT": str(port), "LOG_LEVEL": "FATAL",
           "MODEL_NAME": "echo", "TOKENIZER": "byte",
           "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "1",
           "FLIGHT_SLOW_MS": "60000",
           "TIMEBASE_INTERVAL_S": "0.05", "TIMEBASE_WINDOW_S": "60",
           "POSTMORTEM_DIR": pm_dir,
           # 0.7s injected stall: degraded at 0.15s, wedged at 0.45s
           "WATCHDOG_DISPATCH_TIMEOUT_S": "0.15"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("timebase_e2e"))
    try:
        app = gofr_tpu.new()
    finally:
        os.chdir(cwd)
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    register_openai_routes(app)
    app.start()
    yield app, f"http://127.0.0.1:{port}", pm_dir
    app.shutdown()


def _post(base, payload, path="/v1/chat/completions"):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read()), dict(resp.headers.items())


def _get(base, path, headers=None):
    req = urllib.request.Request(base + path, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())["data"]


def _wait_snapshots(app, n=2, timeout=10.0):
    """Deterministic deflake: the sampler thread's first ticks can land
    arbitrarily late on a loaded CI host, so a fixed sleep of a few
    intervals flakes — poll until the ring actually holds ``n``
    snapshots (generous ceiling, returns the moment it's true)."""
    timebase = app.container.timebase
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if timebase.stats()["snapshots"] >= n:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"timebase never reached {n} snapshots within {timeout}s "
        f"(stats: {timebase.stats()})"
    )


def _wait_series(base, path, timeout=10.0):
    """Poll a timeseries query until it serves a series: the request that
    makes the series is counted behind its response, and only a snapshot
    taken after that holds it — however many snapshots came before."""
    deadline = time.monotonic() + timeout
    while True:
        out = _get(base, path)
        if out["series"] or time.monotonic() >= deadline:
            return out
        time.sleep(0.02)


def test_timeseries_endpoint_serves_series_and_rates(echo_app):
    app, base, _ = echo_app
    _post(base, {"messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 2, "temperature": 0})
    _wait_snapshots(app, n=2)
    chat = ("/admin/timeseries?metric=gofr_http_requests_total"
            "&labels=path:/v1/chat/completions")
    _wait_series(base, chat)  # a snapshot that has counted the request above
    out = _get(base, "/admin/timeseries?metric=gofr_http_requests_total")
    assert out["kind"] == "counter"
    assert out["series"], "no series for a counter that was incremented"
    assert all(len(s["points"]) >= 1 for s in out["series"])
    assert out["timebase"]["snapshots"] >= 2
    # labels filter narrows to the chat route
    filtered = _get(base, chat)
    assert filtered["series"]
    assert all(
        s["labels"]["path"] == "/v1/chat/completions"
        for s in filtered["series"]
    )


def test_timeseries_endpoint_validates_params(echo_app):
    app, base, _ = echo_app
    for path in ("/admin/timeseries",
                 "/admin/timeseries?metric=gofr_nope_total",
                 "/admin/timeseries?metric=gofr_http_requests_total&window=-1",
                 "/admin/timeseries?metric=gofr_http_requests_total&labels=xx"):
        try:
            _get(base, path)
            raise AssertionError(f"expected 400 for {path}")
        except urllib.error.HTTPError as e:
            assert e.code == 400, path


def test_overview_is_one_page_ops_rollup(echo_app):
    app, base, _ = echo_app
    _post(base, {"messages": [{"role": "user", "content": "roll"}],
                 "max_tokens": 2, "temperature": 0})
    _wait_snapshots(app, n=2)
    out = _get(base, "/admin/overview")
    assert out["engine"]["state"] == "serving"
    assert out["model"] == "echo"
    assert out["timebase"]["snapshots"] >= 2
    assert "now" in out["req_per_sec"] and "trend" in out["req_per_sec"]
    assert "slo" in out and "models" in out["slo"]
    assert out["dispatches"]["total"] >= 1
    assert "watchdog" in out and "postmortems" in out


def test_stall_leaves_black_box_bundle_and_history(echo_app):
    """The acceptance spine: injected stall -> wedged -> a postmortem
    bundle on disk with the stalling dispatch_id, the in-flight flight
    record that rode it, >=2 timebase snapshots, and thread stacks;
    /admin/timeseries then serves a rate series spanning the incident;
    the OpenMetrics exposition carries an exemplar resolving to an
    /admin/requests row."""
    app, base, pm_dir = echo_app
    # warm traffic before the incident anchors the rate series: wait
    # for two MORE snapshots so the warm request's counter bump is
    # bracketed in the ring (same deflake discipline as _wait_snapshots)
    before = app.container.timebase.stats()["snapshots"]
    _post(base, {"messages": [{"role": "user", "content": "warm"}],
                 "max_tokens": 2, "temperature": 0})
    _wait_snapshots(app, n=before + 2)
    tpu = app.container.tpu
    stall_start = time.time()
    # supervisor off for the duration: this test pins the postmortem
    # layer's own evidence capture against a LIVE wedge (the recovery
    # rebuild path — including its bundle-before-quarantine order —
    # is covered by tests/test_recovery.py)
    tpu.recovery.enabled = False
    # long enough that the bundle is captured while the dispatch still stalls
    # when every xdist worker is busy (0.7 s lost that race on a loaded
    # machine: the watchdog fires at 0.15 s, the capture came late)
    tpu.runner.stall_hook = lambda: time.sleep(2.0)
    try:
        worker = threading.Thread(
            target=lambda: _post(
                base,
                {"messages": [{"role": "user", "content": "stall"}],
                 "max_tokens": 1, "temperature": 0},
            ),
        )
        worker.start()
        bundle_path = None
        deadline = time.time() + 10.0
        while time.time() < deadline and bundle_path is None:
            names = [n for n in os.listdir(pm_dir)
                     if n.startswith("postmortem-") and n.endswith(".json")]
            if names:
                bundle_path = os.path.join(pm_dir, sorted(names)[0])
                break
            time.sleep(0.02)
        worker.join()
    finally:
        tpu.runner.stall_hook = None
        tpu.recovery.enabled = True
    stall_end = time.time()
    assert bundle_path, "wedge never produced a postmortem bundle"
    bundle = json.load(open(bundle_path))
    assert bundle["schema"] == "gofr-postmortem/1"
    assert bundle["reason"] == "wedged"
    # the stalling dispatch: flagged by the watchdog AND visible as
    # running on the timeline snapshot inside the bundle
    stalled = [w for w in bundle["engine"]["watchdog"]["watching"]
               if w["stalled"]]
    assert stalled, "bundle carries no stalled watchdog entry"
    stalled_ids = {w["dispatch_id"] for w in stalled}
    running = {d["dispatch_id"] for d in bundle["dispatches"]
               if d["status"] == "running"}
    assert stalled_ids & running
    # the flight record riding the wedge is in the bundle — with the
    # stalling dispatch_id already linked
    in_flight = bundle["requests_in_flight"]
    assert in_flight, "the wedged request's flight record is missing"
    assert any(
        set(r["dispatch_ids"]) & stalled_ids for r in in_flight
    ), (in_flight, stalled_ids)
    assert len(bundle["timebase"]) >= 2
    stacks = {t["name"]: t["stack"] for t in bundle["threads"]}
    assert len(stacks) >= 2
    assert any("stall_hook" in s for s in stacks.values()), (
        "no thread stack shows the stalled call"
    )
    # recovery, then: the timeseries ring spans the incident
    deadline = time.time() + 3.0
    while tpu.engine.state != "serving" and time.time() < deadline:
        time.sleep(0.02)
    assert tpu.engine.state == "serving"
    _wait_snapshots(
        app, n=app.container.timebase.stats()["snapshots"] + 2
    )
    out = _get(base, "/admin/timeseries?metric=gofr_http_requests_total")
    rates = [p for s in out["series"] for p in s["rate"]]
    assert rates, "no rate points derived"
    assert min(ts for ts, _ in rates) < stall_end
    assert max(ts for ts, _ in rates) > stall_start
    # OpenMetrics exemplar -> flight record join
    _, headers = _post(base, {
        "messages": [{"role": "user", "content": "exemplar"}],
        "max_tokens": 2, "temperature": 0,
    })
    req = urllib.request.Request(
        base + "/metrics",
        headers={"Accept": "application/openmetrics-text"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert "openmetrics-text" in resp.headers["Content-Type"]
        om = resp.read().decode()
    assert om.rstrip().endswith("# EOF")
    corr = headers["X-Correlation-ID"]
    exemplar_lines = [ln for ln in om.splitlines() if "# {" in ln]
    assert any(corr in ln for ln in exemplar_lines), (corr, exemplar_lines[:5])
    trace_ids = {r["trace_id"]
                 for r in _get(base, "/admin/requests?limit=500")["requests"]}
    assert corr in trace_ids


def test_manual_postmortem_trigger_and_listing(echo_app):
    app, base, pm_dir = echo_app
    req = urllib.request.Request(
        base + "/admin/postmortem",
        data=json.dumps({"detail": "operator drill"}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        out = json.loads(resp.read())["data"]
    assert out["reason"] == "manual"
    bundle = json.load(open(out["path"]))
    assert bundle["detail"] == "operator drill"
    listing = _get(base, "/admin/postmortem")
    assert listing["dir"] == pm_dir
    assert any(
        os.path.join(pm_dir, b["file"]) == out["path"]
        for b in listing["bundles"]
    )
