"""Dispatch cost model + residual watchtower (gofr_tpu/tpu/costmodel.py):
roofline prediction units, calibration provenance, residual EMA
accounting, the anomaly verdicts and their false-positive floor, the
AnomalyRing, the costcal fit/check tooling — plus the compile-free
end-to-end acceptance spine on the echo model: a healthy run serves
predicted_ms on every dispatch and ZERO anomalies; an injected stall
(below the watchdog threshold, so the engine never wedges) raises a
counted ``slow_dispatch`` anomaly visible on ``/admin/anomalies``,
``/metrics``, the rider's flight record, and a forced postmortem
bundle."""

import importlib.util
import json
import os
import pathlib
import socket
import time
import urllib.request

import pytest

from gofr_tpu.metrics import Registry
from gofr_tpu.tpu.costmodel import (
    ANOMALY_CAUSES,
    EMA_MIN_SAMPLES,
    AnomalyRing,
    CostModel,
    CostSheet,
)
from gofr_tpu.tpu.introspect import DispatchRecord, DispatchTimeline

REPO = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "costcal", REPO / "tools" / "costcal.py"
)
costcal = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(costcal)


def _model(**kw) -> CostModel:
    return CostModel(metrics=Registry(), **kw)


def _backdated(record: DispatchRecord, observed_ms: float) -> DispatchRecord:
    """Fabricate a dispatch duration by backdating ``t_running``:
    ``finish()`` is set-once on ``t_done``, so the only way to control
    the observed duration through the real timeline path is to move the
    start. The few microseconds between backdating and ``finish()`` are
    noise at the millisecond scales these tests assert with approx."""
    record.t_running = time.perf_counter() - observed_ms / 1e3
    return record


# -- prediction units ---------------------------------------------------------

def test_roofline_prediction_math():
    cm = _model()
    cm.eff_flops = 1e12   # 1 TFLOP/s effective
    cm.eff_bw = 1e11      # 100 GB/s effective
    cm.overhead_ms = 0.5
    cm.install(CostSheet("prefill", bucket=64, batch=8, flops=2e9,
                         bytes_accessed=1e6, source="hlo"))
    ms, source = cm.predict_ms("prefill", bucket=64, batch=8)
    # compute-bound: 2e9/1e12 s = 2ms >> 1e6/1e11 s = 0.01ms
    assert ms == pytest.approx(2.0 + 0.5)
    assert source == "hlo"
    # flip to bandwidth-bound
    cm.install(CostSheet("decode_chunk", bucket=0, batch=8, flops=1e6,
                         bytes_accessed=5e9, source="hlo"))
    ms, _ = cm.predict_ms("decode_chunk", bucket=0, batch=8)
    assert ms == pytest.approx(5e9 / 1e11 * 1e3 + 0.5)  # 50ms + overhead


def test_synthetic_sheet_and_unpriced_kinds():
    cm = _model()
    cm.overhead_ms = 0.2
    cm.install_synthetic("prefill", 5.0)
    ms, source = cm.predict_ms("prefill", bucket=64, batch=3)
    assert ms == pytest.approx(5.2) and source == "synthetic"
    # boot-time kinds have no steady-state cost truth — never priced,
    # even with a wildcard sheet installed for them
    cm.install_synthetic("warmup_compile", 5.0)
    assert cm.predict_ms("warmup_compile") == (None, None)
    assert cm.predict_ms("device_probe") == (None, None)
    # no sheet at all -> no prediction (never a made-up number)
    assert cm.predict_ms("decode_chunk", bucket=0, batch=1) == (None, None)


def test_sheet_lookup_fallback_chain():
    cm = _model()
    exact = CostSheet("prefill", bucket=64, batch=8, flops=1.0, source="hlo")
    cm.install(exact)
    # exact key wins
    assert cm.sheet_for("prefill", bucket=64, batch=8) is exact
    # same bucket, different batch: the compiled shape pads every batch
    # to the bucket's warm shape, so the bucket sheet is the cost truth
    assert cm.sheet_for("prefill", bucket=64, batch=3) is exact
    # different bucket, no sheet, no wildcard -> None
    assert cm.sheet_for("prefill", bucket=128, batch=3) is None
    cm.install_synthetic("prefill", 1.0)
    assert cm.sheet_for("prefill", bucket=128, batch=3).source == "synthetic"
    # hlo_* accessors never serve synthetic numbers
    assert cm.hlo_flops("prefill", bucket=64, batch=8) == 1.0
    assert cm.hlo_flops("prefill", bucket=128, batch=1) is None
    assert cm.hlo_bytes("prefill", bucket=64, batch=8) is None  # no bytes


def test_harvest_defensive_against_backend_quirks():
    cm = _model()

    class _Compiled:
        def cost_analysis(self):
            return {"flops": 3e9, "bytes accessed": 2e6}

        def memory_analysis(self):
            class _M:
                temp_size_in_bytes = 10
                argument_size_in_bytes = 20
                output_size_in_bytes = 30
            return _M()

    sheet = cm.harvest("prefill", 64, 8, _Compiled())
    assert sheet.flops == 3e9 and sheet.bytes_accessed == 2e6
    assert sheet.peak_memory_bytes == 60 and sheet.source == "hlo"

    class _Broken:
        def cost_analysis(self):
            raise RuntimeError("backend says no")

        def memory_analysis(self):
            raise RuntimeError("backend says no")

    assert cm.harvest("prefill", 128, 8, _Broken()) is None


def test_calibration_provenance_profile_vs_nominal(tmp_path):
    # the committed profile: cpu row matches the echo/tier-1 platform
    cm = _model()
    cm.calibrate("cpu", "cpu")
    assert cm.calibration["source"] == "profile"
    assert cm.calibration["matched"] == "cpu"
    assert cm.eff_flops and cm.eff_bw
    # missing profile: labeled nominal fallback off the published peaks,
    # never a silent zero or a boot failure
    cm2 = _model(profile_path=str(tmp_path / "missing.json"))
    cm2.calibrate("TPU v5 lite", "tpu")
    assert cm2.calibration["source"] == "nominal"
    assert cm2.eff_flops and cm2.eff_bw
    # a TPU kind with no published peak is an error (the device probe
    # raises the same one first), never a v5e-class guess
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        cm2.calibrate("warp drive", "tpu")
    # corrupt profile degrades the same way
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    cm3 = _model(profile_path=str(bad))
    cm3.calibrate("v5e", "tpu")
    assert cm3.calibration["source"] == "nominal"


def test_ctor_validates_thresholds():
    for kw in ({"anomaly_factor": 1.0}, {"min_anomaly_ms": -1},
               {"ema_alpha": 0.0}, {"ema_alpha": 1.5}, {"ema_band": 1.0}):
        with pytest.raises(ValueError):
            CostModel(**kw)


# -- residual accounting + anomaly verdicts -----------------------------------

def test_residual_ratio_and_family_ema():
    registry = Registry()
    cm = CostModel(metrics=registry, ema_alpha=0.5)
    cm.overhead_ms = 0.0
    cm.install_synthetic("prefill", 10.0)
    timeline = DispatchTimeline(metrics=registry, costmodel=cm)
    drec = timeline.begin("prefill", bucket=64, batch_size=2)
    assert drec.predicted_ms == pytest.approx(10.0)
    timeline.finish(_backdated(drec, observed_ms=20.0))
    assert drec.residual_ratio == pytest.approx(2.0, rel=0.05)
    fam = cm.residuals()["prefill/64"]
    assert fam["ema"] == pytest.approx(2.0, rel=0.05) and fam["n"] == 1
    # second observation at 1x moves the EMA halfway (alpha 0.5)
    drec2 = timeline.begin("prefill", bucket=64, batch_size=2)
    timeline.finish(_backdated(drec2, observed_ms=10.0))
    assert cm.residuals()["prefill/64"]["ema"] == pytest.approx(1.5, rel=0.05)
    # the gauge tracks the family EMA
    gauge = registry.gauge(
        "gofr_tpu_dispatch_residual_ratio", labels=("kind", "bucket")
    )
    assert gauge.data()[("prefill", "64")] == pytest.approx(1.5, rel=0.05)
    # an errored dispatch never poisons the EMA
    drec3 = timeline.begin("prefill", bucket=64, batch_size=2)
    timeline.finish(_backdated(drec3, observed_ms=9999.0), status="error")
    assert cm.residuals()["prefill/64"]["n"] == 2


def test_slow_dispatch_needs_factor_and_absolute_floor():
    cm = _model(anomaly_factor=4.0, min_anomaly_ms=50.0)
    cm.overhead_ms = 0.0
    cm.install_synthetic("prefill", 0.01)
    timeline = DispatchTimeline(costmodel=cm)
    # 100x the prediction but only ~1ms of excess: a noisy-ratio
    # microsecond dispatch must NOT page anyone
    drec = timeline.begin("prefill", bucket=64)
    timeline.finish(_backdated(drec, observed_ms=1.0))
    assert drec.anomaly is None and cm.ring.total() == 0
    # both the factor and the floor breached -> slow_dispatch
    drec2 = timeline.begin("prefill", bucket=64)
    timeline.finish(_backdated(drec2, observed_ms=80.0))
    assert drec2.anomaly == "slow_dispatch"
    events = cm.ring.events()
    assert events[0]["cause"] == "slow_dispatch"
    assert events[0]["dispatch_id"] == drec2.dispatch_id
    assert events[0]["predicted_ms"] == pytest.approx(0.01)


def test_ema_drift_latches_once_per_excursion():
    cm = _model(anomaly_factor=1000.0, min_anomaly_ms=1.0,
                ema_alpha=0.5, ema_band=2.0)
    cm.overhead_ms = 0.0
    cm.install_synthetic("decode_chunk", 10.0)
    timeline = DispatchTimeline(costmodel=cm)

    def dispatch(observed_ms):
        drec = timeline.begin("decode_chunk", bucket=0)
        timeline.finish(_backdated(drec, observed_ms=observed_ms))
        return drec

    # drift every dispatch to 3x: the EMA crosses the band only after
    # EMA_MIN_SAMPLES, and the verdict fires ONCE (latched)
    for _ in range(EMA_MIN_SAMPLES + 4):
        dispatch(30.0)
    drift_events = cm.ring.events(cause="ema_drift")
    assert len(drift_events) == 1
    assert cm.residuals()["decode_chunk/0"]["drift_latched"] is True
    # recover: enough 1x dispatches pull the EMA back inside the band
    # and unlatch; a second excursion then fires a SECOND event
    for _ in range(8):
        dispatch(10.0)
    assert cm.residuals()["decode_chunk/0"]["drift_latched"] is False
    for _ in range(8):
        dispatch(30.0)
    assert len(cm.ring.events(cause="ema_drift", limit=10)) == 2


def test_observe_skips_unpredicted_and_running_records():
    cm = _model()
    timeline = DispatchTimeline(costmodel=cm)
    # no sheet -> no prediction -> observe is a no-op
    drec = timeline.begin("prefill", bucket=64)
    assert drec.predicted_ms is None
    timeline.finish(_backdated(drec, observed_ms=500.0))
    assert drec.residual_ratio is None and cm.ring.total() == 0


# -- the anomaly ring ---------------------------------------------------------

def test_anomaly_ring_bounds_filters_and_stats():
    ring = AnomalyRing(capacity=4)
    for i in range(10):
        ring.record(kind="prefill" if i % 2 else "decode_chunk",
                    cause="slow_dispatch", dispatch_id=i)
    assert ring.total() == 10
    events = ring.events(limit=100)
    assert len(events) == 4  # bounded retention
    assert [e["dispatch_id"] for e in events] == [9, 8, 7, 6]  # newest first
    assert all(e["kind"] == "prefill"
               for e in ring.events(kind="prefill"))
    assert ring.events(cause="ema_drift") == []
    stats = ring.stats()
    assert stats["total"] == 10 and stats["retained"] == 4
    assert stats["capacity"] == 4 and ring.capacity == 4
    assert stats["by"]["prefill/slow_dispatch"] == 5
    assert stats["last_ts"] == events[0]["ts"]


def test_snapshot_and_overview_shapes():
    cm = _model()
    cm.calibrate("cpu", "cpu")
    cm.install_synthetic("prefill", 1.0)
    snap = cm.snapshot()
    assert snap["calibration"]["source"] == "profile"
    assert snap["thresholds"]["anomaly_factor"] == 4.0
    assert len(snap["sheets"]) == 1
    assert snap["anomalies"]["total"] == 0
    over = cm.overview()
    assert over["calibration"] == "profile" and over["sheets"] == 1
    assert over["anomalies_total"] == 0
    assert over["worst_residual_ema"] is None  # needs EMA_MIN_SAMPLES


# -- timebase: labeled rate_total (the rollup's filter) -----------------------

def test_rate_total_labels_filter():
    from gofr_tpu.timebase import TimebaseSampler

    registry = Registry()
    counter = registry.counter("gofr_x_total", "x", labels=("cause",))
    sampler = TimebaseSampler(registry, interval_s=1.0, window_s=60.0,
                              start=False)
    counter.inc(10, cause="a")
    counter.inc(100, cause="b")
    sampler.sample_now()
    counter.inc(10, cause="a")
    sampler.sample_now()
    all_rates = sampler.rate_total("gofr_x_total")
    only_a = sampler.rate_total("gofr_x_total", labels={"cause": "a"})
    only_b = sampler.rate_total("gofr_x_total", labels={"cause": "b"})
    assert all_rates[0][1] == only_a[0][1]  # only `a` moved
    assert only_b[0][1] == 0.0


# -- costcal: the fit/check tooling -------------------------------------------

def test_costcal_fit_reproduces_synthesis_truth(tmp_path):
    out = tmp_path / "records.json"
    costcal.synth(str(out))
    row = costcal.fit([str(out)])
    assert row["device_kind"] == costcal.SYNTH_DEVICE_KIND
    assert row["n_compute_bound"] and row["n_bandwidth_bound"]
    assert row["eff_flops"] == pytest.approx(
        costcal.SYNTH_EFF_FLOPS, rel=0.05
    )
    assert row["eff_bw"] == pytest.approx(costcal.SYNTH_EFF_BW, rel=0.05)
    assert row["overhead_ms"] == pytest.approx(
        costcal.SYNTH_OVERHEAD_MS, rel=0.25
    )


def test_costcal_check_passes_on_committed_artifacts(capsys):
    """The CI smoke: the committed records artifact must reproduce the
    committed cost_profile.json coefficients — editing one side without
    refitting the other is exactly the drift --check exists to catch."""
    rc = costcal.check(
        str(REPO / "gofr_tpu" / "tpu" / "cost_profile.json"),
        [str(REPO / "hw" / "r02" / "dispatch_records.json")],
        tolerance=0.1,
    )
    assert rc == 0, capsys.readouterr().out
    # and a drifted profile fails
    drifted = dict(json.loads(
        (REPO / "gofr_tpu" / "tpu" / "cost_profile.json").read_text()
    ))
    for row in drifted["device_kinds"].values():
        row["eff_flops"] = row["eff_flops"] * 3
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as fh:
        json.dump(drifted, fh)
        path = fh.name
    try:
        assert costcal.check(
            path, [str(REPO / "hw" / "r02" / "dispatch_records.json")],
            tolerance=0.1,
        ) == 1
    finally:
        os.unlink(path)


def test_costcal_synth_is_deterministic(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    costcal.synth(str(a))
    costcal.synth(str(b))
    assert a.read_text() == b.read_text()


# -- end-to-end: the compile-free acceptance spine ----------------------------

@pytest.fixture(scope="module")
def echo_app(tmp_path_factory):
    """Echo app with the cost model on defaults and the watchdog
    threshold ABOVE the injected stall — the anomaly path must fire
    without the engine ever wedging."""
    import gofr_tpu
    from gofr_tpu.openai_compat import register_openai_routes

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    pm_dir = str(tmp_path_factory.mktemp("postmortems"))
    env = {"HTTP_PORT": str(port), "LOG_LEVEL": "FATAL",
           "MODEL_NAME": "echo", "TOKENIZER": "byte",
           "BATCH_MAX_SIZE": "4", "BATCH_TIMEOUT_MS": "1",
           "TIMEBASE_INTERVAL_S": "0.05", "TIMEBASE_WINDOW_S": "60",
           "POSTMORTEM_DIR": pm_dir,
           # the 0.25s injected stall stays FAR below this: an anomaly
           # is a latency regression verdict, not a wedge
           "WATCHDOG_DISPATCH_TIMEOUT_S": "5"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("costmodel_e2e"))
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


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as resp:
        return json.loads(resp.read())["data"]


def test_healthy_dispatches_are_predicted_with_zero_anomalies(echo_app):
    app, base, _ = echo_app
    _post(base, {"messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 2, "temperature": 0})
    tpu = app.container.tpu
    recs = tpu.timeline.records(limit=20, kind="prefill")
    assert recs, "no prefill dispatch recorded"
    assert recs[0]["predicted_ms"] is not None
    assert recs[0]["cost_source"] == "synthetic"
    assert recs[0]["residual_ratio"] is not None
    assert recs[0]["anomaly"] is None
    # the acceptance contract: a healthy run produces ZERO anomalies
    out = _get(base, "/admin/anomalies")
    assert out["anomalies"] == [] and out["count"] == 0
    assert out["stats"]["total"] == 0


def test_costmodel_admin_page_serves_calibration_and_sheets(echo_app):
    app, base, _ = echo_app
    out = _get(base, "/admin/costmodel")
    assert out["calibration"]["source"] == "profile"
    assert out["calibration"]["matched"] == "cpu"
    sources = {s["source"] for s in out["sheets"]}
    assert sources == {"synthetic"}  # echo: no HLO harvest on CPU
    kinds = {s["kind"] for s in out["sheets"]}
    assert {"prefill", "decode_chunk"} <= kinds
    assert out["thresholds"]["anomaly_factor"] == 4.0
    assert "residuals" in out and "anomalies_per_sec" in out
    # the engine snapshot carries the small overview block
    engine = _get(base, "/admin/engine")
    assert engine["costmodel"]["calibration"] == "profile"
    assert engine["costmodel"]["sheets"] >= 2


def test_anomalies_endpoint_validates_params(echo_app):
    app, base, _ = echo_app
    import urllib.error

    for path in ("/admin/anomalies?limit=0",
                 "/admin/anomalies?limit=x",
                 "/admin/anomalies?cause=nope"):
        try:
            _get(base, path)
            raise AssertionError(f"expected 400 for {path}")
        except urllib.error.HTTPError as e:
            assert e.code == 400, path


def test_injected_stall_raises_counted_anomaly_everywhere(echo_app):
    """The tentpole's e2e: one dispatch stalls 0.25s (>=4x the echo
    prediction AND past the 50ms absolute floor, but far below the 5s
    watchdog threshold) -> a slow_dispatch anomaly lands in the ring,
    on the counter, on the rider's flight record, and in a forced
    postmortem bundle — while the engine stays serving throughout."""
    app, base, pm_dir = echo_app
    tpu = app.container.tpu
    tpu.runner.stall_hook = lambda: time.sleep(0.25)
    try:
        _post(base, {"messages": [{"role": "user", "content": "slowpoke"}],
                     "max_tokens": 2, "temperature": 0})
    finally:
        tpu.runner.stall_hook = None
    assert tpu.engine.state == "serving"  # an anomaly is NOT a wedge
    out = _get(base, "/admin/anomalies?cause=slow_dispatch")
    assert out["count"] >= 1
    event = out["anomalies"][0]
    assert event["cause"] == "slow_dispatch"
    assert event["observed_ms"] >= 250.0
    assert event["observed_ms"] >= event["predicted_ms"] * 4
    anomalous_id = event["dispatch_id"]
    # the dispatch record itself carries the verdict
    rec = [r for r in tpu.timeline.records(limit=50)
           if r["dispatch_id"] == anomalous_id]
    assert rec and rec[0]["anomaly"] == "slow_dispatch"
    # the flight record that rode the stalled dispatch is marked
    reqs = _get(base, "/admin/requests?limit=50")["requests"]
    marked = [r for r in reqs if r.get("anomalous_dispatches")]
    assert any(anomalous_id in r["anomalous_dispatches"] for r in marked)
    # the counter is on /metrics with the kind/cause labels
    with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
        om = resp.read().decode()
    assert "gofr_tpu_dispatch_anomalies_total" in om
    counted = [ln for ln in om.splitlines()
               if ln.startswith("gofr_tpu_dispatch_anomalies_total{")
               and 'cause="slow_dispatch"' in ln]
    assert counted and float(counted[0].rsplit(" ", 1)[1]) >= 1
    # overview + fleet-facing engine snapshot headline the anomaly
    over = _get(base, "/admin/overview")
    assert over["costmodel"]["anomalies_total"] >= 1
    assert over["costmodel"]["last_anomaly_ts"]
    # forced postmortem: the bundle snapshots the watchtower state
    req = urllib.request.Request(
        base + "/admin/postmortem",
        data=json.dumps({"detail": "costmodel drill"}).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        bundle_path = json.loads(resp.read())["data"]["path"]
    bundle = json.load(open(bundle_path))
    assert bundle["costmodel"]["calibration"]["source"] == "profile"
    assert bundle["costmodel"]["anomalies"]["total"] >= 1
    assert any(e["dispatch_id"] == anomalous_id
               for e in bundle["anomalies"])
    # COSTMODEL_* / ANOMALY_* keys are postmortem config fingerprints
    from gofr_tpu.postmortem import CONFIG_PREFIXES
    assert "COSTMODEL_" in CONFIG_PREFIXES and "ANOMALY_" in CONFIG_PREFIXES


def test_costmodel_off_disables_the_surface(tmp_path, monkeypatch):
    """COSTMODEL=off removes the whole layer: no predictions, no ring,
    503 on the admin pages (same contract as an unconfigured tpu)."""
    monkeypatch.setenv("MODEL_NAME", "echo")
    monkeypatch.setenv("TOKENIZER", "byte")
    monkeypatch.setenv("COSTMODEL", "off")
    monkeypatch.setenv("LOG_LEVEL", "FATAL")
    monkeypatch.chdir(tmp_path)
    import gofr_tpu

    app = gofr_tpu.new()
    tpu = app.container.tpu
    try:
        deadline = time.monotonic() + 30.0
        while tpu.engine.state != "serving" and time.monotonic() < deadline:
            time.sleep(0.02)
        assert tpu.costmodel is None
        assert tpu.timeline.costmodel is None
        out = tpu.generate([1, 2, 3], max_new_tokens=2)
        recs = tpu.timeline.records(limit=5, kind="prefill")
        assert recs and recs[0]["predicted_ms"] is None
        assert tpu.engine_snapshot()["costmodel"] is None
    finally:
        tpu.close()
