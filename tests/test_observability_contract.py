"""What the observability surface serves, pinned: the admin pages and the
postmortem bundle carry no cost-model block (the benchmark's trace is the
one account of utilisation), the dispatch and flight records carry exactly
the fields the guide documents, and the removed config keys are ordinary
undeclared names."""

import json
import pathlib
import re
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu.config import DECLARED_KEYS, EnvConfig

DOC = (pathlib.Path(__file__).resolve().parents[1]
       / "docs" / "advanced-guide" / "observability.md")

# names only the dispatch cost model and its gauges ever served
_REMOVED_NAMES = {
    "costmodel", "anomalies_per_sec", "anomalies_total", "worst_residual_ema",
    "mfu", "mbu", "predicted_ms", "residual_ratio", "cost_source", "anomaly",
    "anomalous_dispatches",
}


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=10) as resp:
        return json.loads(resp.read())["data"]


def _post(base, path, body):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["data"]


def _keys(node):
    """Every dict key anywhere in a JSON value."""
    if isinstance(node, dict):
        for key, value in node.items():
            yield key
            yield from _keys(value)
    elif isinstance(node, list):
        for value in node:
            yield from _keys(value)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """One echo replica that has served a request, behind a router whose
    prober has scraped it."""
    from gofr_tpu.devtools.chaos import chaos_fleet, chaos_router

    pm_dir = str(tmp_path_factory.mktemp("postmortems"))
    with chaos_fleet(1, env={"POSTMORTEM_DIR": pm_dir}) as (replica,):
        _post(replica.address, "/generate",
              {"tokens": [1, 2, 3], "max_new_tokens": 4})
        with chaos_router([replica]) as router:
            base = f"http://127.0.0.1:{router.http_server.port}"
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                rows = _get(base, "/admin/fleet/overview")["replicas"]
                if rows and rows[0]["queue_depth"] is not None:
                    break
                time.sleep(0.05)
            yield replica.address, base


def _engine_page(replica, router):
    page = _get(replica, "/admin/engine")
    assert page["engine"]["state"] == "serving"
    assert page["dispatches"]["by_kind"]["prefill"] >= 1
    return page


def _overview_page(replica, router):
    page = _get(replica, "/admin/overview")
    assert page["engine"]["state"] == "serving" and "slo_budget" in page
    return page


def _fleet_overview_page(replica, router):
    page = _get(router, "/admin/fleet/overview")
    (row,) = page["replicas"]
    assert row["name"] == "r0" and row["queue_depth"] is not None
    assert page["slo"] is not None  # the SLO headline still rides the scrape
    return page


def _postmortem_bundle(replica, router):
    path = _post(replica, "/admin/postmortem", {"detail": "contract"})["path"]
    bundle = json.load(open(path))
    assert bundle["dispatches"]
    assert bundle["engine"]["engine"]["state"] == "serving"
    assert bundle["anomalies"] == []  # the SLO engine's ring, empty when healthy
    assert not any(k.startswith("COSTMODEL") for k in bundle["config"]["keys"])
    return bundle


def _costmodel_route(replica, router):
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(replica, "/admin/costmodel")
    assert err.value.code == 404
    return {}


@pytest.mark.parametrize("page", [
    _engine_page, _overview_page, _fleet_overview_page, _postmortem_bundle,
    _costmodel_route,
], ids=lambda f: f.__name__.lstrip("_"))
def test_served_without_a_cost_model(page, fleet):
    served = page(*fleet)
    assert not _REMOVED_NAMES & set(_keys(served))


def _documented_fields(heading):
    """The backticked names in the first column of the table rows under
    ``heading`` of the observability guide, up to the next heading."""
    lines = DOC.read_text(encoding="utf-8").splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith(heading))
    fields = []
    for ln in lines[start + 1:]:
        if ln.startswith("#"):
            break
        if ln.startswith("| `"):
            fields += re.findall(r"`([a-z_]+)`", ln.split("|")[1])
    return fields


def _dispatch_record():
    from gofr_tpu.tpu.introspect import DispatchRecord

    return DispatchRecord(1, "decode_chunk").to_dict()


def _flight_record():
    from gofr_tpu.telemetry import FlightRecorder

    return FlightRecorder(capacity=4).start("echo", "/v1/completions").to_dict()


@pytest.mark.parametrize("record, heading", [
    (_dispatch_record, "### `GET /admin/dispatches`"),
    (_flight_record, "### `GET /admin/requests`"),
], ids=["dispatch_record", "flight_record"])
def test_record_fields_are_the_documented_ones(record, heading):
    """A field comes with its row in the guide, and goes with it: nothing
    rides a record that no reader was told about."""
    documented = _documented_fields(heading)
    assert len(documented) == len(set(documented))
    assert sorted(record()) == sorted(documented)


@pytest.mark.parametrize("key", [
    "COSTMODEL", "COSTMODEL_PROFILE", "COSTMODEL_HLO",
    "COSTMODEL_ANOMALY_FACTOR", "COSTMODEL_MIN_ANOMALY_MS",
    "COSTMODEL_EMA_ALPHA", "COSTMODEL_EMA_BAND",
])
def test_removed_key_is_an_undeclared_name(key, monkeypatch):
    """Set in the environment it is read by nothing: a value the parser
    once refused boots the engine, and the postmortem fingerprint leaves
    it out as it leaves out any name outside the framework's prefixes."""
    from gofr_tpu.logging import Level
    from gofr_tpu.metrics import Registry
    from gofr_tpu.postmortem import _config_fingerprint
    from gofr_tpu.testutil import MockLogger
    from gofr_tpu.tpu.device import new_device

    assert key not in DECLARED_KEYS and len(DECLARED_KEYS) == 151
    monkeypatch.setenv(key, "not-a-value")
    monkeypatch.setenv("MODEL_NAME", "echo")
    monkeypatch.setenv("TIMEBASE_ENABLED", "off")
    device = new_device(EnvConfig(), MockLogger(Level.FATAL), Registry())
    try:
        device.wait_ready(30)
        assert device.generate([1, 2, 3], max_new_tokens=2) == [1, 2]
        assert not hasattr(device, "costmodel")
    finally:
        device.close()
    assert key not in _config_fingerprint()["keys"]
