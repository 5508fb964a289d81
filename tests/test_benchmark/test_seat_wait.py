"""``pool.seat_wait_share.saturated`` and ``pool.seat_wait_p50_ms.saturated``
(PR 37): how many of a window's served requests found the decode pool full
and waited for a seat in it, and for how long, from the FlightRecord's
``pool_seat_wait_s``. Hand-built records; no chip, no server."""

import types

import pytest

from benchmark import spec

SHARE = "pool.seat_wait_share.saturated"
P50 = "pool.seat_wait_p50_ms.saturated"
CELL = "mistral-7b-int8.chat-saturated"


def _run(*waits, extra=()):
    flights = [{"status": "ok", "pool_admit_s": 0.004, "pool_seat_wait_s": w} for w in waits]
    return types.SimpleNamespace(flights=flights + list(extra))


def _read(name):
    return spec.load_module("layer_metrics", name).read


def test_share_counts_the_served_requests_that_waited():
    run = _run(None, 0.2, None, 0.4, 0.9, None, None, None,
               extra=[{"status": "error", "pool_seat_wait_s": 5.0}])  # not served: not counted
    assert _read(SHARE)(run) == pytest.approx(100.0 * 3 / 8)
    # every request seated at once: nobody waited, and that is a reading
    assert _read(SHARE)(_run(None, None)) == 0.0


def test_p50_is_the_median_wait_of_those_that_waited():
    run = _run(None, 0.2, None, 0.4, 0.9, extra=[{"status": "error", "pool_seat_wait_s": 5.0}])
    assert _read(P50)(run) == pytest.approx(400.0)
    assert _read(P50)(_run(None, None)) is None  # nobody waited: no median


@pytest.mark.parametrize("name", [SHARE, P50])
def test_a_program_whose_flights_have_no_such_field_reads_nothing(name):
    """The parent's records (a full pool refused the request, which decoded
    solo): the reader gives None and the harness leaves the metric out."""
    old = [{"status": "ok", "pool_admit_s": 0.004, "pool_reject_reason": reason}
           for reason in (None, "no_free_slots", None)]
    assert _read(name)(types.SimpleNamespace(flights=old)) is None
    assert _read(name)(types.SimpleNamespace(flights=[])) is None


def test_the_saturated_cell_reports_both_and_no_steady_cell_does():
    manifest = spec.load_manifest()
    for name, unit in ((SHARE, "%"), (P50, "ms")):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_span",
                         "layer": "decode pool", "moves": "out_tok_s", "workloads": [CELL]}
    for cell in (c["name"] for c in manifest["workloads"]):
        names = {m["name"] for m in spec.metrics_of_cell(manifest, cell, "per_layer")}
        assert ({SHARE, P50} <= names) == (cell == CELL)
        assert not ({SHARE, P50} & names) or cell == CELL
