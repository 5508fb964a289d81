"""FlightRecord t_dispatch - t_enqueue: the wait for a prefill cohort."""
from benchmark.readers import pct


def read(run):
    return pct([r["queue_wait_s"] for r in run.flights
                if r.get("status") == "ok" and r.get("queue_wait_s") is not None], 50, 1e3)
