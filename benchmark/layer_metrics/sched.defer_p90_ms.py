"""FlightRecord sched_defer_s: how long the interference scheduler held a
request's prefill back behind decode chunks."""
from benchmark.readers import flights, pct


def read(run):
    return pct(flights(run, "sched_defer_s"), 90, 1e3)
