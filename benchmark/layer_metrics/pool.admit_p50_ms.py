"""Median time from a request's first token to the decode pool's answer, a
slot or the refusal that sends it solo (FlightRecord ``pool_admit_s``)."""
from benchmark.span_readers import flight_p50_ms


def read(run):
    return flight_p50_ms(run, "pool_admit_s")
