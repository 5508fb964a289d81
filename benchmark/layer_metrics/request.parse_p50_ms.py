"""Median time from the request's record to its prefill's enqueue: parse,
admission, tokens prepared (FlightRecord ``parse_s``)."""
from benchmark.span_readers import flight_p50_ms


def read(run):
    return flight_p50_ms(run, "parse_s")
