"""Median time from the HTTP server having read a request to its record's
start: middleware, routing and the hop to the handler's thread
(FlightRecord ``accept_s``; before ``parse_s``, in no other term of the
server's TTFT). A program before PR 38 stamps no such field."""
from benchmark.span_readers import flight_p50_ms


def read(run):
    return flight_p50_ms(run, "accept_s")
