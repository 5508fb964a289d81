"""Median time from the first token on the host to its frame handed to
the socket (FlightRecord ``first_frame_s``; streams only)."""
from benchmark.span_readers import flight_p50_ms


def read(run):
    return flight_p50_ms(run, "first_frame_s")
