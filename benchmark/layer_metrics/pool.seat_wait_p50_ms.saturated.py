"""Median wait for a seat of the window's served requests that found the
decode pool full (FlightRecord ``pool_seat_wait_s``, the span
``gofr.pool.seat_wait``): the pause between such a stream's first token and
its second."""
from benchmark.span_readers import flight_p50_ms


def read(run):
    return flight_p50_ms(run, "pool_seat_wait_s")
