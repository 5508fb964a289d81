"""Median host time to enqueue the move of a prefilled row's state into
its decode slot (span ``gofr.pool.state_insert``; FlightRecord
``state_insert_s``)."""
from benchmark.span_readers import flight_p50_ms


def read(run):
    return flight_p50_ms(run, "state_insert_s")
