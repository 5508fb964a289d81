"""The latest the server's event loop woke during any finished request of
the window (FlightRecord ``loop_lag_max_s``): near a client's longest
silence, the loop's thread did not run."""
from benchmark.span_readers import _field, _finished


def read(run):
    lags = _field(_finished(run), "loop_lag_max_s")
    return 1e3 * max(lags) if lags else None
