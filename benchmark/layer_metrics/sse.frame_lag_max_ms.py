"""The longest wait of any token frame of the window's finished streams
between its delivery and its write (FlightRecord ``frame_lag_max_s``):
near a client's longest silence with ``pool.deliver_gap_max_ms`` small,
tokens were delivered and not written."""
from benchmark.span_readers import _field, _finished


def read(run):
    lags = _field(_finished(run), "frame_lag_max_s")
    return 1e3 * max(lags) if lags else None
