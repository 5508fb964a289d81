"""How late the server's event loop woke from its 50 ms sleeps: the mean
over the window's finished requests of the mean over each one's life
(FlightRecord ``loop_lag_mean_s``, from ``http/server.py::LoopClock``).
Small where parse and first-frame waits are long, the wait is between
threads (the interpreter lock) and not the loop's."""
from benchmark.span_readers import _field, _finished, _mean


def read(run):
    return _mean(_field(_finished(run), "loop_lag_mean_s"), 1e3)
