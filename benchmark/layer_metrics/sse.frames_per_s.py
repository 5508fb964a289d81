"""Token frames the server wrote a second: the ``frames`` of the requests
begun in the window and finished, over its length (a request that ends in
the drain counts whole, one begun before the window not at all). The load
on the server's one event loop, at which ``request.parse_p50_ms`` tips."""
from benchmark.span_readers import _field, _finished


def read(run):
    frames = _field(_finished(run), "frames")
    if not frames or run.seconds <= 0:
        return None
    return sum(frames) / run.seconds
