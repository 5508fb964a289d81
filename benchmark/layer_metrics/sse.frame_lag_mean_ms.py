"""Mean wait of a token frame, from its tokens being the stream's to send
(the pool's burst put, the first token's mark) to the frame's write
returning on the server's event loop: the window's finished streams'
FlightRecord ``frame_lag_mean_s`` weighted by their ``frames``
("gofr.sse.frame"). A program before PR 38 has neither field."""
from benchmark.span_readers import _finished


def read(run):
    streams = [r for r in _finished(run) if r.get("frames") and r.get("frame_lag_mean_s") is not None]
    frames = sum(r["frames"] for r in streams)
    if not frames:
        return None
    return 1e3 * sum(r["frames"] * r["frame_lag_mean_s"] for r in streams) / frames
