"""Median host-timed duration of a pooled decode chunk dispatch."""
from benchmark.readers import decode_chunk_p50_ms as read  # noqa: F401
