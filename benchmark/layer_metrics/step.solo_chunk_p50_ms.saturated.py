"""Median issue + fetch wait of one chunk of a solo decode (DispatchRecord
kind decode_solo): what a request the pool refused pays a chunk, beside
the pool's own chunks on the same device."""
from benchmark.span_readers import solo_chunk_p50_ms as read  # noqa: F401
