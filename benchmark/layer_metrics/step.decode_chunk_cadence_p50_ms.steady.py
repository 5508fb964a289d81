"""Median interval between the decode pool's deliveries (DispatchRecord
``cadence_s``, kind decode_chunk): one pooled chunk's device time as the
program itself can tell it, where ``step.decode_chunk_p50_ms`` holds the
wait behind the chunks in flight."""
from benchmark.span_readers import decode_chunk_cadence_p50_ms as read  # noqa: F401
