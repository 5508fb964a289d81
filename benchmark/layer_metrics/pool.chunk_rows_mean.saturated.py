"""Mean rows (live slots) per pooled decode chunk (DispatchRecord
batch_size, kind decode_chunk)."""
from benchmark.readers import chunk_rows_mean as read  # noqa: F401
