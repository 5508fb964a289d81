"""memory_stats()["peak_bytes_in_use"] of the fullest chip, after the window."""
from benchmark.readers import hbm_peak_gb as read  # noqa: F401
