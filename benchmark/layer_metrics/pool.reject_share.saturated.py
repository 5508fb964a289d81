"""Share of the window's requests the decode pool refused (they decode
solo): FlightRecords with a pool_reject_reason."""
from benchmark.readers import reject_share as read  # noqa: F401
