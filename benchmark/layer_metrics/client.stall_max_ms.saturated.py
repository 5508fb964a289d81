"""The longest stretch of the window with a request in flight and no token
frame on any stream (a stall of the server, whatever its cause)."""
from benchmark.readers import stall_max_ms as read  # noqa: F401
