"""1 - (union of device-operation intervals) / traced window."""
from benchmark.readers import idle_share as read  # noqa: F401
