"""Mean number of pool chunks issued and not yet fetched when a prefill
(whole or chunk) was issued: what it queued behind on the device
(DispatchRecord ``chunks_ahead``)."""
from benchmark.span_readers import prefill_chunks_ahead_mean as read  # noqa: F401
