"""Share of the window the pool worker spent issuing and delivering
chunks (sum of DispatchRecord ``issue_s`` + ``deliver_s``, kind
decode_chunk, over the window): when this grows the host sets the pace."""
from benchmark.span_readers import pool_host_share as read  # noqa: F401
