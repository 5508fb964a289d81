"""Mean server-side TTFT: request accepted to first token frame handed to
the socket (FlightRecord ``server_ttft_s``). ``ttft_mean_ms`` less this is
accept, socket and generator lateness."""
from benchmark.span_readers import server_ttft_mean_ms as read  # noqa: F401
