"""The pooled decode program's share of its roofline: the least time the
chip could take for the traced chunks (weights and live KV over the HBM
peak, or the FLOPs over the bf16 peak, whichever is larger; HBM bounds it)
over the device time the trace shows for them."""
from benchmark.readers import decode_step_roofline as read  # noqa: F401
