"""The pooled decode program's share of the chip's bf16 peak: the FLOPs of
the traced chunks (2 x weights x live rows, and the attention over the live
KV) over what the peak does in the device time the trace shows for them.
HBM bounds a decode step, so this is a few per cent where the roofline
share is tens: it is the whole step's number, which still reads when a
kernel inside the step is taken off the path."""
from benchmark.readers import decode_step_mfu as read  # noqa: F401
