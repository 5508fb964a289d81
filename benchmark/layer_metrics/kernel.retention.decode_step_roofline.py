"""The pooled decode program of a retention model against its roofline: the
least time the chip could take for the traced chunks (weights, head and the
live rows' state read and written over the HBM peak, or the FLOPs over the
bf16 peak; HBM bounds it) over the device time the trace shows for them."""
from benchmark import spec
from benchmark.readers import roofline_share


def read(run):
    found = spec.load_module("kernels", "retention_decode_step").pooled_program(run)
    if found is None:
        return None
    return roofline_share(run, "retention_decode_step", lambda name: name == found[0])
