"""The longest interval between two consecutive deliveries of tokens to one
request, over the window's finished requests (FlightRecord
``deliver_gap_max_s``): the pool's side of a silence. One or two pooled
chunks' cadence in a sound run (the first gap holds the seat and the
chunk queued ahead); near a client's longest silence, the pool or the
device delivered nothing."""
from benchmark.span_readers import _field, _finished


def read(run):
    gaps = _field(_finished(run), "deliver_gap_max_s")
    return 1e3 * max(gaps) if gaps else None
