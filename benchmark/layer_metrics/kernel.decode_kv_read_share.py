"""How much of the pool's K/V cache its traffic makes live: the blocks of
128 positions the window's decode chunks' attention had to read
(DispatchRecord ``kv_blocks_read``: steps x the sum over live rows of the
blocks up to the row's length) over the blocks the cache holds
(``kv_blocks_held``: steps x slots x max_seq / 128). The decode form of the
attention kernel (``gofr_tpu/ops/flash.py``) does work in proportion to the
first; a program whose records lack the fields reads nothing."""
from benchmark.readers import dispatches


def read(run):
    chunks = [d for d in dispatches(run, ("decode_chunk",)) if d.get("kv_blocks_held")]
    if not chunks:
        return None
    read_, held = (sum(d[key] for d in chunks) for key in ("kv_blocks_read", "kv_blocks_held"))
    return 100.0 * read_ / held
