"""``kernel.decode_kv_read_share`` past the knee: the same counters, where
the pool's slots are full and the rows long."""
from benchmark.spec import load_module

read = load_module("layer_metrics", "kernel.decode_kv_read_share").read
