"""The chip benchmark of gofr_tpu: harness, traffic, plain reference, trace
reduction and the yardstick's arithmetic. ``BENCHMARK.json`` at the root of
the repo names the cells; everything a cell needs is a file found by name
under this directory (see ``benchmark/spec.py``)."""
