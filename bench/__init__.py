"""The chip benchmark of this repository: one cell of ``BENCHMARK.json`` per
run, found by name (``bench/main.py``)."""
