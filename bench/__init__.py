"""The on-chip benchmark: one command, ``python3 bench/run.py``, driven by
``BENCHMARK.json`` and the data files beside this package."""
