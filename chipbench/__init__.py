"""The chip benchmark of the sDTW system: one command that runs one cell
of ``BENCHMARK.json`` on a TPU and prints its metrics.  See ``run.py``."""
