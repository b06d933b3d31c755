"""Chip benchmark of the scheduler: cells, metrics and references, driven by
the data files named in ``BENCHMARK.json`` (see ``chipbench/run.py``)."""
