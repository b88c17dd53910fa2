"""The benchmark: cells, traffic, estimators, trace reduction, reference.

Everything a number rests on lives here (``BENCHMARK.json`` ``paths``), so a
PR that claims a gain cannot change the yardstick.  From the program the
benchmark takes only the system under test and its spans, counters and kernel
names.  Entry point: ``python benchmark/run.py``.
"""
