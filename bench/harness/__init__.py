"""Chip benchmark for the filtered-ANN serving path.

Everything that defines the yardstick lives in this package: the loader of
``BENCHMARK.json`` and of the per-name data files, the data and traffic
generators, the wall-clock open-loop batcher, the plain reference that
decides ``correct``, the reduction of the profiler trace and the roofline
arithmetic.  From the program it takes only the system under test
(``repro.core.FilteredANNEngine``), its tracer spans and its kernel names.
"""
