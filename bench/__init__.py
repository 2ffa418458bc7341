"""Chip benchmark of the Cappuccino reproduction (see ``bench/run.py``).

Everything the benchmark measures with lives here: traffic generation,
the plain float32 reference, FLOP/byte counts, the peaks table, the
reduction from profiler traces to metrics, and the comparison that
decides ``correct``.  The program under ``src/`` is used only as the
system under test.
"""
