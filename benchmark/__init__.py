"""The benchmark: one command runs one cell of BENCHMARK.json once.

Everything a later PR may not change lives here: traffic generation, the
estimators, the trace reduction, the table of peaks, the arithmetic of
operations and bytes, the plain reference, and the comparison that
decides ``correct``. From the program it takes only the system under
test and its counters and kernel names.
"""
