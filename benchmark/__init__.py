"""The benchmark: one command runs one cell of ``BENCHMARK.json`` once.

Everything that decides a number lives here (traffic generation, the
reduction from traces to metrics, the table of peaks, operation and byte
counts, the plain references and the comparison that decides ``correct``).
From the program it takes only the system under test.
"""
