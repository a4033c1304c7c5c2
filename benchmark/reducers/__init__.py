"""One small reader per kind of per-layer metric. ``read(run, params)``
gets what the run gathered (host spans and counters; with ``--trace 1``
the device operations too) and the parameters of the metric's own file
under ``metrics/``. A reader that finds nothing to read returns None and
the metric is left out of the line; a trace that lacks a NAMED kernel or
program is an error, never a 0."""
