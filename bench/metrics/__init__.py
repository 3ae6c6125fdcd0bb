"""Per-layer metric readers, one file per metric, found by name.

Each module defines ``read(run)``: the metric's value from the traced
run's data (``bench.run.TracedRun``), or None where that run has nothing
to read, in which case the metric is left out of the result line.
"""
