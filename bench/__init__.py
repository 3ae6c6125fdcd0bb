"""Chip benchmark of the paged serving path (entry: ``bench/run.py``)."""
