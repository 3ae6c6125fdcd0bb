"""Median wait from a request's due time to its admission by
``admit_pending`` (host clock, requests admitted in the window)."""
import numpy as np


def read(run):
    if not run.queue_waits:
        return None
    return float(np.percentile(run.queue_waits, 50))
