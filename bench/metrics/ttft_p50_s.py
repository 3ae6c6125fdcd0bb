"""Median time from a request's due time to its first token, over every
request whose first token came inside the window (host clock).  The
median: an open-loop cell below its knee finishes some tens of requests
in a window, too few for a tail with ten samples beyond it."""
import numpy as np


def read(run):
    ttft = run.ttfts()
    if not ttft:
        return None
    return float(np.percentile(ttft, 50))
