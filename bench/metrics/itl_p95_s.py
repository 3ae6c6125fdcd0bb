"""95th percentile of the gaps between consecutive output tokens of a
request, over every gap that ends inside the window (host clock)."""
import numpy as np


def read(run):
    gaps = run.itls()
    if not gaps:
        return None
    return float(np.percentile(gaps, 95))
