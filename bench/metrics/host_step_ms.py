"""Host time of a scheduler step that is not the wait for the device:
the median, over the ``sched.step`` spans inside the traced window, of
the step's span less its ``sched.sync`` child (host clock)."""
import numpy as np

from bench import spans


def read(run):
    steps = spans.in_window(run, ("sched.step",))
    syncs = spans.in_window(run, spans.SYNC)
    if steps is None or syncs is None:
        return None
    steps = spans.inside(run, steps)
    if not steps:
        return None
    from repro.obs.trace import self_times_ns
    return float(np.median(self_times_ns(steps, syncs))) * 1e-6
