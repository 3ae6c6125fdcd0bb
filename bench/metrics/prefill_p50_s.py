"""Median program time of a request's prefill: its ``sched.prefill``
span, from admission to the arrival of token 0 on the host, counted
only where the scheduler was inside its own ``sched.step`` or
``sched.admit`` spans, over the requests admitted inside the traced
window (the base of ``queue_wait_p50_s``); a span may end after the
window (host clock).  Time the harness holds the loop, such as the
profiler's stop after the traced part, is left out."""
import numpy as np

from bench import spans
from bench import trace as tracemod


def read(run):
    pre = spans.in_window(run, ("sched.prefill",))
    if pre is None:
        return None
    t0, t1 = run.t0 * 1e9, run.t1 * 1e9
    pre = [s for s in pre if t0 <= s.start_ns <= t1]
    if not pre:
        return None
    busy = tracemod.merge([
        (s.start_ns, s.end_ns) for s in spans.log().select(
            ("sched.step", "sched.admit"),
            lo_ns=min(s.start_ns for s in pre),
            hi_ns=max(s.end_ns for s in pre))])
    dur = [spans.overlap([(s.start_ns, s.end_ns)], busy) for s in pre]
    return float(np.median(dur)) * 1e-9
