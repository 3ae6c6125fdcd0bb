"""The program's own host spans inside the traced window, on the trace's
clock.

The scheduler records its spans (``sched.step`` and its phases, the
request spans) in the process-wide span log of ``repro.obs.trace``, on
``time.perf_counter_ns``.  The readers here take the spans that overlap
the window ``[run.t0, run.t1]`` (perf_counter seconds; ``run.t1`` is
where the traced part ends) and, where they meet device time, map a
perf_counter time ``t`` onto the trace by the window's two edges:
``lo + (t - t0) * (hi - lo) / (t1 - t0)``, where ``(lo, hi)`` is the
trace's ``bench.window`` span.  Two anchors cancel any drift between
the clocks.

Each function returns None where the program keeps no span log, or
where the log has wrapped past the window's start.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from bench import trace as tracemod

# What the host does between steps and around the dispatch: every step
# phase but the wait for the device.
HOST = ("sched.admit", "sched.feed", "sched.dispatch", "sched.post")
SYNC = ("sched.sync",)


def log():
    """The program's span log, or None where it keeps none."""
    try:
        from repro.obs.trace import SPANS
    except ImportError:
        return None
    return SPANS


def in_window(run, names: Sequence[str]) -> Optional[list]:
    """Spans named ``names`` that overlap the window, oldest end first."""
    spans = log()
    t0, t1 = round(run.t0 * 1e9), round(run.t1 * 1e9)
    if spans is None or not spans.holds_since(t0):
        return None
    return spans.select(names, lo_ns=t0, hi_ns=t1)


def inside(run, spans: list) -> list:
    """The spans that lie wholly inside the window."""
    t0, t1 = run.t0 * 1e9, run.t1 * 1e9
    return [s for s in spans if t0 <= s.start_ns and s.end_ns <= t1]


def to_trace(run, t_ns: float) -> float:
    """A perf_counter time (ns) on the trace's clock (ns)."""
    lo, hi = tracemod.window(run.trace)
    t0, t1 = run.t0 * 1e9, run.t1 * 1e9
    return lo + (t_ns - t0) * (hi - lo) / (t1 - t0)


def overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
            ) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_share(run, names: Sequence[str]) -> Optional[float]:
    """Percent of the traced window in which the device was idle while
    the host was inside one of the spans named ``names``."""
    if run.trace is None:
        return None
    spans = in_window(run, names)
    if spans is None:
        return None
    tr = run.trace
    lo, hi = tracemod.window(tr)
    idle = tracemod.gaps(tr.ops[tracemod.device(tr)], lo, hi)
    host = tracemod.merge([(to_trace(run, s.start_ns),
                            to_trace(run, s.end_ns)) for s in spans])
    return 100.0 * overlap(idle, host) / (hi - lo)
