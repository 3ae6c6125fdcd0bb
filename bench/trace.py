"""Reduction of a profiler trace to device time, busy share and gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Trace` of plain event lists; everything after that is
arithmetic on those lists, so it is tested on small synthetic traces.
Times are nanoseconds on the profiler's clock, which puts host and
device events on one axis.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    """Events of one traced window.

    ``ops``: per device, every operation the device ran (XLA ops and
    kernels).  ``modules``: per device, each execution of a compiled
    program.  ``host``: spans the benchmark annotated on the host.
    """

    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: List[Event]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[
                len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(evs)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(ops=ops, modules=modules, host=host)


def window(tr: Trace, name: str = "bench.window") -> Tuple[int, int]:
    """(start, end) of the host span that marks the measured window."""
    spans = [(s, s + d) for n, s, d in tr.host if n == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clip(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return sorted(out)


def merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: List[Event], lo: int, hi: int) -> int:
    """Length of the union of the events' intervals inside [lo, hi)."""
    return sum(b - a for a, b in merge(_clip(events, lo, hi)))


def gaps(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle intervals of [lo, hi) outside every event."""
    out, t = [], lo
    for a, b in merge(_clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle: List[Tuple[int, int]], host: List[Event]
              ) -> Dict[str, int]:
    """Idle nanoseconds by the benchmark's host span (``bench.*``) that
    covered them ("other" where none did)."""
    out: Dict[str, int] = {}
    for a, b in idle:
        left = b - a
        for name, s, d in host:
            if not name.startswith("bench.") or name == "bench.window":
                continue
            o = min(b, s + d) - max(a, s)
            if o > 0:
                out[name] = out.get(name, 0) + o
                left -= o
        if left > 0:
            out["other"] = out.get("other", 0) + left
    return out


def program(events: List[Event], match: str, lo: int, hi: int
            ) -> Tuple[int, int]:
    """(total ns, count) of events whose name contains ``match`` and
    that start inside [lo, hi)."""
    sel = [d for n, s, d in events if match in n and lo <= s < hi]
    return sum(sel), len(sel)


CONTAINERS = ("%while", "%conditional", "%call")


def top(events: List[Event], lo: int, hi: int, n: int = 10
        ) -> List[List]:
    """The ``n`` operation names with the most device time, leaving out
    control-flow ops whose span holds the operations of their body."""
    tot: Dict[str, int] = {}
    for name, s, d in events:
        if lo <= s < hi and not name.startswith(CONTAINERS):
            tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def device(tr: Trace) -> str:
    """The device plane to read: the first TPU."""
    names = sorted(tr.ops)
    if not names:
        raise ValueError("no device operations in the trace")
    return names[0]
