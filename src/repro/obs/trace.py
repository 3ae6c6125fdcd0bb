"""Structured event trace and host spans of the serving loop.

Two bounded rings live here.  :class:`EventTrace` keeps typed
control-plane events stamped with a step index; :class:`SpanLog` keeps
timed host spans (``sched.step`` and its phases, each request's
``sched.prefill``) stamped on ``time.perf_counter_ns``.

Every control-plane decision the serving loop makes on the host --
admission, retirement, CapacityError backpressure, COW forks, page
migrations, quarantines, block retirements, governor replans, setpoint
escalations -- lands here as one typed event with a step-index
timestamp.  The ring is bounded (old events drop), but per-kind counts
are cumulative, so exporters can report lifetime totals even after the
ring wraps.  Export is JSONL (one event per line) for offline
debugging of a serving incident: "which tenant's admission forced the
replan that moved shard 3 to 0.94 V?" is a grep, not a re-run.

Events are host-side by construction -- the compiled step emits
nothing -- so the trace adds zero work to the donated step and cannot
perturb the trace/launch budgets.

Spans are recorded once and read two ways.  While a profiler session
is open, :func:`span` opens a ``jax.profiler.TraceAnnotation`` of the
same name, so the session shows the span on the device trace's clock;
and it appends ``(name, start, end, rid, owner)`` to the process-wide
:data:`SPANS` log, which a benchmark or the metrics registry reads
after the fact.
The log is process-wide because the profiler is: a span recorded by a
scheduler that has since been freed can still be read beside the
trace it annotated.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import io
import itertools
import json
import time
from typing import Any, Dict, Iterable, Iterator, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled      # a profiler session is open

# The closed set of event kinds the scheduler emits.  Exporters and
# dashboards key on these; adding a kind is backward-compatible,
# renaming is not.
EVENT_KINDS = (
    "admission", "retirement", "backpressure", "cow_fork", "migration",
    "quarantine", "block_retire", "prefix_evict", "replan", "escalation",
)


@dataclasses.dataclass(frozen=True)
class Event:
    """One typed scheduler event.

    ``step`` is the scheduler's step index at emission time (the only
    clock the serving loop has that survives replay); ``shard``/``rid``
    are optional labels; ``data`` carries kind-specific fields.
    """

    kind: str
    step: int
    shard: Optional[int] = None
    rid: Any = None
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"kind": self.kind, "step": self.step}
        if self.shard is not None:
            out["shard"] = self.shard
        if self.rid is not None:
            out["rid"] = str(self.rid)
        if self.data:
            out.update(self.data)
        return out


class EventTrace:
    """Bounded ring of :class:`Event` with cumulative per-kind counts."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"trace capacity {capacity} must be >= 1")
        self.capacity = int(capacity)
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self.counts: collections.Counter = collections.Counter()
        self.emitted = 0

    def emit(self, kind: str, *, step: int, shard: Optional[int] = None,
             rid: Any = None, **data: Any) -> Event:
        if kind not in EVENT_KINDS:
            raise ValueError(
                f"unknown event kind {kind!r}; known kinds: "
                f"{EVENT_KINDS}")
        ev = Event(kind=kind, step=int(step), shard=shard, rid=rid,
                   data=data)
        self._ring.append(ev)
        self.counts[kind] += 1
        self.emitted += 1
        return ev

    def __len__(self) -> int:
        return len(self._ring)

    def __iter__(self) -> Iterator[Event]:
        return iter(self._ring)

    def events(self, kind: Optional[str] = None):
        """Events still in the ring, oldest first (optionally one kind)."""
        return [e for e in self._ring if kind is None or e.kind == kind]

    # ---- export ----------------------------------------------------------
    def to_jsonl(self, path_or_file) -> int:
        """Write the ring as JSON Lines; returns the event count."""
        own = isinstance(path_or_file, (str, bytes))
        f = open(path_or_file, "w") if own else path_or_file
        try:
            for ev in self._ring:
                f.write(json.dumps(ev.to_dict(), default=str) + "\n")
        finally:
            if own:
                f.close()
        return len(self._ring)

    def jsonl(self) -> str:
        buf = io.StringIO()
        self.to_jsonl(buf)
        return buf.getvalue()


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------
SPAN_CAPACITY = 1 << 18


class Span(NamedTuple):
    """One recorded span: times are ``time.perf_counter_ns`` values."""

    name: str
    start_ns: int
    end_ns: int
    rid: Any = None
    owner: int = 0


class SpanLog:
    """Bounded ring of :class:`Span`, in one preallocated array.

    Spans are appended when they end, so the ring is ordered by end
    time and a parent lands after its children.  When it wraps it
    overwrites its oldest spans and says so: :attr:`lost_until_ns` is
    the end of the newest span it dropped, so every span that ended
    later is still held.  ``owner`` tags the spans of one recorder
    (a scheduler's :class:`~repro.obs.metrics.MetricsRegistry`).
    """

    def __init__(self, capacity: int = SPAN_CAPACITY):
        if capacity < 1:
            raise ValueError(f"span capacity {capacity} must be >= 1")
        self.capacity = int(capacity)
        self._ring: List[Optional[tuple]] = [None] * self.capacity
        self.recorded = 0                    # spans ever added
        self.lost_until_ns: Optional[int] = None

    def add(self, name: str, start_ns: int, end_ns: int, rid: Any = None,
            owner: int = 0) -> None:
        i = self.recorded % self.capacity
        old = self._ring[i]
        if old is not None and (self.lost_until_ns is None
                                or old[2] > self.lost_until_ns):
            self.lost_until_ns = old[2]
        self._ring[i] = (name, start_ns, end_ns, rid, owner)
        self.recorded += 1

    def holds_since(self, t_ns: int) -> bool:
        """True when every span that ended at or after ``t_ns`` is
        still in the ring."""
        return self.lost_until_ns is None or self.lost_until_ns < t_ns

    def select(self, names: Optional[Iterable[str]] = None, *,
               lo_ns: Optional[int] = None, hi_ns: Optional[int] = None,
               owner: Optional[int] = None, since: int = 0) -> List[Span]:
        """Held spans, oldest end first: those named ``names`` (all
        when None), of ``owner`` (any when None), that overlap
        ``[lo_ns, hi_ns]``, among those added after the first
        ``since`` (a past value of :attr:`recorded`)."""
        cap, n = self.capacity, self.recorded
        first = min(max(since, n - cap), n)
        a, count = first % cap, n - first
        held = (self._ring[a:a + count] if a + count <= cap
                else self._ring[a:] + self._ring[:a + count - cap])
        want = None if names is None else frozenset(names)
        return [Span(*e) for e in held
                if (want is None or e[0] in want)
                and (owner is None or e[4] == owner)
                and (lo_ns is None or e[2] >= lo_ns)
                and (hi_ns is None or e[1] <= hi_ns)]


SPANS = SpanLog()
_OWNERS = itertools.count(1)


def new_owner() -> int:
    """A fresh ``owner`` tag for one recorder's spans."""
    return next(_OWNERS)


class span:
    """Context manager timing one host span: a profiler annotation named
    ``name`` (while a profiler session is open, so that the span lands
    in its trace on the device trace's clock) and an entry of
    :data:`SPANS` on ``time.perf_counter_ns``.  The serving loop's
    names start with ``sched.``.  One object may time its span again
    and again, never nested in itself, and keeps a running ``count``
    and ``total_ns`` of the times it closed, which outlive the log's
    ring."""

    __slots__ = ("name", "rid", "owner", "count", "total_ns", "_ann", "_t0")

    def __init__(self, name: str, rid: Any = None, owner: int = 0):
        self.name, self.rid, self.owner = name, rid, owner
        self.count = self.total_ns = 0

    def __enter__(self):
        if _profiling():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        SPANS.add(self.name, self._t0, t1, self.rid, self.owner)
        self.count += 1
        self.total_ns += t1 - self._t0
        return False


_NULL = contextlib.nullcontext()      # records nothing, opens nothing


# The serving loop's step phases (``sched.admit`` runs between steps).
STEP_SPANS = ("sched.admit", "sched.step", "sched.feed", "sched.dispatch",
              "sched.sync", "sched.post")


def step_spans(owner: Optional[int]) -> Dict[str, Any]:
    """A reusable :class:`span` for each of :data:`STEP_SPANS`, tagged
    ``owner``; with ``owner`` None, stand-ins that record nothing."""
    if owner is None:
        return dict.fromkeys(STEP_SPANS, _NULL)
    return {n: span(n, None, owner) for n in STEP_SPANS}


def self_times_ns(parents: List[Span], children: List[Span]) -> List[int]:
    """Each parent's duration less the part of it that the union of
    ``children`` covers (its self time with respect to them)."""
    merged: List[List[int]] = []
    for a, b in sorted((c.start_ns, c.end_ns) for c in children):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    ends = [b for _, b in merged]
    out = []
    for p in parents:
        covered = 0
        for a, b in itertools.islice(
                merged, bisect.bisect_right(ends, p.start_ns), None):
            if a >= p.end_ns:
                break
            covered += min(b, p.end_ns) - max(a, p.start_ns)
        out.append(p.end_ns - p.start_ns - covered)
    return out
