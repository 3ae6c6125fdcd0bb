"""Fleet observability plane: in-step metrics, energy accounting, and
a structured event trace for the undervolted serving scheduler.

Three layers, all preserving the one-donated-step / flat-trace /
flat-pallas-launch serving contracts:

  * :mod:`repro.obs.metrics` -- a :class:`MetricsRegistry` of per-shard
    *donated* counters (tokens decoded, prefill tokens, KV bytes moved
    through the page tables, pages migrated) accumulated inside the
    compiled step with zero extra pallas launches, plus host-side
    step-latency histograms (p50/p95/p99).
  * :mod:`repro.obs.energy` -- an :class:`EnergyModel` that converts
    bytes-moved counters and measured wall time into joules/token and
    $/1M-tokens at any frontier voltage (pJ/byte from the paper's power
    curve + static watts), the unit fleets actually buy.
  * :mod:`repro.obs.trace` -- a bounded ring buffer of typed scheduler
    events (admission, retirement, backpressure, COW fork, migration,
    quarantine, block retirement, replan, escalation), exported as
    JSONL and as Prometheus-text / JSON snapshots
    (:mod:`repro.obs.export`); and the host spans of the serving loop
    (:func:`span`, the process-wide :data:`SPANS` log), which land in
    a profiler trace on the device trace's clock as well.
"""
from repro.obs.energy import DEFAULT_ENERGY_MODEL, EnergyModel
from repro.obs.metrics import (STEP_COUNTERS, MetricsRegistry, ObsConfig,
                               step_counter_delta)
from repro.obs.trace import SPANS, Event, EventTrace, Span, SpanLog, span

__all__ = [
    "DEFAULT_ENERGY_MODEL", "EnergyModel", "STEP_COUNTERS",
    "MetricsRegistry", "ObsConfig", "step_counter_delta", "Event",
    "EventTrace", "SPANS", "Span", "SpanLog", "span",
]
