"""CPU tests of the readers of the program's spans (``bench/spans.py``
and the metrics that use it), on a synthetic trace and synthetic spans.
No number here is a device metric."""
from __future__ import annotations

import importlib.util
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spans  # noqa: E402
from bench import trace as tr  # noqa: E402

import repro.obs.trace as obs_trace  # noqa: E402
from repro.obs.trace import SpanLog  # noqa: E402

MS = 1_000_000
T0 = 100.0                       # perf_counter seconds at the window start
LO = 5_000 * MS                  # the trace's clock at the window start
SCALE = 2.0                      # trace ns a perf_counter ns (a drift)
NAMES = ("host_step_ms", "idle_host_share", "idle_sync_share",
         "prefill_p50_s")


def reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def perf(ms: float) -> int:
    """perf_counter_ns ``ms`` into the window."""
    return round((T0 + ms * 1e-3) * 1e9)


def trace_at(ms: float) -> int:
    return LO + round(ms * MS * SCALE)


def make_run(capacity=64):
    """A 100 ms window.  The device runs [4, 24] and [37, 58] ms; two
    steps and part of a third, admissions between them, and the prefill
    spans of four requests, two of them admitted inside the window."""
    ops = [("fusion.1", trace_at(4), trace_at(24) - trace_at(4)),
           ("fusion.2", trace_at(37), trace_at(58) - trace_at(37))]
    trace = tr.Trace(ops={"/device:TPU:0": ops}, modules={},
                     host=[("bench.window", LO, trace_at(100) - LO)])
    log = SpanLog(capacity)
    for name, a, b, rid in [
            ("sched.prefill", -80, -10, "r4"),      # ends before
            ("sched.feed", 0, 2, None), ("sched.dispatch", 2, 5, None),
            ("sched.sync", 5, 25, None), ("sched.prefill", -60, 20, "r1"),
            ("sched.post", 25, 30, None), ("sched.step", 0, 30, None),
            ("sched.admit", 30, 32, None),
            ("sched.feed", 32, 34, None), ("sched.dispatch", 34, 36, None),
            ("sched.sync", 36, 60, None), ("sched.prefill", 10, 50, "r2"),
            ("sched.post", 60, 70, None), ("sched.step", 32, 70, None),
            ("sched.admit", 70, 72, None),
            ("sched.sync", 92, 104, None),           # a step past the end
            ("sched.step", 90, 105, None),
            ("sched.prefill", 60, 130, "r3"),       # admitted inside
            ("sched.sync", 121, 130, None),          # r3's token 0,
            ("sched.step", 120, 135, None)]:         # after a pause
        log.add(name, perf(a), perf(b), rid)
    run = types.SimpleNamespace(t0=T0, t1=T0 + 0.1, trace=trace)
    return run, log


@pytest.fixture
def patched(monkeypatch):
    def use(log):
        monkeypatch.setattr(obs_trace, "SPANS", log)
    return use


def test_the_window_edges_map_the_clocks():
    run, _ = make_run()
    assert spans.to_trace(run, perf(0)) == pytest.approx(LO)
    assert spans.to_trace(run, perf(100)) == pytest.approx(trace_at(100))
    assert spans.to_trace(run, perf(37)) == pytest.approx(trace_at(37))


def test_overlap_of_interval_lists():
    assert spans.overlap([(0, 4), (24, 37)], [(2, 30), (36, 40)]) == \
        2 + 6 + 1
    assert spans.overlap([], [(0, 1)]) == 0


def test_readers_on_synthetic_spans(patched):
    run, log = make_run()
    patched(log)
    got = {n: reader(n)(run) for n in NAMES}
    # device idle [0,4], [24,37], [58,100]; host spans cover [0,5],
    # [25,36], [60,72]: 4 + 11 + 12 ms of 100
    assert got["idle_host_share"] == pytest.approx(27.0)
    # sync [5,25], [36,60], [92,104] over the idle: 1 + 1 + 2 + 8 ms
    assert got["idle_sync_share"] == pytest.approx(12.0)
    # both shares lie inside the device's idle share, 59 %
    assert got["idle_host_share"] + got["idle_sync_share"] <= 59.0
    # steps wholly inside the window: 30 - 20 and 38 - 24 ms
    assert got["host_step_ms"] == pytest.approx(12.0)
    # admitted inside the window: r2 and r3 (token 0 after the window);
    # not r1 (admitted before, token 0 inside).  Only the time inside a
    # step or an admission counts: r2 40 ms, all of it; r3 37 of 70 ms
    # (not [72, 90] nor [105, 120], where the harness held the loop)
    assert got["prefill_p50_s"] == pytest.approx(0.0385)


def test_readers_give_none_without_a_log_or_past_a_wrap(patched,
                                                        monkeypatch):
    run, log = make_run(capacity=4)      # wrapped past the window start
    assert not log.holds_since(perf(0))
    patched(log)
    assert all(reader(n)(run) is None for n in NAMES)
    run, log = make_run()
    patched(log)
    run.trace = None                     # a run without a trace
    assert reader("idle_host_share")(run) is None
    assert reader("idle_sync_share")(run) is None
    monkeypatch.delattr(obs_trace, "SPANS")    # a program with no log
    assert spans.log() is None
    run, _ = make_run()
    assert all(reader(n)(run) is None for n in NAMES)
