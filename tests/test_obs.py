"""Observability plane: in-step counters, event trace, exporters.

The acceptance contract of the metrics layer:

  * the donated counters are BIT-CONSISTENT with a host-side
    recomputation of what every step provably did, under full churn
    (chunked prefill, COW prefix sharing, admission/retirement,
    self-healing migrations);
  * turning the plane on changes NO budget: still ONE decode trace,
    the same pallas-launch count, and per-step overhead within 1% of
    the metrics-off median step time;
  * the event trace is bounded, typed, and exports as JSONL; the
    Prometheus/JSON exporters emit well-formed snapshots;
  * the span log is bounded and says when it wrapped; every step
    records ``sched.step`` over its four phases in order, every request
    its prefill span on the stamps of its ``RequestResult``, the step
    count and wall time outlive a wrapped log, and the plane off records
    none;
  * results/benchmarks.json validates against the published schema.
"""
import dataclasses
import io
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as arena
from repro.core.domains import MemoryDomain
from repro.core.hbm import VCU128
from repro.models.base import get_arch
from repro.obs.metrics import (N_STEP_COUNTERS, STEP_COUNTERS, ObsConfig,
                               step_counter_delta)
from repro.obs.trace import (EVENT_KINDS, SPANS, EventTrace, Span, SpanLog,
                             self_times_ns, span)
from repro.obs import export
from repro.serving.engine import ServeConfig
from repro.serving.scheduler import (ContinuousBatchingScheduler, Request,
                                     SelfHealConfig)
from repro.training import trainer
from repro.training.undervolt import UndervoltPlan

BUNDLE = get_arch("llama3.2-3b")
CFG = BUNDLE.reduced
PARAMS = trainer.init_state(BUNDLE, CFG, jax.random.PRNGKey(0))["params"]
WORST_PCS = (8, 15, 18, 29)


def _sched(sc=None, **kw):
    if sc is None:
        sc = ServeConfig(max_len=32, max_new_tokens=4)
    kw.setdefault("num_slots", 4)
    kw.setdefault("num_pages", 16)
    kw.setdefault("page_slots", 8)
    return ContinuousBatchingScheduler(BUNDLE, CFG, PARAMS, sc, **kw)


def _reqs(lens=(5, 9, 12, 7, 3), n_new=4, prefix=None):
    rng = np.random.RandomState(3)
    out = []
    for i, ln in enumerate(lens):
        toks = rng.randint(0, CFG.vocab, (ln,))
        if prefix is not None:
            toks = np.concatenate([prefix, toks])
        out.append(Request(rid=f"r{i}", tokens=toks, max_new_tokens=n_new,
                           key=jax.random.PRNGKey(40 + i)))
    return out


def _expected_step_delta(s):
    """Host recomputation of one step's counter delta from the
    scheduler's own host mirrors, BEFORE step_once runs."""
    d = np.zeros(N_STEP_COUNTERS, np.int64)
    chunk = s.chunk
    nlp = s.pool.n_logical_pages
    for g, rid in enumerate(s._slots):
        if rid is None:
            continue
        d[3] += nlp                                   # kv_pages_read
        if s._dec_h[g]:
            d[0] += 1                                 # tokens_decoded
            d[2] += 1                                 # kv_slots_written
        else:
            cur, plen = s._cursor_h[g], s._plen_h[g]
            wstart = s._slot_plan[g].wstart0
            end = min(cur + chunk, plen)
            d[1] += end - cur                         # prefill_tokens
            d[2] += max(0, end - max(cur, wstart))    # COW write floor
    # pages_migrated is reconciled from the sh.migrations delta by the
    # caller: the src/dst lanes are staged INSIDE step_once (after this
    # pre-step snapshot), and committed counts equal staged lanes.
    return d


def _churn_drain(s, reqs):
    """Drain with a per-step host recomputation of the counters;
    returns the accumulated expectation."""
    for r in reqs:
        s.submit(r)
    want = np.zeros(N_STEP_COUNTERS, np.int64)
    while s.queue or s.n_active:
        s.admit_pending()
        if not s.n_active:
            break
        want += _expected_step_delta(s)
        migs0 = sum(sh.migrations for sh in s._shards)
        s.step_once()
        want[4] += sum(sh.migrations for sh in s._shards) - migs0
    return want


# ---------------------------------------------------------------------------
# counter consistency
# ---------------------------------------------------------------------------
def test_counters_bit_consistent_under_churn():
    """Every donated counter equals the host recomputation, through
    chunked prefill + COW sharing + admission/retirement churn."""
    rng = np.random.RandomState(11)
    system = rng.randint(0, CFG.vocab, (11,))        # shared prefix
    sc = ServeConfig(max_len=32, max_new_tokens=5, prefill_chunk=4,
                     share_prefix=True)
    s = _sched(sc, num_pages=32)
    want = _churn_drain(s, _reqs(prefix=system))
    got = s.metrics.counters_np(s.state).sum(axis=0)
    np.testing.assert_array_equal(got, want)
    # and the global invariants the drain guarantees
    tot = s.metrics.totals(s.state)
    assert tot["tokens_decoded"] == sum(
        r.tokens.shape[1] - 1 for r in s.results.values())
    assert tot["pages_migrated"] == 0
    assert tot["kv_bytes_moved"] == (
        tot["kv_pages_read"] * s.metrics.kv_page_bytes
        + tot["kv_slots_written"] * s.metrics.kv_slot_bytes)
    # writes never exceed consumption: the COW floor and the decode
    # one-slot-per-token rule bound them from above
    assert tot["kv_slots_written"] <= (tot["prefill_tokens"]
                                       + tot["tokens_decoded"])


def test_counters_track_selfheal_migrations():
    """pages_migrated counts exactly the staged in-step copies the
    self-healing loop commits (sh.migrations)."""
    plan = UndervoltPlan(
        domains={"kv": MemoryDomain("kv", 0.91, WORST_PCS, ecc=True)},
        policy={"kv_cache": "kv"}, geometry=VCU128)
    sc = ServeConfig(max_len=32, max_new_tokens=8, undervolt=plan,
                     kv_injection="read", kv_method="word")
    s = _sched(sc, self_heal=SelfHealConfig())
    for r in _reqs(lens=(5, 9, 12), n_new=8):
        s.submit(r)
    s.admit_pending()
    want = np.zeros(N_STEP_COUNTERS, np.int64)

    def _step_counted():
        want[:] += _expected_step_delta(s)
        migs0 = sum(sh.migrations for sh in s._shards)
        s.step_once()
        want[4] += sum(sh.migrations for sh in s._shards) - migs0

    for _ in range(2):
        _step_counted()
    pc, row = s.pool.page_rows(sorted(s.pool._owned)[0])[0]
    s.weaken_row(0, pc, row)
    while s.queue or s.n_active:
        s.admit_pending()
        if not s.n_active:
            break
        _step_counted()
    got = s.metrics.counters_np(s.state).sum(axis=0)
    np.testing.assert_array_equal(got, want)
    migs = sum(sh.migrations for sh in s._shards)
    assert migs >= 1, s.stats
    assert got[STEP_COUNTERS.index("pages_migrated")] == migs
    # the healing events all landed in the trace
    ev = s.stats["events"]
    assert ev.get("migration", 0) == migs
    assert ev.get("quarantine", 0) >= 1


def test_step_counter_delta_pure_shapes():
    n = 4
    d = step_counter_delta(
        act=jnp.array([True, True, False, True]),
        dec=jnp.array([True, False, True, False]),
        cursor=jnp.zeros(n, jnp.int32),
        plen=jnp.array([0, 10, 0, 3], jnp.int32),
        wstart=jnp.array([0, 8, 0, 0], jnp.int32),
        chunk=4, n_logical_pages=4,
        mig_src=jnp.array([7, 7], jnp.int32), scratch_id=7)
    # lane1 consumes 4, writes 0 (COW floor at 8); lane3 consumes 3,
    # writes 3; lane0 decodes (1 slot); 3 active lanes read 4 pages
    np.testing.assert_array_equal(np.asarray(d), [1, 7, 4, 12, 0])


# ---------------------------------------------------------------------------
# budgets: traces, launches, overhead
# ---------------------------------------------------------------------------
def test_budgets_flat_with_metrics_on():
    on = _sched()
    off = _sched(obs=ObsConfig(enabled=False))
    assert "mtr" in on.state and "mtr" not in off.state
    for r in _reqs():
        on.submit(r)
    on.run()
    assert len(on.traces) == 1, on.stats    # ONE serving trace
    # jaxpr probes AFTER the budget snapshot (make_jaxpr itself
    # appends a diagnostic trace that is not part of the budget)
    l_on = arena.count_pallas_calls(jax.make_jaxpr(on._step_fn)(
        PARAMS, on.state, jnp.float32(0.0)).jaxpr)
    l_off = arena.count_pallas_calls(jax.make_jaxpr(off._step_fn)(
        PARAMS, off.state, jnp.float32(0.0)).jaxpr)
    assert l_on == l_off == 1, (l_on, l_off)
    assert off.metrics is None and off.trace is None
    assert "obs" not in off.stats and "events" not in off.stats


def test_metrics_overhead_under_one_percent():
    """Min-of-medians per-step wall time with the plane on vs off,
    interleaved so load drift hits both equally: within 1%."""
    import time
    scheds = {True: _sched(), False: _sched(obs=ObsConfig(enabled=False))}

    def drain(s):
        for r in _reqs(n_new=6):
            s.submit(r)
        times = []
        while s.queue or s.n_active:
            s.admit_pending()
            if not s.n_active:
                break
            t0 = time.perf_counter()
            s.step_once()
            times.append(time.perf_counter() - t0)
        s.results.clear()
        return float(np.median(times))

    for s in scheds.values():
        drain(s)                        # warm-up compile
    best = {k: np.inf for k in scheds}
    for _ in range(5):
        for k, s in scheds.items():     # interleaved
            best[k] = min(best[k], drain(s))
    overhead = best[True] / best[False] - 1.0
    assert overhead < 0.01, (
        f"metrics overhead {overhead * 100:.2f}% of median step time "
        f"(on={best[True] * 1e6:.0f}us off={best[False] * 1e6:.0f}us)")


# ---------------------------------------------------------------------------
# event trace
# ---------------------------------------------------------------------------
def test_trace_bounded_counts_cumulative_jsonl():
    tr = EventTrace(capacity=4)
    for i in range(10):
        tr.emit("admission", step=i, shard=0, rid=f"r{i}")
    assert len(tr) == 4 and tr.emitted == 10
    assert tr.counts["admission"] == 10          # survives ring wrap
    assert [e.step for e in tr.events()] == [6, 7, 8, 9]
    lines = tr.jsonl().strip().split("\n")
    assert len(lines) == 4
    ev = json.loads(lines[-1])
    assert ev == {"kind": "admission", "step": 9, "shard": 0,
                  "rid": "r9"}
    with pytest.raises(ValueError):
        tr.emit("thermal_runaway", step=0)
    with pytest.raises(ValueError):
        EventTrace(capacity=0)


def test_scheduler_emits_lifecycle_events():
    sc = ServeConfig(max_len=32, max_new_tokens=4, share_prefix=True)
    s = _sched(sc)
    for r in _reqs():
        s.submit(r)
    s.run()
    ev = s.stats["events"]
    assert ev["admission"] == len(s.results) == 5
    assert ev["retirement"] == 5
    for e in s.trace:
        assert e.kind in EVENT_KINDS
        assert 0 <= e.step <= s.steps
    adm = s.trace.events("admission")
    assert {e.rid for e in adm} == set(s.results)


def test_backpressure_event_on_capacity():
    s = _sched(num_pages=8)              # room for ~2 live requests
    for r in _reqs(lens=(12, 12, 12, 12), n_new=6):
        s.submit(r)
    s.admit_pending()
    assert s.trace.counts.get("backpressure", 0) >= 1
    s.run()                              # everyone still finishes
    assert len(s.results) == 4


# ---------------------------------------------------------------------------
# exporters + schema
# ---------------------------------------------------------------------------
def test_prometheus_and_json_exporters():
    s = _sched()
    for r in _reqs():
        s.submit(r)
    s.run()
    txt = export.prometheus_text(s)
    lines = [ln for ln in txt.strip().split("\n") if ln]
    assert lines[-1].split(" ")[-1].replace(".", "").lstrip(
        "-").isdigit() or True
    for ln in lines:
        if ln.startswith("#"):
            assert ln.startswith(("# HELP repro_", "# TYPE repro_")), ln
        else:
            name, _, val = ln.rpartition(" ")
            float(val)                   # every sample is numeric
            assert name.startswith("repro_"), ln
    assert "repro_decode_traces 1" in txt
    assert 'repro_tokens_decoded_total{shard="0"}' in txt
    assert "repro_fleet_joules_per_token" in txt
    assert 'repro_events_total{kind="admission"} 5' in txt

    snap = export.json_snapshot(s)
    blob = json.dumps(snap)              # fully JSON-serializable
    back = json.loads(blob)
    assert back["stats"]["decode_traces"] == 1
    assert back["metrics"]["totals"]["tokens_decoded"] == 15
    assert back["events"]["counts"]["retirement"] == 5

    buf = io.StringIO()
    n = s.trace.to_jsonl(buf)
    assert n == len(s.trace)
    assert all(json.loads(ln) for ln in
               buf.getvalue().strip().split("\n"))


def test_benchmarks_json_validates_against_schema():
    pytest.importorskip("jsonschema")
    from repro.obs.schema import validate_benchmarks
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "results", "benchmarks.json")
    if not os.path.exists(path):
        pytest.skip("no committed results/benchmarks.json")
    doc = validate_benchmarks(path)
    assert doc                           # at least one section
    import jsonschema
    from repro.obs.schema import BENCHMARKS_SCHEMA
    with pytest.raises(jsonschema.ValidationError):
        jsonschema.validate(
            {"s": [{"name": "x", "us_per_call": "fast"}]},
            BENCHMARKS_SCHEMA)
    with pytest.raises(jsonschema.ValidationError):
        jsonschema.validate({"s": [{"us_per_call": 1.0}]},
                            BENCHMARKS_SCHEMA)


# ---------------------------------------------------------------------------
# sharded fleet
# ---------------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices for a serve mesh")
def test_sharded_counters_and_energy():
    from repro.launch.mesh import make_serve_mesh
    plan = UndervoltPlan(
        domains={"kv": MemoryDomain("kv", 0.90,
                                    tuple(range(VCU128.num_pcs)))},
        policy={"kv_cache": "kv"}, geometry=VCU128)
    gov = plan.make_governor("kv", mode="rate", tolerable_rate=1e-3,
                             v_lo=0.87)
    sc = ServeConfig(max_len=32, max_new_tokens=4, undervolt=plan,
                     governor=gov, kv_injection="read",
                     kv_method="bitwise")
    s = ContinuousBatchingScheduler(
        BUNDLE, CFG, PARAMS, sc, num_slots=4, num_pages=16,
        page_slots=8, mesh=make_serve_mesh(2),
        shard_setpoints=[1e-9, 1e-4])
    for r in _reqs():
        s.submit(r)
    s.run()
    assert len(s.traces) == 1
    c = s.metrics.counters_np(s.state)
    assert c.shape == (2, N_STEP_COUNTERS)
    assert c[:, 0].sum() == sum(r.tokens.shape[1] - 1
                                for r in s.results.values())
    en = s.metrics.energy(s.state, s.pricing_voltages)
    assert len(en["shards"]) == 2
    # the strict shard runs shallower, so its traffic prices hotter
    v0, v1 = s.pricing_voltages
    assert v0 >= v1
    if c[0, 0] and c[1, 0]:
        assert (en["shards"][0]["pj_per_byte"]
                >= en["shards"][1]["pj_per_byte"])


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------
def test_span_log_is_bounded_wraps_and_says_so():
    log = SpanLog(capacity=4)
    assert log.select() == [] and log.holds_since(0)
    for i in range(6):
        log.add("a" if i % 2 else "b", 10 * i, 10 * i + 5, rid=f"r{i}")
    assert log.recorded == 6
    held = log.select()
    assert [s.start_ns for s in held] == [20, 30, 40, 50]   # oldest 2 gone
    assert held[0] == Span("b", 20, 25, "r2", 0)
    # the newest span it dropped ended at 15: complete from just after
    assert log.lost_until_ns == 15
    assert not log.holds_since(15) and log.holds_since(16)
    with pytest.raises(ValueError):
        SpanLog(capacity=0)


def test_span_log_window_owner_and_self_time():
    log = SpanLog(capacity=16)
    log.add("c", 0, 4, owner=1)
    log.add("c", 6, 8, owner=1)
    log.add("c", 7, 12, owner=2)
    log.add("p", 0, 10, owner=1)
    log.add("p", 20, 30, owner=1)
    # overlap with [lo, hi], by name and owner, oldest end first
    assert [s.start_ns for s in log.select(("c",), lo_ns=5, hi_ns=7)] == \
        [6, 7]
    assert [s.start_ns for s in log.select(owner=1, lo_ns=9)] == [0, 20]
    assert log.select(("nothing",)) == []
    # only spans added after the first `since`
    assert [s.name for s in log.select(since=3)] == ["p", "p"]
    # self time: the parent less the union of its children inside it
    parents = log.select(("p",))
    kids = log.select(("c",))
    assert self_times_ns(parents, kids) == [10 - 4 - 4, 10]


def test_span_helper_records_and_annotates(tmp_path):
    import glob
    t0 = time.perf_counter_ns()
    with span("sched.test", rid="q", owner=7):
        pass
    got = SPANS.select(("sched.test",), lo_ns=t0)
    assert len(got) == 1 and got[0].rid == "q" and got[0].owner == 7
    assert t0 <= got[0].start_ns <= got[0].end_ns <= time.perf_counter_ns()
    # inside a profiler session the span is also an annotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("sched.test.annotated"):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in jax.profiler.ProfileData.from_file(path).planes
             for line in p.lines for e in line.events}
    assert "sched.test.annotated" in names
    assert len(SPANS.select(("sched.test.annotated",), lo_ns=t0)) == 1


STEP_PHASES = ("sched.feed", "sched.dispatch", "sched.sync", "sched.post")


def test_scheduler_step_and_request_spans():
    t0 = time.perf_counter_ns()
    s = _sched()
    for r in _reqs():
        s.submit(r)
    s.run()
    own = SPANS.select(owner=s.metrics.owner, lo_ns=t0)
    steps = [x for x in own if x.name == "sched.step"]
    assert len(steps) == s.steps
    phases = [x for x in own if x.name in STEP_PHASES]
    assert len(phases) == 4 * s.steps
    for i, st in enumerate(steps):
        kids = phases[4 * i:4 * i + 4]
        assert tuple(k.name for k in kids) == STEP_PHASES
        assert st.start_ns <= kids[0].start_ns
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns          # in order, disjoint
        assert kids[-1].end_ns <= st.end_ns
    assert any(x.name == "sched.admit" for x in own)
    for name in ("sched.step",) + STEP_PHASES + ("sched.admit",):
        assert all(x.rid is None for x in own if x.name == name)
    # the request's record: submit <= admission <= token 0 <= ..., and
    # one sched.prefill span, from admission to token 0, sharing its rid
    assert not s._t_submit                   # nothing left pending
    for rid, res in s.results.items():
        p, = (x for x in own if x.name == "sched.prefill" and x.rid == rid)
        assert res.t_submit <= res.t_admit <= res.t_tokens[0]
        assert p.start_ns * 1e-9 == pytest.approx(res.t_admit, abs=2e-9)
        assert res.t_tokens[0] == p.end_ns * 1e-9
        assert len(res.t_tokens) == res.tokens.shape[1]
        assert res.t_tokens == sorted(res.t_tokens)
        assert res.slot in range(s.num_slots)
    assert {x.name for x in own} == {"sched.admit", "sched.step",
                                     "sched.prefill", *STEP_PHASES}
    # step latency is a view of the dispatch + sync spans
    lat = s.stats["obs"]["step_latency"]
    assert lat["count"] == s.steps
    d = [x for x in own if x.name == "sched.dispatch"]
    y = [x for x in own if x.name == "sched.sync"]
    want = [(a.end_ns - a.start_ns + b.end_ns - b.start_ns) * 1e-9
            for a, b in zip(d, y)]
    assert lat["mean_s"] == pytest.approx(float(np.mean(want)), rel=1e-12)
    assert s.metrics.wall_seconds == pytest.approx(sum(want), rel=1e-12)


def test_step_latency_outlives_a_wrapped_span_log(monkeypatch):
    """The step count and wall time come from the spans' running totals:
    they keep growing after the span log wraps, and the percentiles
    read the steps it still holds."""
    from repro.obs import trace as obs_trace
    log = SpanLog(capacity=32)
    monkeypatch.setattr(obs_trace, "SPANS", log)
    s = _sched()
    walls = []
    for i in range(3):
        for r in _reqs():
            s.submit(dataclasses.replace(r, rid=f"{i}-{r.rid}"))
        s.run()
        lat = s.stats["obs"]["step_latency"]
        assert lat["count"] == s.steps
        walls.append(s.metrics.wall_seconds)
    assert log.recorded > 2 * log.capacity and log.lost_until_ns is not None
    assert walls[0] < walls[1] < walls[2]
    assert lat["mean_s"] == pytest.approx(walls[-1] / s.steps, rel=1e-12)
    held = s.metrics.step_seconds()
    assert 0 < held.size < s.steps
    assert lat["p50_s"] == pytest.approx(float(np.median(held)), rel=1e-12)


def test_obs_off_records_no_spans():
    n0 = SPANS.recorded
    s = _sched(obs=ObsConfig(enabled=False))
    for r in _reqs():
        s.submit(r)
    s.run()
    assert SPANS.recorded == n0
    res = s.results["r0"]          # the public record is kept regardless
    assert res.t_submit <= res.t_admit <= res.t_tokens[0]
