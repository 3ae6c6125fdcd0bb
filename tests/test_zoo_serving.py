"""The model-zoo serving matrix: one scheduler front door, every family.

Every registered architecture serves through
``ContinuousBatchingScheduler`` -- the paged route for families whose
ring caches page (dense/llama/yi/gemma3-window), the state-arena route
for everything else (MoE/MLA, recurrent-state hybrids, xLSTM, whisper
enc-dec, the VLM wrapper) -- under the same contracts:

  * bit-equivalence: every request's tokens are identical to replaying
    it ALONE through ``generate()`` on its placement, greedy and
    sampled, ECC off and on;
  * ONE compiled decode step per scheduler (``decode_traces == 1``);
  * a flat pallas-launch budget (launch count independent of slot
    count, == 1 for the paged route's fused kernel on uniform-full
    layouts);
  * persistent-fault semantics for carried ``state`` leaves
    (corrupt-once-on-write, asserted against the one-shot whole-tree
    injection oracle), placed in a fault-tolerant tier by default;
  * MoE expert weights criticality-tiered by routing frequency;
  * loud errors, not silent fallbacks, for the combinations a route
    cannot serve.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as arena
from repro.core.domains import MemoryDomain
from repro.core.hbm import VCU128
from repro.core.injection import inject_group
from repro.kernels.bitflip.ops import to_u32
from repro.models.base import (cache_layouts, cache_slot_axes, get_arch,
                               list_archs, spec_avals)
from repro.serving import readpath
from repro.serving.engine import ServeConfig, bucketed_prefill, generate
from repro.serving.scheduler import (ContinuousBatchingScheduler, Request,
                                     SelfHealConfig, ShardLayoutError)
from repro.serving.statearena import StateArenaScheduler
from repro.training import trainer
from repro.training.undervolt import (UndervoltPlan, aggressive_plan,
                                      tiered_plan)

ZOO = list_archs()
ALL_PCS = tuple(range(VCU128.num_pcs))
MAX_LEN = 32
V_DEEP = 0.86


@functools.lru_cache(maxsize=None)
def _setup(name):
    bundle = get_arch(name)
    cfg = bundle.reduced
    params = trainer.init_state(bundle, cfg,
                                jax.random.PRNGKey(0))["params"]
    return bundle, cfg, params


def _plan(v, ecc=False):
    return UndervoltPlan(
        domains={"kv": MemoryDomain("kv", v, ALL_PCS, ecc=ecc)},
        policy={"kv_cache": "kv"}, geometry=VCU128)


def _extras(cfg, rng):
    """Unbatched modality inputs for the enc-dec / VLM families."""
    if cfg.family == "audio":
        return {"frames": rng.standard_normal(
            (cfg.enc_len, cfg.d_model)).astype(np.float32)}
    if cfg.family == "vlm":
        return {"patches": rng.standard_normal(
            (cfg.enc_len, cfg.frontend_dim)).astype(np.float32)}
    return None


def _requests(cfg, rng, n=2):
    """Overlapping requests with distinct prompt lengths/lifetimes."""
    out = []
    for i in range(n):
        out.append((f"r{i}", rng.randint(0, cfg.vocab, (4 + 3 * i,)),
                    3 + i, 10 * i + 7, _extras(cfg, np.random.RandomState(3))))
    return out


def _serve(bundle, cfg, params, sc, reqs, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("num_pages", 16)
    kw.setdefault("page_slots", 8)
    sched = ContinuousBatchingScheduler(bundle, cfg, params, sc, **kw)
    for rid, toks, n, seed, extras in reqs:
        sched.submit(Request(rid=rid, tokens=toks, max_new_tokens=n,
                             tier="cheap", key=jax.random.PRNGKey(seed),
                             extras=extras))
    return sched, sched.run()


def _replay(bundle, cfg, params, sc, reqs, res):
    """Each request alone through generate() on its own placement."""
    out = {}
    for rid, toks, n, seed, extras in reqs:
        batch = {"tokens": jnp.asarray(np.asarray(toks)[None])}
        for k, v in (extras or {}).items():
            batch[k] = jnp.asarray(v)[None]
        out[rid] = np.asarray(generate(
            bundle, cfg, params, batch,
            dataclasses.replace(sc, max_new_tokens=n),
            key=jax.random.PRNGKey(seed),
            kv_placement=res[rid].placement))
    return out


# ---------------------------------------------------------------------------
# The matrix: every family x {greedy, sampled} x {ECC off, on}.  The
# state-route families run here, the paged-route families in
# tests/test_zoo_matrix_paged.py: the matrix is most of this module's
# time, and a second module lets a parallel test run take the halves
# on two workers.
# ---------------------------------------------------------------------------

PAGED_ZOO = [n for n in ZOO
             if getattr(get_arch(n).module, "SUPPORTS_PAGED", False)]
STATE_ZOO = [n for n in ZOO if n not in PAGED_ZOO]


@pytest.mark.parametrize("name", STATE_ZOO)
def test_zoo_matrix(name):
    check_zoo_matrix(name)


def check_zoo_matrix(name):
    """Overlapped undervolted serving == solo replay, bit for bit, for
    one registered config, greedy+sampled x ECC on/off, on ONE
    compiled decode step."""
    bundle, cfg, params = _setup(name)
    rng = np.random.RandomState(1)
    reqs = _requests(cfg, rng)
    for temperature, ecc in [(0.0, False), (0.7, False),
                             (0.0, True), (0.7, True)]:
        sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=4,
                         temperature=temperature,
                         undervolt=_plan(V_DEEP, ecc=ecc),
                         kv_injection="write",
                         kv_method="word" if ecc else "bitwise")
        sched, res = _serve(bundle, cfg, params, sc, reqs)
        assert len(sched.traces) == 1, (name, temperature, ecc,
                                        sched.stats)
        refs = _replay(bundle, cfg, params, sc, reqs, res)
        for rid, *_ in reqs:
            np.testing.assert_array_equal(
                refs[rid], res[rid].tokens,
                err_msg=f"{name} temp={temperature} ecc={ecc} {rid}")


@pytest.mark.parametrize("name", ZOO)
def test_zoo_clean_matches_solo(name):
    """Without a plan the scheduler is pure serving mechanics and must
    reproduce plain generate() for every family."""
    bundle, cfg, params = _setup(name)
    rng = np.random.RandomState(2)
    reqs = _requests(cfg, rng)
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=4)
    sched, res = _serve(bundle, cfg, params, sc, reqs)
    assert len(sched.traces) == 1, sched.stats
    refs = _replay(bundle, cfg, params, sc, reqs, res)
    for rid, *_ in reqs:
        np.testing.assert_array_equal(refs[rid], res[rid].tokens,
                                      err_msg=f"{name} {rid}")
    # the undervolted matrix really faults at this depth: at least the
    # deep bitwise cell must disagree with clean serving somewhere
    sc_f = dataclasses.replace(sc, undervolt=_plan(V_DEEP),
                               kv_injection="write",
                               kv_method="bitwise")
    _, res_f = _serve(bundle, cfg, params, sc_f, reqs)
    assert any((res[rid].tokens != res_f[rid].tokens).any()
               for rid, *_ in reqs), (
        f"{name}: deep undervolt produced no observable corruption")


@pytest.mark.parametrize("name", ZOO)
def test_zoo_launch_budget_flat(name):
    """The pallas-launch count of the one decode step is a per-family
    constant: independent of slot provision.  On the paged route's
    fused read path it is == 1 for uniform-full families (the single
    batched paged-attention launch); window families launch once per
    period slot (still flat in slots and pool).  The state route has
    no read path, so it rides write-mode injection."""
    bundle, cfg, params = _setup(name)
    paged = bool(getattr(bundle.module, "SUPPORTS_PAGED", False))
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=4,
                     undervolt=_plan(V_DEEP),
                     kv_injection="read" if paged else "write",
                     kv_method="bitwise")
    counts = {}
    for slots in (2, 4):
        s = ContinuousBatchingScheduler(
            bundle, cfg, params, sc, num_slots=slots,
            num_pages=8 * slots, page_slots=8)
        jaxpr = jax.make_jaxpr(s._step_fn)(params, s.state,
                                           s._volt_vec())
        counts[slots] = arena.count_pallas_calls(jaxpr.jaxpr)
    assert counts[2] == counts[4], (name, counts)
    if not isinstance(s, StateArenaScheduler) and \
            set(s.layout_kinds) == {"full"}:
        assert counts[2] == 1, (name, counts)


def test_zoo_routes():
    """__new__ dispatch: families with SUPPORTS_PAGED page, everything
    else rides the state arena -- through the same constructor."""
    for name in ZOO:
        bundle, cfg, params = _setup(name)
        sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=2)
        s = ContinuousBatchingScheduler(bundle, cfg, params, sc,
                                        num_slots=2, num_pages=8,
                                        page_slots=8)
        paged = bool(getattr(bundle.module, "SUPPORTS_PAGED", False))
        assert isinstance(s, StateArenaScheduler) == (not paged), name
        assert isinstance(s, ContinuousBatchingScheduler), name
        if not paged:
            assert s.stats["route"] == "state", name
            assert set(s.stats["cache_layouts"]) <= {
                "full", "window", "cross", "state"}, name


# ---------------------------------------------------------------------------
# Window-cache prefill soundness (the engine.py bucketing hole)
# ---------------------------------------------------------------------------


def test_window_prefill_exact_fallback():
    """gemma3's window rings must NOT ride the pow2-padded prefill
    (padding rewrites rotated-out rows): the bucketed entry routes
    every prompt length to the exact per-shape prefill, bit-identical
    to module.prefill, and never traces the padded path."""
    bundle, cfg, params = _setup("gemma3-4b")
    bp = bucketed_prefill(bundle.module, cfg, MAX_LEN)
    assert bp is not None and bp.uniform is False
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, cfg.vocab, (1, 11)))
    logits, cache = bp(params, {"tokens": toks})
    ref_logits, ref_cache = jax.jit(
        lambda p, bt: bundle.module.prefill(p, bt, cfg, MAX_LEN))(
            params, {"tokens": toks})
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(ref_logits))
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(ref_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not bp.traces, "padded prefill traced on a window family"
    # uniform full-length rings still bucket
    bundle_l, cfg_l, _ = _setup("llama3.2-3b")
    assert bucketed_prefill(bundle_l.module, cfg_l, MAX_LEN).uniform


# ---------------------------------------------------------------------------
# Persistent-fault semantics for carried state
# ---------------------------------------------------------------------------


def _random_cache(avals, key):
    flat, treedef = jax.tree_util.tree_flatten(avals)
    keys = jax.random.split(key, len(flat))
    leaves = []
    for a, k in zip(flat, keys):
        if jnp.issubdtype(a.dtype, jnp.floating):
            leaves.append(jax.random.normal(k, a.shape,
                                            jnp.float32).astype(a.dtype))
        else:
            leaves.append(jnp.zeros(a.shape, a.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _assert_bits_equal(x, y, path):
    """Bit-exact leaf equality via the injection engine's u32 view
    (NaN-safe for ml_dtypes bf16, which numpy's comparison is not)."""
    np.testing.assert_array_equal(np.asarray(to_u32(x)[0]),
                                  np.asarray(to_u32(y)[0]), err_msg=path)


def test_persistent_fault_oracle():
    """The write-path step injection corrupts carried ``state`` leaves
    WHOLE (== the one-shot whole-tree oracle), deterministically and
    idempotently -- so state rewritten every decode step re-acquires
    the same stuck-at faults: corrupt-once-on-write, persistent across
    the scan.  Ring leaves stay incremental (only the written row)."""
    bundle, cfg, _ = _setup("recurrentgemma-9b")
    module = bundle.module
    specs = module.cache_specs(cfg, 1, MAX_LEN)
    avals = spec_avals(specs)
    slot_axes = cache_slot_axes(specs)
    plan = _plan(0.84)
    fmap = plan.fault_map()
    placement = plan.place({"kv_cache": avals})["kv_cache"]
    state_paths = set(readpath.state_leaf_paths(specs, MAX_LEN))
    assert state_paths, "recurrentgemma must carry state leaves"
    v = jnp.float32(0.84)

    tree = _random_cache(avals, jax.random.PRNGKey(5))
    step, _ = arena.inject_placement_slice(
        tree, placement, fmap, slot_axes=slot_axes, pos=jnp.int32(3),
        voltage=v, method="bitwise")
    oracle, _ = inject_group(tree, placement, fmap, voltage=v,
                             method="bitwise")
    again, _ = arena.inject_placement_slice(
        step, placement, fmap, slot_axes=slot_axes, pos=jnp.int32(4),
        voltage=v, method="bitwise")

    flat_t = jax.tree_util.tree_flatten_with_path(tree)[0]
    flat_s = jax.tree_util.tree_leaves(step)
    flat_o = jax.tree_util.tree_leaves(oracle)
    flat_a = jax.tree_util.tree_leaves(again)
    axes = jax.tree_util.tree_leaves(slot_axes)
    corrupted_state = 0
    for (p, t), s, o, a, ax in zip(flat_t, flat_s, flat_o, flat_a,
                                   axes):
        path = jax.tree_util.keystr(p)
        if path in state_paths:
            # whole-leaf == the one-shot oracle; re-injecting the
            # already-corrupt value is a no-op (stuck-at idempotence).
            # Compare raw bits: corrupted bf16 values include NaNs, and
            # numpy's NaN-aware equality doesn't cover ml_dtypes.
            _assert_bits_equal(s, o, path)
            _assert_bits_equal(a, s, path)
            corrupted_state += int(np.any(np.asarray(to_u32(s)[0])
                                          != np.asarray(to_u32(t)[0])))
            continue
        t, s = np.asarray(t), np.asarray(s)
        if ax >= 0 and np.issubdtype(t.dtype, np.floating):
            # ring leaf: rows other than the written slot untouched
            other = [i for i in range(t.shape[ax]) if i != 3]
            np.testing.assert_array_equal(
                np.take(s, other, axis=ax),
                np.take(t, other, axis=ax), err_msg=path)
    assert corrupted_state >= 1, (
        "no carried-state leaf faulted at 0.84 V (oracle vacuous)")


def test_state_tier_default_fault_tolerant():
    """On a tiered plan the per-slot caches land on the ``cheap``
    (fault-tolerant) tier by default, and requests still replay
    bit-exactly on their placement."""
    bundle, cfg, params = _setup("xlstm-350m")
    plan = tiered_plan(v_unsafe=V_DEEP, geometry=VCU128)
    assert plan.tiers is not None
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=3, undervolt=plan,
                     kv_injection="write", kv_method="bitwise")
    rng = np.random.RandomState(4)
    reqs = _requests(cfg, rng, n=1)
    sched, res = _serve(bundle, cfg, params, sc, reqs)
    assert isinstance(sched, StateArenaScheduler)
    assert sched.state_tier == "cheap"
    assert all(p is not None for p in sched.placements)
    refs = _replay(bundle, cfg, params, sc, reqs, res)
    np.testing.assert_array_equal(refs["r0"], res["r0"].tokens)


# ---------------------------------------------------------------------------
# MoE expert criticality tiering
# ---------------------------------------------------------------------------


def test_moe_expert_tiering():
    """Routing-frequency-driven expert placement: hot quarter 'safe',
    cold quarter 'disposable', rest 'cheap'; weights in unsafe domains
    corrupt ONCE at construction; serving replays bit-exactly on
    sched.params while the corruption is observable vs clean params."""
    bundle, cfg, params = _setup("deepseek-v2-lite-16b")
    plan = aggressive_plan(v_unsafe=V_DEEP)
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=4, undervolt=plan,
                     kv_injection="write", kv_method="bitwise")
    rng = np.random.RandomState(5)
    probe = rng.randint(0, cfg.vocab, (24,))
    reqs = _requests(cfg, rng, n=1)
    sched, res = _serve(bundle, cfg, params, sc, reqs,
                        expert_probe=probe)
    tiers = sched.stats["expert_tiers"]
    q = max(cfg.n_experts // 4, 1)
    assert tiers.get("safe", 0) == q and tiers.get("disposable", 0) == q
    assert sum(tiers.values()) == cfg.n_experts
    refs = _replay(bundle, cfg, sched.params, sc, reqs, res)
    np.testing.assert_array_equal(refs["r0"], res["r0"].tokens)
    clean = _replay(bundle, cfg, params,
                    dataclasses.replace(sc, undervolt=None), reqs,
                    {"r0": dataclasses.replace(res["r0"],
                                               placement=None)})
    assert (clean["r0"] != res["r0"].tokens).any(), (
        "expert corruption not observable in tokens")


def test_expert_probe_rejected_off_moe():
    bundle, cfg, params = _setup("xlstm-350m")
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=2,
                     undervolt=aggressive_plan(v_unsafe=V_DEEP),
                     kv_injection="write", kv_method="bitwise")
    with pytest.raises(ValueError, match="MoE-only"):
        ContinuousBatchingScheduler(bundle, cfg, params, sc,
                                    num_slots=1,
                                    expert_probe=np.arange(8))


# ---------------------------------------------------------------------------
# Whisper encoder sharing (content-addressed prefill reuse)
# ---------------------------------------------------------------------------


def test_whisper_prefill_reuse():
    """share_prefix on the state route: identical (tokens, frames)
    admissions reuse the prefill result -- the encoder runs once --
    with identical tokens out and pages_shared flagging the reuse."""
    bundle, cfg, params = _setup("whisper-large-v3")
    rng = np.random.RandomState(6)
    toks = rng.randint(0, cfg.vocab, (5,))
    frames = _extras(cfg, rng)
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=3,
                     share_prefix=True)
    sched = ContinuousBatchingScheduler(bundle, cfg, params, sc,
                                        num_slots=3)
    for i in range(3):
        sched.submit(Request(rid=i, tokens=toks, max_new_tokens=3,
                             key=jax.random.PRNGKey(9), extras=frames))
    res = sched.run()
    assert sched.prefill_reuse == 2, sched.stats
    assert [res[i].pages_shared for i in range(3)] == [0, 1, 1]
    for i in (1, 2):
        np.testing.assert_array_equal(res[0].tokens, res[i].tokens)


# ---------------------------------------------------------------------------
# Route boundaries: loud errors, not silent fallbacks
# ---------------------------------------------------------------------------


def test_paged_route_rejects_extras():
    bundle, cfg, params = _setup("llama3.2-3b")
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=2)
    s = ContinuousBatchingScheduler(bundle, cfg, params, sc,
                                    num_slots=2, num_pages=8,
                                    page_slots=8)
    with pytest.raises(ValueError, match="extras"):
        s.submit(Request(rid="x", tokens=np.arange(4),
                         max_new_tokens=2,
                         extras={"frames": np.zeros((2, 4))}))


def test_state_route_rejections():
    bundle, cfg, params = _setup("recurrentgemma-9b")
    plan = _plan(V_DEEP)

    def build(sc, **kw):
        return ContinuousBatchingScheduler(bundle, cfg, params, sc,
                                           num_slots=2, **kw)

    base = ServeConfig(max_len=MAX_LEN, max_new_tokens=2,
                       undervolt=plan, kv_injection="write",
                       kv_method="bitwise")
    with pytest.raises(ShardLayoutError, match="single-shard"):
        from repro.launch.mesh import make_serve_mesh
        build(base, mesh=make_serve_mesh(1))
    with pytest.raises(ValueError, match="page pool"):
        build(base, self_heal=SelfHealConfig())
    with pytest.raises(ValueError, match="governor"):
        build(dataclasses.replace(
            base, governor=plan.make_governor(
                "kv", mode="rate", tolerable_rate=1e-3, v_lo=0.85)))
    with pytest.raises(ValueError, match="read-path"):
        build(dataclasses.replace(base, kv_injection="read"))
    with pytest.raises(ValueError, match="rewrite"):
        build(dataclasses.replace(base, kv_injection="rewrite"))


def test_state_route_records_step_and_request_spans():
    """The state-arena route times its steps with the paged route's
    spans, less ``sched.feed`` (prompts prefill at admission), and
    records each request's prefill span beside its public record."""
    import time
    from repro.obs.trace import SPANS
    bundle, cfg, params = _setup("xlstm-350m")
    sc = ServeConfig(max_len=MAX_LEN, max_new_tokens=2)
    t0 = time.perf_counter_ns()
    sched, res = _serve(bundle, cfg, params, sc,
                        _requests(cfg, np.random.RandomState(0)))
    assert isinstance(sched, StateArenaScheduler)
    own = SPANS.select(owner=sched.metrics.owner, lo_ns=t0)
    steps = [x for x in own if x.name == "sched.step"]
    phases = [x for x in own
              if x.name in ("sched.dispatch", "sched.sync", "sched.post")]
    assert len(steps) == sched.steps and len(phases) == 3 * sched.steps
    for i, st in enumerate(steps):
        kids = phases[3 * i:3 * i + 3]
        assert [k.name for k in kids] == ["sched.dispatch", "sched.sync",
                                          "sched.post"]
        assert st.start_ns <= kids[0].start_ns and \
            kids[-1].end_ns <= st.end_ns
    assert sched.stats["obs"]["step_latency"]["count"] == sched.steps
    for rid, r in res.items():
        p, = (x for x in own if x.name == "sched.prefill" and x.rid == rid)
        assert r.t_submit <= r.t_admit <= r.t_tokens[0]
        assert p.start_ns * 1e-9 == pytest.approx(r.t_admit, abs=2e-9)
        assert r.t_tokens[0] == p.end_ns * 1e-9
        assert len(r.t_tokens) == r.tokens.shape[1]
        assert r.slot in range(2)
