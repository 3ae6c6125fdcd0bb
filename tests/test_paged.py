"""Paged KV-cache primitives.

Three contracts back the continuous-batching scheduler:

  * the batched *paged* decode-attention kernel is bit-identical to the
    PR 3 contiguous read-path kernel on the same operands -- injection
    on/off x ECC on/off x constant/traced voltage -- because both share
    one flash tile body and one mask math addressed by physical word
    ids;
  * per-page physical tables are pure refinements of the arena block
    tables (a page never straddles a block), and the same candidate-
    select addressing resolves them at page granularity;
  * the page pool routes criticality tiers (weak pages to tolerant
    requests first, weak-avoiding tiers never see weak pages), recycles
    freed pages deterministically, and turns exhaustion into a typed
    CapacityError.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.domains import CapacityError, MemoryDomain, place_groups
from repro.core.faultmap import FaultMap
from repro.core.hbm import VCU128, HBMGeometry
from repro.kernels.flash_attention import faulty
from repro.models.base import get_arch
from repro.serving.paged import PagedLayoutError, PagePool
from repro.training.undervolt import UndervoltPlan

TINY = HBMGeometry(name="tiny", num_stacks=2, channels_per_stack=2,
                   pcs_per_channel=2, bytes_per_pc=64 * 1024)
FMAP = FaultMap.from_seed(TINY, seed=7)

B, L, KH, G, D = 2, 32, 2, 3, 8
H = KH * G
PS = 8                                  # page_slots
N_LP = L // PS


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x.reshape(-1),
        {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]))


def _operands(seed, v, ecc, *, method, b=B, l=L, kh=KH, g=G, d=D,
              fmap=FMAP):
    """Contiguous cache + both granularities of physical tables."""
    rng = np.random.RandomState(seed)
    tree = {"k": jnp.asarray(rng.randn(b, l, kh, d), jnp.bfloat16),
            "v": jnp.asarray(rng.randn(b, l, kh, d), jnp.bfloat16)}
    domains = {"d": MemoryDomain("d", v, tuple(range(6)), ecc=ecc)}
    placement = place_groups({"g": tree}, {"g": "d"}, domains,
                             fmap.geometry)["g"]
    table = fmap.threshold_table(v)
    tabs = engine.leaf_block_tables(placement)
    paths = [lp.path for lp in placement.leaves]
    wps = faulty.kv_words_per_slot(kh, d, jnp.bfloat16)
    page_words = PS * wps
    block_t, page_t = {}, {}
    for name in ("k", "v"):
        bb, bp = tabs[paths.index(f"['{name}']")]
        block_t[name] = (jnp.asarray(bb), table[jnp.asarray(bp)])
        pb, pp = engine.refine_tables(bb, bp, page_words)
        page_t[name] = (jnp.asarray(pb), table[jnp.asarray(pp)])
    q = jnp.asarray(rng.randn(b, 1, kh * g, d), jnp.bfloat16)
    pos_vals = np.arange(l)[None, :].repeat(b, 0).astype(np.int32)
    pos_vals[:, -3:] = -1               # empty ring slots stay masked
    pos = jnp.asarray(pos_vals)
    kw = dict(causal=True, window=0, seed=fmap.seed, method=method,
              words_per_row_log2=fmap.words_per_row_log2, ecc=ecc)
    return tree, q, pos, block_t, page_t, page_words, kw


def _pool_view(tree, pos):
    """The same cache as a page pool with identity page tables."""
    b, l, kh, d = tree["k"].shape
    n_lp = l // PS
    pool_k = tree["k"].reshape(b * n_lp, PS, kh, d)
    pool_v = tree["v"].reshape(b * n_lp, PS, kh, d)
    pool_pos = pos.reshape(b * n_lp, PS)
    ptab = jnp.asarray(
        np.arange(b * n_lp, dtype=np.int32).reshape(b, n_lp))
    return pool_k, pool_v, pool_pos, ptab


CASES = [("word", 0.88, False), ("bitwise", 0.86, False),
         ("word", 0.86, True)]

# The benchmark models' head shapes (8 KV heads of 128: a 4096-word
# page, 16 pages a block) over a 256-slot ring of two blocks, in an HBM
# large enough to place them.
HEADS = dict(b=2, l=256, kh=8, g=2, d=128,
             fmap=FaultMap.from_seed(dataclasses.replace(
                 TINY, name="heads", bytes_per_pc=1 << 20), seed=7))
# Per-slot decode positions: below the ring (lane 0 skips its second
# block), past it (wrapped, nothing skipped), and non-causal (nothing
# skipped); the tiny ring of one block is read wrapped.
LAYOUTS = {"tiny": ({}, (L + 4, L + 4), True),
           "heads-below": (HEADS, (70, 200), True),
           "heads-wrap": (HEADS, (256 + 4, 2 * 256 + 100), True),
           "heads-noncausal": (HEADS, (70, 200), False)}
CONTRACT = (
    [pytest.param(m, v, e, inject, "tiny", id=f"{m}-{v}-{e}-{inject}")
     for inject in (True, False) for m, v, e in CASES]
    + [pytest.param(m, v, e, True, layout, id=f"{layout}-{m}-{e}")
       for layout, (m, v, e) in (
           ("heads-below", ("bitwise", 0.86, False)),
           ("heads-below", ("word", 0.86, True)),
           ("heads-wrap", ("word", 0.86, True)),
           ("heads-noncausal", ("bitwise", 0.86, False)))])


@pytest.mark.parametrize("method,v,ecc,inject,layout", CONTRACT)
def test_paged_kernel_bit_identical_to_contiguous(method, v, ecc, inject,
                                                  layout):
    """The satellite contract: batched paged attention == the PR 3
    contiguous kernel on the same operands, at the paged kernel's block
    of pages, including the clean-slot exemption and the skipped blocks
    past each lane's position, with and without injection.  With ECC the
    kernel's per-page telemetry equals :func:`corrupt_page_tile`'s count
    of that page, and a skipped page counts 0."""
    shape, q_pos, causal = LAYOUTS[layout]
    tree, q, pos, block_t, page_t, page_words, kw = _operands(
        1, v, ecc, method=method, **shape)
    kw["causal"] = causal
    b, l, kh, d = tree["k"].shape
    wps = faulty.kv_words_per_slot(kh, d, jnp.bfloat16)
    pages = faulty.block_pages(l // PS, PS, wps)
    lg2 = page_words.bit_length() - 1

    # the batched paged kernel over the pool view of the cache
    pool_k, pool_v, pool_pos, ptab = _pool_view(tree, pos)
    telemetry = ecc and inject
    out_paged = faulty.paged_decode_attention(
        q, pool_k, pool_v, pool_pos, ptab,
        q_pos=jnp.asarray(q_pos, jnp.int32), k_tables=page_t["k"],
        v_tables=page_t["v"], inject=inject, telemetry=telemetry, **kw)
    if telemetry:
        out_paged, counts = out_paged

    for lane in range(b):
        # the contiguous kernel on this lane's ring, through arena-block
        # and page-granular tables, tiled by the paged kernel's block
        word0 = jnp.uint32(lane * l * wps)
        one = dict(q_pos=jnp.int32(q_pos[lane]), k_word0=word0,
                   v_word0=word0, inject=inject,
                   clean_slot=jnp.int32(q_pos[lane] % l),
                   bkv=pages * PS, **kw)
        sl = slice(lane, lane + 1)
        ref = faulty.faulty_decode_attention(
            q[sl], tree["k"][sl], tree["v"][sl], pos[sl],
            k_tables=block_t["k"], v_tables=block_t["v"], **one)
        out_pg = faulty.faulty_decode_attention(
            q[sl], tree["k"][sl], tree["v"][sl], pos[sl],
            k_tables=page_t["k"], v_tables=page_t["v"], words_log2=lg2,
            **one)
        np.testing.assert_array_equal(_bits(ref), _bits(out_pg))
        np.testing.assert_array_equal(_bits(ref), _bits(out_paged[sl]))

    if not telemetry:
        return
    live = np.asarray([faulty.live_blocks(qp, l, pages * PS, causal)
                       for qp in q_pos]) * pages
    assert layout != "heads-below" or live[0] < l // PS, "nothing skipped"
    want = np.zeros(counts.shape, np.int64)
    for lane in range(b):
        for lp in range(int(live[lane])):
            pid = int(ptab[lane, lp])
            for pool, (base, thr) in ((pool_k, page_t["k"]),
                                      (pool_v, page_t["v"])):
                _, n = faulty.corrupt_page_tile(
                    pool[pid], base[pid],
                    tuple(thr[pid, c] for c in range(thr.shape[1])),
                    seed=kw["seed"], method=method,
                    words_per_row_log2=kw["words_per_row_log2"], ecc=True,
                    slot0=lp * PS, clean_slot=q_pos[lane] % l,
                    with_counts=True)
                want[lane, lp] += int(n)
    assert want.sum() > 0, "no corrected codeword to count"
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_paged_kernel_skips_blocks_past_position():
    """A skipped block is never read: poisoning every slot past the
    live blocks with NaN leaves the output bit for bit as it was (a
    kernel that read them would propagate NaN through the masked
    scores), and the output matches float32 attention over the live
    positions."""
    tree, q, pos, _, page_t, _, kw = _operands(4, 0.86, False,
                                               method="word", **HEADS)
    b, l, kh, d = tree["k"].shape
    wps = faulty.kv_words_per_slot(kh, d, jnp.bfloat16)
    bk = faulty.block_pages(l // PS, PS, wps) * PS
    q_pos = (70, 200)
    live = [int(faulty.live_blocks(qp, l, bk, True)) * bk for qp in q_pos]
    assert live == [bk, l], live

    def run(k, v):
        pool_k, pool_v, pool_pos, ptab = _pool_view({"k": k, "v": v}, pos)
        return faulty.paged_decode_attention(
            q, pool_k, pool_v, pool_pos, ptab,
            q_pos=jnp.asarray(q_pos, jnp.int32), k_tables=page_t["k"],
            v_tables=page_t["v"], inject=False, **kw)

    out = run(tree["k"], tree["v"])
    dead = np.arange(l)[None, :] >= np.asarray(live)[:, None]
    poison = jnp.asarray(dead)[:, :, None, None]
    out_poisoned = run(jnp.where(poison, jnp.nan, tree["k"]),
                       jnp.where(poison, jnp.nan, tree["v"]))
    np.testing.assert_array_equal(_bits(out), _bits(out_poisoned))

    g = q.shape[2] // kh
    for lane, qp in enumerate(q_pos):
        keep = (np.asarray(pos[lane]) >= 0) & (np.asarray(pos[lane]) <= qp)
        qf = q[lane, 0].astype(jnp.float32) * np.float32(d ** -0.5)
        kf = jnp.repeat(tree["k"][lane].astype(jnp.float32), g, axis=1)
        vf = jnp.repeat(tree["v"][lane].astype(jnp.float32), g, axis=1)
        sc = jnp.einsum("hd,lhd->hl", qf, kf,
                        precision=jax.lax.Precision.HIGHEST)
        sc = jnp.where(jnp.asarray(keep)[None, :], sc, -jnp.inf)
        ref = jnp.einsum("hl,lhd->hd", jax.nn.softmax(sc, axis=-1), vf,
                         precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_allclose(
            np.asarray(out[lane, 0], np.float32), np.asarray(ref),
            rtol=2e-2, atol=2e-2)


def test_paged_kernel_per_slot_positions():
    """Every serving slot carries its own decode position (and hence
    its own causal mask and clean-slot exemption): each batched row
    equals a standalone single-request call at that position."""
    tree, q, pos, _, page_t, _, kw = _operands(2, 0.86, False,
                                               method="bitwise")
    pool_k, pool_v, pool_pos, ptab = _pool_view(tree, pos)
    q_pos = jnp.asarray([L + 4, L - 9], jnp.int32)
    out = faulty.paged_decode_attention(
        q, pool_k, pool_v, pool_pos, ptab, q_pos=q_pos,
        k_tables=page_t["k"], v_tables=page_t["v"], inject=True, **kw)
    for b in range(B):
        single = faulty.paged_decode_attention(
            q[b:b + 1], pool_k, pool_v, pool_pos, ptab[b:b + 1],
            q_pos=q_pos[b:b + 1], k_tables=page_t["k"],
            v_tables=page_t["v"], inject=True, **kw)
        np.testing.assert_array_equal(_bits(out[b]), _bits(single[0]))


def test_paged_kernel_traced_voltage_traces_once():
    """Page threshold tables derive from a traced voltage inside the
    caller's trace: a 5-point sweep compiles once and matches eager."""
    tree, q, pos, _, _, page_words, kw = _operands(3, 0.90, False,
                                                   method="word")
    pool_k, pool_v, pool_pos, ptab = _pool_view(tree, pos)
    domains = {"d": MemoryDomain("d", 0.90, tuple(range(6)))}
    placement = place_groups({"g": {k: tree[k] for k in ("k", "v")}},
                             {"g": "d"}, domains, TINY)["g"]
    tabs = engine.leaf_block_tables(placement)
    paths = [lp.path for lp in placement.leaves]
    refined = {name: engine.refine_tables(*tabs[paths.index(f"['{name}']")],
                                          page_words)
               for name in ("k", "v")}
    traces = []

    def run(vv):
        traces.append(1)
        table = FMAP.threshold_table(vv)
        t = {name: (jnp.asarray(pb), table[jnp.asarray(pp)])
             for name, (pb, pp) in refined.items()}
        return faulty.paged_decode_attention(
            q, pool_k, pool_v, pool_pos, ptab,
            q_pos=jnp.full((B,), L, jnp.int32), k_tables=t["k"],
            v_tables=t["v"], inject=True, **kw)

    jrun = jax.jit(run)
    outs = {vv: jrun(jnp.float32(vv))
            for vv in (0.90, 0.89, 0.88, 0.87, 0.86)}
    assert len(traces) == 1, f"voltage sweep retraced {len(traces)} times"
    assert bool(jnp.any(outs[0.90] != outs[0.86]))
    for vv in (0.90, 0.86):
        np.testing.assert_array_equal(_bits(outs[vv]),
                                      _bits(run(jnp.float32(vv))))


def test_refine_tables_is_pure_index_transform():
    bb = np.asarray([4096 * 7, 4096 * 11], np.uint32)
    bp = np.asarray([3, 5], np.int32)
    pb, pp = engine.refine_tables(bb, bp, 1024)
    np.testing.assert_array_equal(
        pb, [4096 * 7, 4096 * 7 + 1024, 4096 * 7 + 2048, 4096 * 7 + 3072,
             4096 * 11, 4096 * 11 + 1024, 4096 * 11 + 2048,
             4096 * 11 + 3072])
    np.testing.assert_array_equal(pp, [3, 3, 3, 3, 5, 5, 5, 5])
    with pytest.raises(ValueError, match="divide"):
        engine.refine_tables(bb, bp, 24)


# ---------------------------------------------------------------------------
# PagePool: tier routing, recycling, layout validation
# ---------------------------------------------------------------------------

BUNDLE = get_arch("llama3.2-3b")
CFG = BUNDLE.reduced
ALL_PCS = tuple(range(VCU128.num_pcs))


def _plan(v=0.88, ecc=False):
    return UndervoltPlan(
        domains={"kv": MemoryDomain("kv", v, ALL_PCS, ecc=ecc)},
        policy={"kv_cache": "kv"}, geometry=VCU128)


def _pool(num_pages=16, page_slots=8, plan=None, max_len=32, cfg=CFG):
    return PagePool(BUNDLE.module, cfg, max_len=max_len,
                    page_slots=page_slots, num_pages=num_pages,
                    plan=plan if plan is not None else _plan())


def test_pool_tier_routing_and_capacity_backpressure():
    pool = _pool()
    n_strong, n_weak = len(pool._strong), len(pool._weak)
    assert n_strong + n_weak == 16
    assert n_weak >= 1, "fault map should make some pages weak"

    strict = pool.alloc(2, "critical")
    assert not any(int(p) in pool._weak_set for p in strict)
    tolerant = pool.alloc(min(n_weak, 2), "cheap")
    assert all(int(p) in pool._weak_set for p in tolerant), (
        "tolerant tiers must consume weak pages first")

    with pytest.raises(CapacityError) as ei:
        pool.alloc(n_strong + n_weak, "critical")
    assert ei.value.domain == "kv"
    assert "weak" in str(ei.value)
    # ...but the same footprint is admissible for a tolerant tier if it
    # fits the whole pool
    assert pool.free_pages == 16 - len(strict) - len(tolerant)


def test_pool_free_realloc_deterministic_and_double_free_raises():
    pool = _pool()
    a = pool.alloc(4, "cheap")
    b = pool.alloc(3, "critical")
    pool.free(a)
    a2 = pool.alloc(4, "cheap")
    np.testing.assert_array_equal(np.sort(a), np.sort(a2))
    pool.free(a2)
    with pytest.raises(ValueError, match="double free"):
        pool.free(a2)
    with pytest.raises(ValueError, match="double free"):
        pool.free(np.asarray([pool.scratch_id]))  # never handed out
    pool.free(b)
    assert pool.free_pages == 16


def test_pool_request_words_match_standalone_cache():
    pool = _pool()
    from repro.models.base import spec_avals
    avals = spec_avals(BUNDLE.module.cache_specs(CFG, 1, 32))
    n_words = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize // 4
                  for a in jax.tree_util.tree_leaves(avals))
    assert pool.request_words == n_words


def test_pool_layout_errors_are_typed_and_actionable():
    # page count not dividing the ring length
    with pytest.raises(PagedLayoutError, match="divide"):
        _pool(page_slots=7)
    # page words not dividing the arena block size (kv slot = 8 words,
    # 3 slots -> 24-word pages; 4096 % 24 != 0)
    with pytest.raises(PagedLayoutError, match="block size"):
        _pool(page_slots=3, max_len=24)
    # window rings page cleanly now (window-modular tables), but a ring
    # shorter than one page cannot hold it: page must fit in the window
    cfg = dataclasses.replace(CFG, pattern=("local", "global"), window=8)
    with pytest.raises(PagedLayoutError, match="page_slots <= 8"):
        _pool(cfg=cfg, page_slots=16)
    # and the page size must divide the window (cfg.window named)
    cfg = dataclasses.replace(CFG, pattern=("local", "global"), window=12)
    with pytest.raises(PagedLayoutError, match="cfg.window"):
        _pool(cfg=cfg, page_slots=8)
    # ECC pools need even per-slot word counts (codeword pairs):
    # 1 kv-head x head_dim 2 = one bf16 word per slot
    cfg = dataclasses.replace(CFG, n_kv_heads=1, head_dim=2, n_heads=3)
    with pytest.raises(PagedLayoutError, match="ECC"):
        _pool(cfg=cfg, plan=_plan(ecc=True))
    # unpaged families are rejected up front
    from repro.models import moe
    with pytest.raises(ValueError, match="paged"):
        PagePool(moe, CFG, max_len=32, page_slots=8, num_pages=4,
                 plan=_plan())


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing: refcounts, typed guards, prefix cache
# ---------------------------------------------------------------------------


def test_pool_sharing_refcounts_and_typed_guards():
    """Every sharing-protocol violation is a typed PageSharingError:
    double release by the same holder, COW-forking an unshared page,
    re-sharing, re-retaining, and free()-ing a page with live holders."""
    from repro.serving.paged import PageSharingError
    pool = _pool()
    pids = pool.alloc(2, "cheap")
    a, b = ("__req__", "a"), ("__req__", "b")

    with pytest.raises(PageSharingError, match="not a shared page"):
        pool.cow_fork(pids[0])          # private pages fork nothing
    with pytest.raises(PageSharingError, match="not a shared page"):
        pool.retain(pids, a)

    pool.share(pids, a)
    assert pool.shared_pages == 2
    with pytest.raises(PageSharingError, match="already shared"):
        pool.share(pids[:1], b)
    with pytest.raises(PageSharingError, match="released per holder"):
        pool.free(pids)                 # live holders block free()

    pool.retain(pids, b)
    with pytest.raises(PageSharingError, match="already"):
        pool.retain(pids[:1], b)        # double retain, same holder

    fork = pool.cow_fork(pids[0], "critical")
    assert fork not in set(int(p) for p in pids)
    assert int(fork) not in pool._weak_set

    pool.release(pids, a)
    with pytest.raises(PageSharingError, match="double release"):
        pool.release(pids, a)           # second release, same request
    assert pool.shared_pages == 2       # b still holds both
    pool.release(pids, b)
    assert pool.shared_pages == 0
    pool.free([fork])
    assert pool.free_pages == 16        # refcounted release recycles


def test_pool_prefix_cache_match_register_evict():
    """Longest-prefix matching is content-hashed and page-aligned (the
    full prompt may end inside a page), registration is idempotent, and
    LRU eviction releases only the cache's own holds."""
    pool = _pool()
    ps = pool.page_slots
    toks = np.arange(20, dtype=np.int32)          # 2 full pages + 4 rows
    pids = pool.alloc(3, "cheap")
    pool.share(pids, ("__req__", "creator"))
    assert pool.register_prefix(toks[:ps], pids[:1])
    assert pool.register_prefix(toks[:2 * ps], pids[:2])
    assert pool.register_prefix(toks, pids)
    assert not pool.register_prefix(toks, pids)   # already cached
    assert pool.prefix_entries == 3

    ln, got = pool.match_prefix(toks)             # full match first
    assert ln == 20 and np.array_equal(got, pids)
    other = np.concatenate([toks[:2 * ps], [999, 998]]).astype(np.int32)
    ln, got = pool.match_prefix(other)            # page-aligned fallback
    assert ln == 2 * ps and np.array_equal(got, pids[:2])
    ln, got = pool.match_prefix(toks[:ps - 1])    # shorter than a page
    assert ln == 0 and got.shape == (0,)
    ln, _ = pool.match_prefix(np.array([7, 7, 7], np.int32))
    assert ln == 0

    # LRU eviction: oldest entry first; pages survive through the
    # holders that remain (later entries, the creating request)
    assert pool.evict_prefix()
    assert pool.prefix_entries == 2
    assert pool.match_prefix(other)[0] == 2 * ps  # longer entry intact
    pool.release(pids, ("__req__", "creator"))
    assert pool.shared_pages == 3                 # cache holds remain
    while pool.evict_prefix():
        pass
    assert pool.prefix_entries == 0 and pool.shared_pages == 0
    assert pool.free_pages == 16                  # fully recycled
