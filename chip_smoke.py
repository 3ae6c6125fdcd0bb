#!/usr/bin/env python3
"""Chip smoke test: yi-34b at its published widths, served on a TPU.

Drives the paged serving path once, end to end, through the entry
points a user calls: ``ContinuousBatchingScheduler`` with read-path
fault injection (``paged_decode_attention`` corrupting K/V pages in
VMEM as they load), chunked prefill and the one donated step.  The
model is yi-34b (``configs/yi_34b.py``) at every published width; only
the depth is cut so weights and KV pool fit one v5e chip.  Weights are
random, drawn from a seed.

One chip (the default) checks, and fails on any miss:
  * the resolved injection mode is the fused read path;
  * the compiled step holds a Mosaic kernel (``tpu_custom_call``) and
    compiles once (``decode_traces == 1``);
  * one request replayed alone through a fresh scheduler lands on the
    same pages with bit-identical tokens, and every request keeps its
    tokens when its co-tenants' prompts are re-drawn (isolation);
  * every served token agrees with ``generate()``'s one-request
    programs on the same placement, fed the served tokens: it is the
    reference's top token or within GAP_SIGMA of it (the two programs
    round differently, so near-ties may flip; a wrong program picks
    tokens several sigma down);
  * the compiled ``paged_decode_attention`` agrees with a jnp
    corrupt-then-attend reference at yi-34b shapes, without and with
    ECC (and its telemetry counts), while a kernel that injected
    nothing would not.

``--chips 4`` runs only the sharded scheduler and what it is compared
with: a 4-shard serve mesh, one weight replica per chip, zero
collectives in the step, one request replayed alone on each shard,
every request unchanged when its co-tenants change, and every request
checked as above against ``generate()`` on its shard's plan.

The last line of stdout is one JSON object naming the device; every
other line comes before it.  Without a TPU the script exits non-zero
and prints no result.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DEPTH = 8            # of 60 layers: 10.8 GB of bf16 weights on a 16 GB v5e
MAX_LEN = 512        # ring length per request
PAGE_SLOTS = 8       # one yi KV page (8 slots x 8 heads x 128) = 16 KiB
PREFILL_CHUNK = 64
KV_VOLTAGE = 0.90    # faulty point: ~1e-5 bit faults on the KV domain
# The kernel check runs deeper: at 0.90 V a 257-page yi-34b K pool
# holds ~3 faulted words, too few to move an attention output, so the
# comparison could not tell injection from none; at 0.89 V about 400
# of the 28672 outputs move.  SECDED repairs single faults, so the ECC
# variant runs at 0.88 V, where uncorrectable codewords move outputs
# (deeper, at 0.86 V, every output is NaN and the check goes blind).
CHECK_VOLTAGE = 0.89
CHECK_VOLTAGE_ECC = 0.88
SEED = 0
# Compiled kernel vs float32 reference: the kernel rounds its output to
# bfloat16 (relative step 2**-8) and its f32 dots may take bf16 MXU
# passes (relative error ~2**-8 on scores of order 1), so elementwise
# |kernel - ref| <= ATOL + RTOL * |ref| with both at 2e-2.  A wrong
# mask, page or softmax is off by O(1).
ATOL = RTOL = 2e-2
# Served token vs generate()'s one-request programs fed the served
# tokens: the served token's reference logit lies at most GAP_SIGMA
# below the reference's top logit, in units of the std of that step's
# logits over the vocab.  The programs' bf16 hidden states differ by
# rounding; a relative drift up to 2**-4 (two bf16 roundings per layer
# over 8 layers) moves a logit, a 7168-term sum of random signs, by up
# to ~4.5 * 2**-4 sigma at the extreme of 64000 logits, so the two
# picks lie within twice that.  A wrong program picks tokens several
# sigma down (the typical token sits ~4 sigma below the top).
GAP_SIGMA = 9 * 2.0 ** -4
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def yi_config(depth: int = DEPTH):
    from repro.configs import yi_34b
    return dataclasses.replace(yi_34b.CFG, n_layers=depth)


def kv_plan(voltage: float = KV_VOLTAGE):
    from repro.core.domains import MemoryDomain
    from repro.core.hbm import TPU_V5E
    from repro.training.undervolt import UndervoltPlan
    return UndervoltPlan(
        domains={"kv": MemoryDomain("kv", voltage,
                                    tuple(range(TPU_V5E.num_pcs)))},
        policy={"kv_cache": "kv"}, geometry=TPU_V5E)


def serve_config(max_len: int, max_new: int):
    from repro.serving.engine import ServeConfig
    return ServeConfig(max_len=max_len, max_new_tokens=max_new,
                       undervolt=kv_plan(), kv_injection="read",
                       kv_method="auto", prefill_chunk=PREFILL_CHUNK)


def make_requests(cfg, lengths, news, seed: int = SEED):
    import jax
    import numpy as np
    from repro.serving.scheduler import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=f"r{i}",
                    tokens=rng.randint(0, cfg.vocab, (n,)).astype(np.int32),
                    max_new_tokens=m, key=jax.random.PRNGKey(100 + i))
            for i, (n, m) in enumerate(zip(lengths, news))]


def compile_step(sched):
    """AOT-compile the scheduler's donated step on its live arguments
    (the later dispatch reuses this executable and this trace)."""
    t0 = time.perf_counter()
    compiled = sched._step.lower(sched.params, sched.state,
                                 sched._volt_vec()).compile()
    return compiled, time.perf_counter() - t0


def serve(sched, reqs):
    """Submit ``reqs``, drain, and report host-clock throughput."""
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    res = sched.run()
    wall = time.perf_counter() - t0
    n_tok = sum(int(res[r.rid].tokens.shape[1]) for r in reqs)
    for r in reqs:
        check(res[r.rid].tokens.shape == (1, r.max_new_tokens),
              f"{r.rid}: tokens {res[r.rid].tokens.shape}")
    log(f"served {len(reqs)} requests, {n_tok} tokens in {wall:.3f} s "
        f"host clock: {n_tok / wall:.1f} tokens/s "
        "(smoke, not a benchmark)")
    return res


def rerun(make_sched, reqs, res, keep, drop_others: bool, vocab: int,
          seed: int = SEED + 7) -> None:
    """Serve the stream again from a fresh scheduler with every request
    outside ``keep`` dropped (the kept ones then run alone) or given a
    fresh prompt of the same length (same slots, pages and steps, other
    co-tenants).  Each kept request must come back on the same shard and
    pages with bit-identical tokens: what a request computes does not
    depend on what, or whether anything, is served beside it."""
    import numpy as np
    rng = np.random.RandomState(seed)
    stream = []
    for i, r in enumerate(reqs):
        if i in keep:
            stream.append(r)
        elif not drop_others:
            stream.append(dataclasses.replace(r, tokens=rng.randint(
                0, vocab, r.tokens.shape).astype(np.int32)))
    sched = make_sched()
    for r in stream:
        sched.submit(r)
    again = sched.run()
    for i in keep:
        r, a, b = reqs[i], res[reqs[i].rid], again[reqs[i].rid]
        check(a.shard == b.shard and np.array_equal(a.page_ids, b.page_ids),
              f"{r.rid}: rerun placed on shard {b.shard} pages "
              f"{b.page_ids[:4]}..., first run shard {a.shard} pages "
              f"{a.page_ids[:4]}...")
        check(np.array_equal(a.tokens, b.tokens),
              f"{r.rid}: rerun tokens {b.tokens.tolist()} != first run "
              f"{a.tokens.tolist()}")


def forced_logits(bundle, cfg, params, sc, req, result):
    """Reference logits for every token served to ``req``: the request
    alone through ``generate()``'s one-request programs (its bucketed
    prefill, then its decode engine's step on the recorded placement,
    injecting the same faults) with the served tokens fed back in place
    of the reference's own picks, as ``generate(decode="loop")`` runs
    them.  Returns (max_new_tokens, vocab) float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.engine import build_decode_engine, bucketed_prefill

    served = np.asarray(result.tokens[0])
    plen = int(req.tokens.shape[0])
    v = result.placement.domain.voltage
    eng = build_decode_engine(
        bundle, cfg, dataclasses.replace(sc, max_new_tokens=served.size),
        1, plen, static_voltage=v, kv_placement=result.placement)
    check(eng.mode == "read" and eng.use_fused,
          f"{req.rid}: reference not on the fused read path")
    prefill = bucketed_prefill(bundle.module, cfg, sc.max_len)
    logits, cache = prefill(params, {"tokens": jnp.asarray(req.tokens)[None]})
    varr = jnp.float32(v)
    cache = jax.jit(eng.init_inject)(cache, varr)
    step = jax.jit(eng.step_core, donate_argnums=(1,))
    rows = [logits[0]]
    for i in range(served.size - 1):
        logits, cache = step(params, cache,
                             jnp.asarray(served[i:i + 1])[None],
                             jnp.int32(plen + i), varr)
        rows.append(logits[0])
    return np.asarray(jnp.stack(rows), np.float32)


def check_against_generate(bundle, cfg, params, sc_of, reqs, res) -> None:
    """Every served token against :func:`forced_logits` (``sc_of(r)``:
    the ServeConfig whose plan served ``r``).  Where the reference's
    logits spread (std over the vocab > 0), the served token is its top
    token or within GAP_SIGMA of it.  Where an injected fault made them
    NaN or flat, the served token is the reference's own pick (greedy
    argmax takes a NaN row's first NaN, a flat row's first entry), so
    both programs must degenerate at the same steps."""
    import numpy as np
    t0 = time.perf_counter()
    worst, agree, total, n_flat, typical = 0.0, 0, 0, 0, []
    for r in reqs:
        lg = forced_logits(bundle, cfg, params, sc_of(r), r,
                           res[r.rid]).astype(np.float64)
        served = np.asarray(res[r.rid].tokens[0])
        pick = lg.argmax(axis=-1)
        with np.errstate(invalid="ignore"):
            sigma = lg.std(axis=-1)
        flat = ~(sigma > 0)
        check(bool((pick[flat] == served[flat]).all()),
              f"{r.rid}: at the steps where generate()'s logits are NaN "
              f"or flat ({np.flatnonzero(flat).tolist()}) it picks "
              f"{pick[flat].tolist()}, the scheduler served "
              f"{served[flat].tolist()}")
        lf, sf, sg = lg[~flat], served[~flat], sigma[~flat]
        top = lf.max(axis=-1)
        gap = (top - lf[np.arange(sf.size), sf]) / sg
        check(bool((gap <= GAP_SIGMA).all()),
              f"{r.rid}: served tokens {sf.tolist()} sit "
              f"{np.round(gap, 4).tolist()} sigma below generate()'s top "
              f"logit (bound {GAP_SIGMA})")
        worst = max([worst] + gap.tolist())
        agree += int((pick == served).sum())
        total += served.size
        n_flat += int(flat.sum())
        if sf.size:
            typical.append(float(np.median((top[:, None] - lf)
                                           / sg[:, None])))
    log(f"generate() replay fed the served tokens, {len(reqs)} requests "
        f"on their placements: {agree} of {total} tokens are its top "
        f"token ({n_flat} at NaN or flat steps); worst served-token gap "
        f"{worst:.4f} sigma (bound {GAP_SIGMA}); the median token sits "
        f"{min(typical, default=float('nan')):.3f} sigma below the top "
        f"({time.perf_counter() - t0:.2f} s)")


def kernel_reference(q, k_pool, v_pool, pos_pool, ptab, q_pos, k_tabs,
                     v_tabs, *, seed, method, wprl2, inject, ecc):
    """jnp corrupt-then-attend twin of ``paged_decode_attention``: each
    page's packed words corrupted by the arena mask math, or with
    ``ecc`` by the reshape-paired SECDED oracle (no kernel, no lane
    shuffles), the written slot exempt, then one-shot float32 softmax
    attention at HIGHEST precision.  Returns (out, telemetry): with
    ``ecc``, corrected codewords per (slot, logical page) outside the
    written slot, K and V pages summed, 0 on the pages the kernel skips
    past the slot's position; else None."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import faultmap as fm
    from repro.kernels.bitflip.bitflip import apply_masks
    from repro.kernels.bitflip.ops import from_u32, to_u32
    from repro.kernels.ecc.ecc import arena_ecc_events
    from repro.kernels.flash_attention import faulty

    s, _, h, d = q.shape
    n, ps, kh, _ = k_pool.shape
    n_lp = ptab.shape[1]
    length = n_lp * ps
    pb = faulty.block_pages(n_lp, ps,
                            faulty.kv_words_per_slot(kh, d, k_pool.dtype))

    def corrupt(pool, tabs):
        """(corrupted pool, corrected codewords per (page, slot row))."""
        base, thr = tabs
        u, meta = to_u32(pool)                 # pages are word-contiguous
        u = u.reshape(n, -1)
        wid = base[:, None] + jnp.arange(u.shape[1], dtype=jnp.uint32)
        row = tuple(thr[:, c][:, None] for c in range(fm.NUM_THR_COLS))
        if ecc:
            out, corr, _ = arena_ecc_events(u, wid, row, seed=seed,
                                            words_per_row_log2=wprl2)
            corr = np.asarray(corr).reshape(n, ps, -1).sum(-1)
        else:
            out = apply_masks(u, wid, row, seed=seed, method=method,
                              words_per_row_log2=wprl2)
            corr = None
        return from_u32(out.reshape(-1), meta), corr

    kc, k_corr = corrupt(k_pool, k_tabs) if inject else (k_pool, None)
    vc, v_corr = corrupt(v_pool, v_tabs) if inject else (v_pool, None)
    outs = []
    telem = np.zeros((s, n_lp), np.int64)
    for si in range(s):
        pages = ptab[si]
        clean = int(q_pos[si]) % length
        kk = kc[pages].reshape(length, kh, d)
        vv = vc[pages].reshape(length, kh, d)
        kk = kk.at[clean].set(k_pool[pages].reshape(length, kh, d)[clean])
        vv = vv.at[clean].set(v_pool[pages].reshape(length, kh, d)[clean])
        if k_corr is not None:
            rows = (k_corr[pages] + v_corr[pages]).reshape(length)
            rows[clean] = 0
            telem[si] = rows.reshape(n_lp, ps).sum(-1)
            live = int(faulty.live_blocks(q_pos[si], length, pb * ps,
                                          True)) * pb
            telem[si, live:] = 0
        pos = pos_pool[pages].reshape(length)
        g = h // kh
        qf = q[si, 0].astype(jnp.float32) * np.float32(d ** -0.5)
        kf = jnp.repeat(kk.astype(jnp.float32), g, axis=1)   # (L, H, D)
        vf = jnp.repeat(vv.astype(jnp.float32), g, axis=1)
        sc = jnp.einsum("hd,lhd->hl", qf, kf,
                        precision=jax.lax.Precision.HIGHEST)
        dead = (pos > q_pos[si]) | (pos < 0)
        sc = sc + jnp.where(dead, np.float32(-1e30), 0)[None, :]
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hl,lhd->hd", p, vf,
                               precision=jax.lax.Precision.HIGHEST))
    return (np.asarray(jnp.stack(outs)[:, None]),
            telem if k_corr is not None else None)


def tolerance_units(out, ref):
    """Worst |out - ref| in units of ATOL + RTOL * |ref| over the
    outputs finite in both, and the outputs NaN in exactly one."""
    import numpy as np
    nan_o, nan_r = np.isnan(out), np.isnan(ref)
    fin = np.isfinite(out) & np.isfinite(ref)
    units = np.abs(out[fin] - ref[fin]) / (ATOL + RTOL * np.abs(ref[fin]))
    return (float(units.max()) if units.size else 0.0,
            int((nan_o != nan_r).sum()))


def kernel_check(sched, voltage: float, ecc: bool) -> None:
    """Compiled ``paged_decode_attention`` at the served model's shapes
    against :func:`kernel_reference`, on the scheduler's own page
    tables and fault map (with ``ecc``, also its telemetry counts).
    The control: the same reference without injection must fall
    outside the tolerance, so a kernel that injected nothing fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention import faulty

    cfg, pool = sched.cfg, sched.pool
    s, h, kh, d = 4, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n, ps = pool.total_pages, pool.page_slots
    n_lp = pool.n_logical_pages
    length = n_lp * ps
    rng = np.random.RandomState(SEED + 1)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    q = jax.random.normal(kq, (s, 1, h, d), jnp.float32).astype(jnp.bfloat16)
    k_pool = jax.random.normal(kk, (n, ps, kh, d), jnp.float32
                               ).astype(jnp.bfloat16)
    v_pool = jax.random.normal(kv, (n, ps, kh, d), jnp.float32
                               ).astype(jnp.bfloat16)
    ptab = rng.permutation(pool.num_pages)[:s * n_lp].reshape(s, n_lp)
    q_pos = np.asarray([length // 3, length - 1, 5, length // 2][:s],
                       np.int32)
    pos = np.full((n, ps), -1, np.int32)
    slots = np.arange(length)
    for si in range(s):
        pos[ptab[si]] = np.where(slots <= q_pos[si], slots,
                                 -1).reshape(n_lp, ps)
    pos = jnp.asarray(pos)
    table = pool.faultmap.threshold_table(jnp.float32(voltage))
    leaf = {l.which: l for l in pool.leaves if l.which in ("k", "v")}
    tabs = {w: (jnp.asarray(leaf[w].page_base[0]),
                table[jnp.asarray(leaf[w].page_pc[0])]) for w in leaf}
    kw = dict(seed=pool.faultmap.seed, method=sched.method)
    wprl2 = pool.faultmap.words_per_row_log2
    got = faulty.paged_decode_attention(
        q, k_pool, v_pool, pos, jnp.asarray(ptab, jnp.int32),
        q_pos=jnp.asarray(q_pos), k_tables=tabs["k"], v_tables=tabs["v"],
        words_per_row_log2=wprl2, ecc=ecc, inject=True, telemetry=ecc, **kw)
    out, telem = got if ecc else (got, None)
    ref, ref_telem = kernel_reference(
        q, k_pool, v_pool, pos, ptab, q_pos, tabs["k"], tabs["v"],
        wprl2=wprl2, inject=True, ecc=ecc, **kw)
    clean, _ = kernel_reference(
        q, k_pool, v_pool, pos, ptab, q_pos, tabs["k"], tabs["v"],
        wprl2=wprl2, inject=False, ecc=ecc, **kw)
    out = np.asarray(out.astype(jnp.float32))
    check(out.shape == ref.shape, f"kernel shape {out.shape} != {ref.shape}")
    worst, nan_diff = tolerance_units(out, ref)
    control, control_nan = tolerance_units(clean, ref)
    name = "ECC + telemetry" if ecc else f"{sched.method} path"
    log(f"paged_decode_attention ({name}) compiled vs jnp reference at "
        f"S={s} H={h} KH={kh} D={d} page={ps} ring={length}, {voltage} V: "
        f"worst error {worst:.4f} of tolerance (atol=rtol={ATOL}), "
        f"{nan_diff} NaN mismatches; control without injection: worst "
        f"{control:.4f} of tolerance, {control_nan} NaN mismatches")
    check(worst <= 1.0 and nan_diff == 0,
          "compiled kernel outside tolerance of reference")
    check(control > 1.0 or control_nan > 0,
          "the reference without injection is inside the tolerance: the "
          "comparison could not tell injection from none")
    if ecc:
        telem = np.asarray(telem)
        log(f"telemetry: {int(telem.sum())} corrected codewords counted "
            f"by the kernel, {int(ref_telem.sum())} by the reference")
        check(np.array_equal(telem, ref_telem),
              "kernel telemetry counts differ from the reference")
        check(int(telem.sum()) > 0, "no corrected codeword to count")


def log_config(cfg) -> None:
    log(f"model yi-34b: depth {cfg.n_layers} of 60 layers (only cut); "
        f"d_model {cfg.d_model}, heads {cfg.n_heads}, kv_heads "
        f"{cfg.n_kv_heads}, head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}, rope_theta {cfg.rope_theta:g}")


def log_memory(devices) -> list:
    used = []
    for dv in devices:
        st = dv.memory_stats() or {}
        used.append(st.get("bytes_in_use", 0))
        log(f"device {dv.id}: bytes_in_use {st.get('bytes_in_use')} "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use')} "
            f"bytes_limit {st.get('bytes_limit')}")
    return used


def one_chip(cfg, max_len=MAX_LEN, lengths=(200, 260, 330, 400),
             news=(16, 24, 32, 20)) -> None:
    import jax
    from repro.models.base import get_arch, init_params
    from repro.serving.scheduler import ContinuousBatchingScheduler

    bundle = get_arch("yi-34b")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(
        bundle.module.param_specs(cfg), jax.random.PRNGKey(SEED)))
    log(f"params drawn in {time.perf_counter() - t0:.2f} s")
    reqs = make_requests(cfg, lengths, news)
    sc = serve_config(max_len, max(news))
    def make_sched():
        return ContinuousBatchingScheduler(
            bundle, cfg, params, sc, num_slots=4,
            num_pages=4 * max_len // PAGE_SLOTS, page_slots=PAGE_SLOTS)

    sched = make_sched()
    log(f"kv domain {KV_VOLTAGE} V; resolved kv_injection={sched.mode} "
        f"kv_method={sched.method} (injection live: {sched.active})")
    check(sched.mode == "read" and sched.active,
          "not on the fused read path with live injection")
    check(sched.method in ("word", "bitwise"), f"method {sched.method}")

    compiled, secs = compile_step(sched)
    hlo = compiled.as_text()
    ma = compiled.memory_analysis()
    log(f"step compile {secs:.2f} s; step memory: arguments "
        f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B, "
        f"aliased {ma.alias_size_in_bytes} B")
    check("tpu_custom_call" in hlo, "no Mosaic kernel in the step's HLO")
    log(f"tpu_custom_call in step HLO: {hlo.count('tpu_custom_call')}")

    res = serve(sched, reqs)
    traces = sched.stats["decode_traces"]
    log(f"decode_traces == {traces}")
    check(traces == 1, f"decode_traces {traces}")

    t0 = time.perf_counter()
    rerun(make_sched, reqs, res, keep={0}, drop_others=True,
          vocab=cfg.vocab)
    log(f"solo replay of {reqs[0].rid} alone through a fresh scheduler: "
        "same pages, bit-identical tokens")
    for parity in (0, 1):
        rerun(make_sched, reqs, res, drop_others=False, vocab=cfg.vocab,
              keep={i for i in range(len(reqs)) if i % 2 == parity})
    log(f"every request bit-identical with its co-tenants' prompts "
        f"re-drawn ({time.perf_counter() - t0:.2f} s for 3 reruns)")
    check_against_generate(bundle, cfg, params, lambda r: sc, reqs, res)

    kernel_check(sched, CHECK_VOLTAGE, ecc=False)
    kernel_check(sched, CHECK_VOLTAGE_ECC, ecc=True)
    log_memory(jax.devices()[:1])


def four_chips(cfg, max_len=MAX_LEN,
               lengths=(120, 180, 150, 210, 100, 240, 130, 200),
               news=(16,) * 8) -> None:
    import jax
    from repro.launch.mesh import make_serve_mesh
    from repro.models.base import get_arch, init_params
    from repro.serving.scheduler import ContinuousBatchingScheduler

    bundle = get_arch("yi-34b")
    mesh = make_serve_mesh(4)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    t0 = time.perf_counter()
    # drawn once on device 0, copied leaf by leaf to the other chips and
    # assembled into replicated arrays: every chip holds one replica
    devs = list(mesh.devices.flat)
    params = jax.tree_util.tree_map(
        lambda x: jax.make_array_from_single_device_arrays(
            x.shape, rep, [x] + [jax.device_put(x, dv) for dv in devs[1:]]),
        init_params(bundle.module.param_specs(cfg),
                    jax.random.PRNGKey(SEED)))
    jax.block_until_ready(params)
    log(f"params drawn as 4 replicas in {time.perf_counter() - t0:.2f} s")
    reqs = make_requests(cfg, lengths, news)
    sc = serve_config(max_len, max(news))
    def make_sched():
        return ContinuousBatchingScheduler(
            bundle, cfg, params, sc, num_slots=8,
            num_pages=8 * max_len // PAGE_SLOTS, page_slots=PAGE_SLOTS,
            mesh=mesh)

    sched = make_sched()
    log(f"serve mesh: {sched.n_shards} shards, "
        f"{sched.slots_per_shard} slots and {sched.pages_per_shard} pages "
        f"each; map seeds {list(sched.shard_seeds)}; resolved "
        f"kv_injection={sched.mode} kv_method={sched.method}")
    check(sched.mode == "read" and sched.active, "not on the read path")

    compiled, secs = compile_step(sched)
    hlo = compiled.as_text()
    ma = compiled.memory_analysis()
    coll = {c: hlo.count(c) for c in COLLECTIVES}
    log(f"step compile {secs:.2f} s; per-device step memory: arguments "
        f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B; "
        f"collectives {coll}; tpu_custom_call "
        f"{hlo.count('tpu_custom_call')}")
    check(not any(coll.values()), f"collectives in the step: {coll}")
    check("tpu_custom_call" in hlo, "no Mosaic kernel in the step's HLO")
    check(all(len(x.sharding.device_set) == 4 and
              x.sharding.is_fully_replicated
              for x in jax.tree_util.tree_leaves(sched.params)),
          "params are not one replica per chip")

    res = serve(sched, reqs)
    traces = sched.stats["decode_traces"]
    shards = sorted({res[r.rid].shard for r in reqs})
    log(f"decode_traces == {traces}; requests per shard "
        f"{[sum(res[r.rid].shard == k for r in reqs) for k in range(4)]}")
    check(traces == 1, f"decode_traces {traces}")
    check(shards == [0, 1, 2, 3], f"requests landed on shards {shards}")
    used = log_memory(jax.devices())
    check(max(used) <= 1.1 * min(used),
          f"device memory uneven across shards: {used}")

    t0 = time.perf_counter()
    first = {min(i for i, r in enumerate(reqs) if res[r.rid].shard == k)
             for k in range(4)}
    rerun(make_sched, reqs, res, keep=first, drop_others=True,
          vocab=cfg.vocab)
    log(f"solo replay of {sorted(reqs[i].rid for i in first)}, one alone "
        "on each shard: same shard and pages, bit-identical tokens")
    for parity in (0, 1):
        rerun(make_sched, reqs, res, drop_others=False, vocab=cfg.vocab,
              keep={i for i in range(len(reqs)) if i % 2 == parity})
    log(f"all {len(reqs)} requests bit-identical with their co-tenants' "
        f"prompts re-drawn ({time.perf_counter() - t0:.2f} s for 3 reruns)")
    # the reference runs on device 0's replica, on each shard's plan
    params0 = jax.tree_util.tree_map(
        lambda x: next(sh.data for sh in x.addressable_shards
                       if sh.device == devs[0]), params)
    check_against_generate(
        bundle, cfg, params0,
        lambda r: dataclasses.replace(
            sc, undervolt=sched.shard_plan(res[r.rid].shard)), reqs, res)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded scheduler on a 4-shard "
                    "serve mesh")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    cfg = yi_config()
    log_config(cfg)
    if args.chips == 1:
        one_chip(cfg)
    else:
        four_chips(cfg)
    log(f"smoke wall {time.perf_counter() - t0:.2f} s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
