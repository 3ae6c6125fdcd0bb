"""Pallas TPU kernel: decode attention with read-path fault injection.

The paper's reduced-voltage faults manifest when undervolted HBM is
*read*.  The write-path model (corrupt the stored cache, then attend)
pays an extra O(cache) HBM read-modify-write per decoded token; this
kernel moves injection onto the read path: K/V tiles are corrupted *in
VMEM, as they are loaded* by the decode attention kernel, so injection
costs zero extra HBM passes and rides the bandwidth the attention read
already spends.

Mechanics:

  * the serving placement exports, per cache leaf, the arena engine's
    ``block -> (physical base word, threshold row)`` tables; they arrive
    as scalar-prefetch operands (SMEM), with threshold rows derived from
    a possibly *traced* voltage -- traced KV-voltage sweeps compile once;
  * each K/V tile is a contiguous run of leaf words (the tile spans all
    KV heads of ``bkv`` cache slots), so its per-word physical ids and
    threshold rows come from :func:`select_block_tables` -- a handful of
    dynamic-scalar SMEM reads plus vector selects, never a gather;
  * the mask math is the exact tile-level functions the arena engine
    runs (:func:`apply_masks` / :func:`arena_ecc_word_events`), so
    read-path corruption is bit-identical to corrupt-then-attend on the
    same operands (asserted in tests/test_readpath.py);
  * the slot written *this* step is exempt (``clean_slot``): the freshly
    computed K/V is still in the store buffer, not yet a round-trip
    through undervolted HBM -- which also makes the scanned decode
    token-for-token identical to the legacy corrupt-after-step loop.

With ``inject=False`` the kernel is plain decode flash attention over
the stored cache -- the write-path modes use the same kernel so every
injection mode shares one set of attention numerics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import faultmap as fm
from repro.kernels.bitflip.bitflip import (BLOCK_WORDS, BLOCK_WORDS_LOG2,
                                           apply_masks, lane_xor,
                                           select_block_tables)
from repro.kernels.bitflip.ops import default_interpret
from repro.kernels.ecc.ecc import arena_ecc_word_events, count_codewords

NEG_INF = -1e30

# Per-tile word cap: bounds the candidate-block selects (SMEM reads) a
# tile needs to resolve its physical addresses.
TILE_WORD_CAP = 16 * BLOCK_WORDS


def packing(dtype) -> int:
    """Elements per uint32 word for a cache dtype."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize > 4:
        raise NotImplementedError(f"itemsize {itemsize} for {dtype}")
    return 4 // itemsize


def kv_words_per_slot(kh: int, d: int, dtype) -> int:
    """uint32 words one cache slot (all KV heads) occupies; the unit of
    the cache-position -> arena-word mapping."""
    p = packing(dtype)
    if (kh * d) % p:
        raise ValueError(
            f"KV slot of {kh}x{d} {jnp.dtype(dtype).name} elements is not "
            "word-aligned; the read path needs whole uint32 words per slot")
    return kh * d // p


def pick_bkv(length: int, words_per_slot: int,
             cap: int = TILE_WORD_CAP) -> int:
    """Largest divisor of the cache length whose tile fits the word cap."""
    best = 1
    for c in range(1, length + 1):
        if length % c == 0 and c * words_per_slot <= cap:
            best = c
    return best


def _tile_to_u32(x):
    """(rows, elems) any-dtype tile -> (rows, words) uint32 view, word
    pairing identical to ``bitflip.ops.to_u32`` on the flattened leaf
    (the jnp paths; kernels use :func:`_lane_words`)."""
    p = packing(x.dtype)
    if p == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    lane = jax.lax.bitcast_convert_type(
        x, jnp.uint16 if p == 2 else jnp.uint8)
    return jax.lax.bitcast_convert_type(
        lane.reshape(x.shape[0], -1, p), jnp.uint32)


def _tile_from_u32(u32, dtype, shape):
    p = packing(dtype)
    if p == 1:
        return jax.lax.bitcast_convert_type(u32, dtype).reshape(shape)
    lane = jax.lax.bitcast_convert_type(
        u32, jnp.uint16 if p == 2 else jnp.uint8)
    return jax.lax.bitcast_convert_type(
        lane.reshape(shape[0], -1), dtype).reshape(shape)


def _lane_words(x):
    """Any-dtype tile -> same-shape uint32 in which every lane holds the
    whole word its element is packed into (word pairing identical to
    ``bitflip.ops.to_u32`` on the flattened leaf; the last axis must be
    a multiple of the packing).

    Kernels keep the tile element-wise instead of packing it to words:
    packing splits the lane axis, which Mosaic cannot lower.  Each word
    is assembled from its elements' lanes with :func:`lane_xor`
    shuffles, so the per-word mask math runs once per element lane and
    lands on the same words bit for bit.
    """
    p = packing(x.dtype)
    if p == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = 32 // p
    u = jax.lax.bitcast_convert_type(
        x, jnp.uint16 if p == 2 else jnp.uint8).astype(jnp.uint32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    word = jnp.zeros(x.shape, jnp.uint32)
    for m in range(p):
        xm = u
        for bit in (1, 2):
            if m & bit:
                xm = lane_xor(xm, bit)
        shift = (((lane ^ m) & (p - 1)) * bits).astype(jnp.uint32)
        word = word | (xm << shift)
    return word


def _lane_elems(words, dtype):
    """Inverse of :func:`_lane_words`: each lane keeps its own element
    of the word it holds."""
    p = packing(dtype)
    if p == 1:
        return jax.lax.bitcast_convert_type(words, dtype)
    bits = 32 // p
    lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, words.ndim - 1)
    shift = ((lane & (p - 1)) * bits).astype(jnp.uint32)
    elem = (words >> shift) & np.uint32((1 << bits) - 1)
    return jax.lax.bitcast_convert_type(
        elem.astype(jnp.uint16 if p == 2 else jnp.uint8), dtype)


def _lane_word_ids(shape, p: int):
    """Word offset of every element of a row-major tile relative to its
    first word: the element's flat offset over the packing ``p``."""
    flat = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(shape))):
        flat = flat + (jax.lax.broadcasted_iota(jnp.uint32, shape, ax)
                       * np.uint32(stride))
        stride *= shape[ax]
    return flat >> np.uint32(p.bit_length() - 1)


def _is_slot(shape, slot0, slot):
    """Mask of the tile elements in cache slot ``slot`` (rows are
    slots ``slot0, slot0 + 1, ...``)."""
    return slot0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) == slot


def corrupt_kv_tile(x, word0, base_ref, thr_ref, *, num_blocks: int,
                    n_cand: int, seed: int, method: str,
                    words_per_row_log2: int, ecc: bool, slot0=None,
                    clean_slot=None, words_log2: int = BLOCK_WORDS_LOG2):
    """Read-path corruption of one K/V tile whose leading axis is cache
    slots (rows are leaf-contiguous, the last axis holds whole words).

    ``word0`` (traced scalar): leaf word offset of the tile's first
    element.  ``base_ref``/``thr_ref``: the
    leaf's arena block tables (SMEM refs inside a kernel, arrays in the
    oracle) at ``words_log2`` granularity -- whole arena blocks by
    default, single KV pages for a page-granular placement.
    ``clean_slot``: optional traced slot index whose row keeps its
    stored (store-buffer) value; ``slot0`` is the slot index of the
    tile's first row.
    """
    p = packing(x.dtype)
    u = _lane_words(x)
    word0 = word0.astype(jnp.uint32)
    off = word0 + _lane_word_ids(u.shape, p)
    j0 = (word0 >> np.uint32(words_log2)).astype(jnp.int32)
    wid, thr = select_block_tables(off, base_ref, thr_ref, j0=j0,
                                   n_cand=n_cand, num_blocks=num_blocks,
                                   words_log2=words_log2)
    if ecc:
        assert (x.shape[-1] // p) % 2 == 0, "ECC needs whole codewords"
        out, _, _ = arena_ecc_word_events(
            u, wid, thr, seed=seed, words_per_row_log2=words_per_row_log2,
            stride=p)
    else:
        out = apply_masks(u, wid, thr, seed=seed, method=method,
                          words_per_row_log2=words_per_row_log2)
    if clean_slot is not None:
        out = jnp.where(_is_slot(u.shape, slot0, clean_slot), u, out)
    return _lane_elems(out, x.dtype)


def _flash_tile_update(q_ref, k_t, v_t, pos_t, q_pos, acc_ref, m_ref,
                       l_ref, *, scale, causal, window, kh, g, d, bkv):
    """One flash-decode accumulator update over a (bkv, KH, D) tile.

    Shared op-for-op by the contiguous and the paged decode kernels, so
    both emit bit-identical outputs on the same tile sequence -- the
    contract that makes a paged serving cache token-equivalent to the
    contiguous per-request cache.  Returns ``(acc, l_new)`` for the
    caller's final normalization.
    """
    q = q_ref[0, 0].astype(jnp.float32) * scale      # (H, D)
    qr = q.reshape(kh, g, d)
    kf = k_t.astype(jnp.float32)
    vf = v_t.astype(jnp.float32)
    # (KH, G, D) x (bkv, KH, D) -> (KH, G, bkv), KH batched
    s = jax.lax.dot_general(qr, kf, (((2,), (2,)), ((0,), (1,))))

    delta = q_pos - pos_t                            # (1, bkv)
    mask = jnp.zeros((1, bkv), jnp.float32)
    if causal:
        mask = jnp.where(delta < 0, NEG_INF, mask)
    if window > 0:
        mask = jnp.where(delta >= window, NEG_INF, mask)
    mask = jnp.where(pos_t < 0, NEG_INF, mask)       # empty ring slots
    s = s + mask[None]

    m_prev = m_ref[...].reshape(kh, g)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    acc = acc_ref[...].reshape(kh, g, d) * corr[..., None]
    # (KH, G, bkv) x (bkv, KH, D) -> (KH, G, D), KH batched
    acc = acc + jax.lax.dot_general(p, vf, (((2,), (0,)), ((0,), (1,))))
    l_new = l_ref[...].reshape(kh, g) * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc.reshape(acc_ref.shape)
    m_ref[...] = m_new.reshape(m_ref.shape)
    l_ref[...] = l_new.reshape(l_ref.shape)
    return acc, l_new


def _decode_kernel(kbase_ref, kthr_ref, vbase_ref, vthr_ref, offs_ref,
                   misc_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, causal, window, bkv,
                   kh, g, d, seed, method, words_per_row_log2, ecc,
                   inject, k_wps, v_wps, k_cand, v_cand, k_blocks,
                   v_blocks, length, words_log2):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nkv = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_t = k_ref[0]                       # (bkv, KH, D)
    v_t = v_ref[0]
    pos_t = pos_ref[0, 0]                # (1, bkv) int32, may carry faults

    if inject:
        # Leaf word offset of this tile: the layer's slice offset
        # (prefetched: period-stacked leaves shift per scan index) plus
        # (b * L + ki * bkv) slots into the (B, L, KH, D) buffer.
        lslot = (b * length + ki * bkv).astype(jnp.uint32)
        kw = dict(seed=seed, method=method,
                  words_per_row_log2=words_per_row_log2, ecc=ecc,
                  slot0=ki * bkv, clean_slot=misc_ref[0],
                  words_log2=words_log2)
        k_t = corrupt_kv_tile(
            k_t, offs_ref[0] + lslot * np.uint32(k_wps), kbase_ref,
            kthr_ref, num_blocks=k_blocks, n_cand=k_cand, **kw)
        v_t = corrupt_kv_tile(
            v_t, offs_ref[1] + lslot * np.uint32(v_wps), vbase_ref,
            vthr_ref, num_blocks=v_blocks, n_cand=v_cand, **kw)

    acc, l_new = _flash_tile_update(
        q_ref, k_t, v_t, pos_t, misc_ref[1], acc_ref, m_ref, l_ref,
        scale=scale, causal=causal, window=window, kh=kh, g=g, d=d,
        bkv=bkv)

    @pl.when(ki == nkv - 1)
    def _finish():
        out = acc / jnp.maximum(l_new[..., None], 1e-30)
        o_ref[0, 0] = out.reshape(kh * g, d).astype(o_ref.dtype)


def faulty_decode_attention(q, k, v, pos, *, q_pos, k_tables, v_tables,
                            k_word0, v_word0, causal: bool = True,
                            window: int = 0, scale=None, seed: int,
                            method: str, words_per_row_log2: int,
                            ecc: bool, inject: bool, clean_slot=None,
                            bkv=None, interpret=None,
                            words_log2: int = BLOCK_WORDS_LOG2):
    """Decode attention over a ring cache with read-path injection.

    q: (B, 1, H, D) -- the decode token's query in model layout.
    k, v: (B, L, KH, D) -- the cache buffers in their *stored* layout.
    pos: (B, L) int32 -- absolute position per slot (-1 = empty).
    q_pos: traced scalar, the decode token's absolute position.
    k_tables / v_tables: (block_base, block_thr) arena tables for the
    cache leaf (thresholds already gathered at the current, possibly
    traced, voltage), at ``words_log2`` granularity -- arena blocks by
    default, single KV pages when the request's cache is physically
    paged.  k_word0 / v_word0: traced word offset of this (B, L, KH, D)
    slice within its leaf (stacked-layer leaves).
    clean_slot: traced slot index exempt from corruption (the slot the
    current token was just written to), or None.

    Returns (B, 1, H, D) in v.dtype.
    """
    b, sq, h, d = q.shape
    _, length, kh, _ = k.shape
    assert sq == 1, "read-path kernel is decode-specialized (S == 1)"
    g = h // kh
    scale = float(d ** -0.5 if scale is None else scale)
    if interpret is None:
        interpret = default_interpret()

    k_wps = kv_words_per_slot(kh, d, k.dtype)
    v_wps = kv_words_per_slot(kh, d, v.dtype)
    if bkv is None:
        bkv = pick_bkv(length, max(k_wps, v_wps))
    assert length % bkv == 0, (length, bkv)
    nkv = length // bkv

    k_base, k_thr = k_tables
    v_base, v_thr = v_tables
    gran = 1 << words_log2
    k_cand = -(-bkv * k_wps // gran) + 1
    v_cand = -(-bkv * v_wps // gran) + 1
    offs = jnp.stack([jnp.asarray(k_word0), jnp.asarray(v_word0)]
                     ).astype(jnp.uint32)
    clean = jnp.int32(-1) if clean_slot is None else clean_slot
    misc = jnp.stack([jnp.asarray(clean, jnp.int32),
                      jnp.asarray(q_pos, jnp.int32)])

    body = functools.partial(
        _decode_kernel, scale=scale, causal=causal, window=window, bkv=bkv,
        kh=kh, g=g, d=d, seed=seed, method=method,
        words_per_row_log2=words_per_row_log2, ecc=ecc, inject=inject,
        k_wps=k_wps, v_wps=v_wps, k_cand=k_cand, v_cand=v_cand,
        k_blocks=int(k_base.shape[0]), v_blocks=int(v_base.shape[0]),
        length=length, words_log2=words_log2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, h, d), lambda b_, k_, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bkv, kh, d),
                         lambda b_, k_, *_: (b_, k_, 0, 0)),
            pl.BlockSpec((1, bkv, kh, d),
                         lambda b_, k_, *_: (b_, k_, 0, 0)),
            # positions as (B, nkv, 1, bkv): the block's last two dims
            # are then whole array dims for any bkv (a page is 8 slots)
            pl.BlockSpec((1, 1, 1, bkv), lambda b_, k_, *_: (b_, k_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, d),
                               lambda b_, k_, *_: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), v.dtype),
        grid_spec=grid_spec,
        interpret=bool(interpret),
        name="faulty_decode_attention",
    )(k_base, k_thr, v_base, v_thr, offs, misc, q, k, v,
      pos.reshape(b, nkv, 1, bkv))


# ---------------------------------------------------------------------------
# Paged variant: batched decode over a page-pool cache
# ---------------------------------------------------------------------------


def corrupt_page_tile(x, base, thr_row, *, seed: int, method: str,
                      words_per_row_log2: int, ecc: bool, slot0=None,
                      clean_slot=None, with_counts: bool = False):
    """Read-path corruption of one K/V tile that is a single physical
    page (leading axis: cache slots): every word shares one threshold
    row and the physical ids are ``base`` plus the word's offset inside
    the page.

    Same mask math as :func:`corrupt_kv_tile` (which resolves the same
    base/row through the candidate selects), so a paged tile corrupts
    bit-identically to the contiguous kernel reading the same physical
    words.

    ``with_counts`` (ECC only) additionally returns the tile's
    corrected-codeword count -- the SECDED events the hardware would
    report for free while the read happens; the clean slot's codewords
    are excluded exactly like its corruption is.
    """
    p = packing(x.dtype)
    u = _lane_words(x)
    wid = jnp.asarray(base, jnp.uint32) + _lane_word_ids(u.shape, p)
    corr_count = None
    if ecc:
        assert (x.shape[-1] // p) % 2 == 0, "ECC needs whole codewords"
        out, corr, _ = arena_ecc_word_events(
            u, wid, thr_row, seed=seed,
            words_per_row_log2=words_per_row_log2, stride=p)
        if with_counts:
            if clean_slot is not None:
                corr = corr & ~_is_slot(u.shape, slot0, clean_slot)
            corr_count = count_codewords(corr, stride=p)
    else:
        assert not with_counts, "telemetry counts require ECC"
        out = apply_masks(u, wid, thr_row, seed=seed, method=method,
                          words_per_row_log2=words_per_row_log2)
    if clean_slot is not None:
        out = jnp.where(_is_slot(u.shape, slot0, clean_slot), u, out)
    tile = _lane_elems(out, x.dtype)
    if with_counts:
        return tile, corr_count
    return tile


def _paged_kernel(ptab_ref, qpos_ref, kbase_ref, kthr_ref, vbase_ref,
                  vthr_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  *rest, scale, causal, window, ps, kh, g, d, seed,
                  method, words_per_row_log2, ecc, inject, length,
                  telemetry):
    if telemetry:
        telem_ref, acc_ref, m_ref, l_ref = rest
    else:
        telem_ref, (acc_ref, m_ref, l_ref) = None, rest
    si = pl.program_id(0)
    pi = pl.program_id(1)
    npg = pl.num_programs(1)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_t = k_ref[0]                       # (ps, KH, D): one physical page
    v_t = v_ref[0]
    pos_t = pos_ref[0]                   # (1, ps) int32, may carry faults
    q_pos = qpos_ref[si]

    if inject:
        pid = ptab_ref[si, pi]
        # The freshly written slot never round-tripped through
        # undervolted HBM this step (store-buffer exemption).
        k_thr = tuple(kthr_ref[pid, c] for c in range(fm.NUM_THR_COLS))
        v_thr = tuple(vthr_ref[pid, c] for c in range(fm.NUM_THR_COLS))
        kw = dict(seed=seed, method=method,
                  words_per_row_log2=words_per_row_log2, ecc=ecc,
                  slot0=pi * ps, clean_slot=q_pos % length,
                  with_counts=telemetry)
        k_t = corrupt_page_tile(k_t, kbase_ref[pid], k_thr, **kw)
        v_t = corrupt_page_tile(v_t, vbase_ref[pid], v_thr, **kw)
        if telemetry:
            (k_t, k_corr), (v_t, v_corr) = k_t, v_t
            telem_ref[si, pi] = k_corr + v_corr
    elif telemetry:
        telem_ref[si, pi] = jnp.zeros((), jnp.int32)

    acc, l_new = _flash_tile_update(
        q_ref, k_t, v_t, pos_t, q_pos, acc_ref, m_ref, l_ref,
        scale=scale, causal=causal, window=window, kh=kh, g=g, d=d,
        bkv=ps)

    @pl.when(pi == npg - 1)
    def _finish():
        out = acc / jnp.maximum(l_new[..., None], 1e-30)
        o_ref[0, 0] = out.reshape(kh * g, d).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, pos_pool, page_table, *,
                           q_pos, k_tables, v_tables, causal: bool = True,
                           window: int = 0, scale=None, seed: int,
                           method: str, words_per_row_log2: int,
                           ecc: bool, inject: bool, telemetry: bool = False,
                           interpret=None):
    """Batched decode attention over a *paged* ring cache.

    The continuous-batching scheduler's kernel: every serving slot
    attends over its own logical ring cache whose tiles live in pool
    pages.  Page tables arrive as scalar-prefetch operands and drive
    the BlockSpec index maps, so K/V tiles are gathered page-by-page
    straight from the pool buffer -- and corrupted in VMEM as they
    load, addressed by the page's physical base word and threshold row
    (one dynamic-scalar SMEM read each; a page never straddles arena
    blocks, so no candidate selects are needed at all).

    q: (S, 1, H, D) -- one decode query per serving slot.
    k_pool, v_pool: (num_pages, PS, KH, D) -- this layer's page pool.
    pos_pool: (num_pages, PS) int32 -- paged absolute positions.
    page_table: (S, n_lp) int32 -- physical page of each slot's
    logical page (inactive slots point at the pool's scratch page).
    The logical ring length is *derived* from the table width
    (``n_lp * PS``), which is what makes the kernel window-modular:
    a sliding-window leaf hands in the leading ``window // PS`` table
    entries and the ring arithmetic (slot = pos % length, store-buffer
    clean-slot exemption included) lands on the window ring, while
    full-length leaves pass their whole table.  One kernel, both
    layouts.
    q_pos: (S,) int32 -- per-slot absolute decode position.
    k_tables / v_tables: (page_base, page_thr) for this layer's leaf
    slice, thresholds gathered at the current (possibly traced)
    voltage.

    ``telemetry`` (ECC read path only) appends an (S, n_lp) int32
    output: corrected-codeword counts per (slot, logical page) -- the
    SECDED correction events the memory controller reports for free on
    real hardware.  Still one launch: the counts are a second output
    tile of the same kernel, never an extra pass.

    Returns (S, 1, H, D) in v.dtype; with ``telemetry`` a tuple of
    (out, counts).
    """
    if telemetry and not (ecc and inject):
        raise ValueError("telemetry output requires ecc=True, inject=True")
    s, sq, h, d = q.shape
    n, ps, kh, _ = k_pool.shape
    assert sq == 1, "paged kernel is decode-specialized (S == 1)"
    if isinstance(seed, jax.core.Tracer):
        raise TypeError(
            "paged_decode_attention seed must be a static Python int: "
            "the hash-stream draws are folded into the kernel body at "
            "trace time (per-plane seeds included).  Per-shard fault "
            "maps get distinct seeds by specializing one branch per "
            "shard (lax.switch over shard index), never by tracing the "
            "seed")
    n_lp = page_table.shape[1]
    length = n_lp * ps
    g = h // kh
    scale = float(d ** -0.5 if scale is None else scale)
    if interpret is None:
        interpret = default_interpret()

    k_base, k_thr = k_tables
    v_base, v_thr = v_tables
    body = functools.partial(
        _paged_kernel, scale=scale, causal=causal, window=window, ps=ps,
        kh=kh, g=g, d=d, seed=seed, method=method,
        words_per_row_log2=words_per_row_log2, ecc=ecc, inject=inject,
        length=length, telemetry=telemetry)
    out_specs = pl.BlockSpec((1, 1, h, d), lambda s_, p_, *_: (s_, 0, 0, 0))
    out_shape = jax.ShapeDtypeStruct((s, 1, h, d), v_pool.dtype)
    if telemetry:
        # whole (S, n_lp) count array resident in SMEM: one scalar
        # store per grid step
        out_specs = (out_specs, pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((s, n_lp), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(s, n_lp),
        in_specs=[
            pl.BlockSpec((1, 1, h, d), lambda s_, p_, *_: (s_, 0, 0, 0)),
            pl.BlockSpec((1, ps, kh, d),
                         lambda s_, p_, ptab, *_: (ptab[s_, p_], 0, 0, 0)),
            pl.BlockSpec((1, ps, kh, d),
                         lambda s_, p_, ptab, *_: (ptab[s_, p_], 0, 0, 0)),
            pl.BlockSpec((1, 1, ps),
                         lambda s_, p_, ptab, *_: (ptab[s_, p_], 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        body,
        out_shape=out_shape,
        grid_spec=grid_spec,
        interpret=bool(interpret),
        name="paged_decode_attention",
    )(page_table, jnp.asarray(q_pos, jnp.int32), k_base, k_thr,
      v_base, v_thr, q, k_pool, v_pool, pos_pool.reshape(n, 1, ps))
