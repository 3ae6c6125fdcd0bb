"""Pallas TPU kernel: stuck-at fault injection for undervolted HBM.

This is the framework's perf-critical hot path: every tensor group placed
in an unsafe memory domain is passed through this kernel each step, so it
must stream at HBM bandwidth with one read-modify-write.  The kernel is
tile-parallel over (8, 512)-word VMEM blocks (16 KiB -- MXU/VPU aligned:
8 sublanes x 512 = 4x128 lanes), computes a counter-based hash per word,
and ORs/ANDNs the resulting stuck-at masks into the data.

Two entry points:

  * :func:`bitflip_pallas` -- the legacy single-segment kernel: one
    contiguous physical run, thresholds folded in as static Python ints
    (a recompile per distinct (voltage, PC) pair).
  * :func:`arena_bitflip_pallas` -- the arena engine's kernel: a grid
    over *all* blocks of a memory domain, with each block's physical
    base word and threshold-table row delivered as scalar-prefetch
    operands (SMEM).  One launch injects a whole multi-leaf, multi-PC
    domain, and because thresholds are runtime data, a voltage sweep
    never retraces or recompiles.

The mask math is shared with :mod:`repro.kernels.bitflip.ref` (pure jnp
integer ops), so kernel and oracle are bit-exact by construction; the
tests assert exact equality over shape/dtype/method sweeps in interpret
mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import faultmap as fm
from repro.kernels.bitflip import ref as _ref

BLOCK_SUBLANES = 8
BLOCK_LANES = 512
BLOCK_WORDS = BLOCK_SUBLANES * BLOCK_LANES  # 4096 words = 16 KiB
BLOCK_WORDS_LOG2 = BLOCK_WORDS.bit_length() - 1
assert 1 << BLOCK_WORDS_LOG2 == BLOCK_WORDS


def _kernel(x_ref, o_ref, *, thresholds, seed, base_word, method):
    x = x_ref[...]
    # Physical word index of every element in this block.
    i = pl.program_id(0).astype(jnp.uint32)
    sub = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    wid = (np.uint32(base_word) + i * np.uint32(BLOCK_WORDS)
           + sub * np.uint32(x.shape[1]) + lane)
    if method == "word":
        mask01, mask10 = _ref._word_masks(wid, seed, thresholds)
    else:
        mask01, mask10 = _ref._bitwise_masks(wid, seed, thresholds)
    mask10 = mask10 & ~mask01
    o_ref[...] = (x | mask01) & ~mask10


def bitflip_pallas(data2d: jax.Array, *, thresholds, seed: int,
                   base_word: int, method: str, interpret: bool):
    """Apply stuck-at faults to a (M, 512) uint32 array, M % 8 == 0."""
    m, n = data2d.shape
    assert n == BLOCK_LANES and m % BLOCK_SUBLANES == 0, (m, n)
    body = functools.partial(_kernel, thresholds=thresholds, seed=seed,
                             base_word=base_word, method=method)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint32),
        grid=(m // BLOCK_SUBLANES,),
        in_specs=[pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                               lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                               lambda i: (i, 0)),
        interpret=interpret,
        name="bitflip",
    )(data2d)


def block_word_ids(base, shape):
    """Physical word index of every element of one (sublane, lane) block
    whose first word sits at physical address ``base`` (traced uint32)."""
    sub = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return base + sub * np.uint32(shape[1]) + lane


def lane_xor(x, k: int):
    """``x[..., j ^ k]`` for every lane ``j`` of the last axis (``k`` a
    power of two dividing the axis): the partner lane of a packed pair.

    Built from two lane rotations and a select, the shuffle the TPU
    vector unit has, so it lowers inside Mosaic kernels (a lane-
    splitting reshape such as ``(..., n) -> (..., n // 2, 2)`` does
    not); outside a kernel the rotation is ``jnp.roll``.  Which
    rotation supplies lane ``j ^ k`` is read off a rotated lane iota,
    so the result does not depend on the rotation's direction.
    """
    ax = x.ndim - 1
    n = x.shape[ax]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
    fwd = _roll(x, k, ax)
    src = _roll(lane, k, ax)
    return jnp.where(src == (lane ^ k), fwd, _roll(x, n - k, ax))


# jit gives the rotation an evaluation path outside kernels (the
# primitive only has lowerings); Mosaic inlines the nested call.
_roll = jax.jit(pltpu.roll, static_argnums=(1, 2))


def arena_masks(wid, thr_row, *, seed: int, method: str,
                words_per_row_log2: int):
    """Stuck-at masks from one traced threshold-table row.

    ``thr_row`` indexes like a (NUM_THR_COLS,) uint32 vector -- inside
    the kernel it is a row of the scalar-prefetch SMEM operand; in the
    oracle it is a row of the gathered per-block table.  Shared by the
    arena kernels and the arena oracle so both are bit-exact.
    """
    if method == "word":
        return _ref.word_masks(
            wid, seed,
            q01_weak=thr_row[fm.COL_Q01_WEAK],
            q01_strong=thr_row[fm.COL_Q01_STRONG],
            q10_weak=thr_row[fm.COL_Q10_WEAK],
            q10_strong=thr_row[fm.COL_Q10_STRONG],
            weak_row_q=thr_row[fm.COL_WEAK_ROW_Q],
            words_per_row_log2=words_per_row_log2)
    if method == "bitwise":
        return _ref.bitwise_masks(
            wid, seed,
            t01_weak=thr_row[fm.COL_T01_WEAK],
            t01_strong=thr_row[fm.COL_T01_STRONG],
            t10_weak=thr_row[fm.COL_T10_WEAK],
            t10_strong=thr_row[fm.COL_T10_STRONG],
            weak_row_q=thr_row[fm.COL_WEAK_ROW_Q],
            words_per_row_log2=words_per_row_log2)
    raise ValueError(f"unknown method {method!r}")


def apply_masks(x_u32, wid, thr_row, *, seed: int, method: str,
                words_per_row_log2: int):
    """Corrupt one uint32 tile in place of its physical words.

    The read-modify-write at the heart of every injection path, exposed
    as a tile-level function so other Pallas kernels (the fused
    flash-attention read path) can corrupt data already resident in
    VMEM.  ``thr_row`` entries may be scalars (one block) or per-word
    arrays (a tile straddling blocks).
    """
    mask01, mask10 = arena_masks(wid, thr_row, seed=seed, method=method,
                                 words_per_row_log2=words_per_row_log2)
    mask10 = mask10 & ~mask01
    return (x_u32 | mask01) & ~mask10


def select_block_tables(off, base_ref, thr_ref, *, j0, n_cand: int,
                        num_blocks: int,
                        words_log2: int = BLOCK_WORDS_LOG2):
    """Physical word ids + per-word threshold columns for a tile of leaf
    word offsets ``off`` that may straddle several arena blocks.

    TPUs cannot gather SMEM with a vector index, so the per-word lookup
    ``block_base[off >> 12]`` is rewritten as ``n_cand`` dynamic-scalar
    reads (the same access pattern the arena kernels use) followed by
    vector selects: ``j0`` (traced scalar) is the first arena block the
    tile can touch and ``n_cand`` (static) bounds how many consecutive
    blocks it can span.  Works identically on SMEM refs inside a Pallas
    kernel and on plain jnp arrays (the oracle / incremental paths).

    ``words_log2`` sets the table granularity: the default addresses
    whole arena blocks; the paged serving cache passes its (smaller,
    block-dividing) page size so the same candidate-select machinery
    resolves per-*page* physical bases and threshold rows.

    Returns ``(wid, thr_cols)`` with ``wid`` the per-word physical ids
    and ``thr_cols`` a NUM_THR_COLS tuple of per-word uint32 arrays.
    """
    off = off.astype(jnp.uint32)
    jvec = off >> np.uint32(words_log2)
    rem = off & np.uint32((1 << words_log2) - 1)
    base = jnp.zeros_like(off)
    thr = [jnp.zeros_like(off) for _ in range(fm.NUM_THR_COLS)]
    j0 = j0.astype(jnp.int32) if hasattr(j0, "astype") else jnp.int32(j0)
    for jj in range(n_cand):
        cand = j0 + jj                       # traced scalar block index
        idx = jnp.minimum(cand, num_blocks - 1)   # clamp the SMEM read
        hit = jvec == cand.astype(jnp.uint32)     # never true if cand OOB
        base = base + jnp.where(hit, base_ref[idx], np.uint32(0))
        for c in range(fm.NUM_THR_COLS):
            thr[c] = thr[c] + jnp.where(hit, thr_ref[idx, c], np.uint32(0))
    return base + rem, tuple(thr)


def _arena_kernel(base_ref, thr_ref, x_ref, o_ref, *, seed, method,
                  words_per_row_log2):
    i = pl.program_id(0)
    x = x_ref[...]
    wid = block_word_ids(base_ref[i], x.shape)
    # Individual scalar SMEM reads (dynamic row, static column) -- the
    # TPU-safe access pattern for prefetched scalars.
    thr_row = tuple(thr_ref[i, c] for c in range(fm.NUM_THR_COLS))
    o_ref[...] = apply_masks(x, wid, thr_row, seed=seed, method=method,
                             words_per_row_log2=words_per_row_log2)


def arena_bitflip_pallas(arena2d: jax.Array, block_base: jax.Array,
                         block_thr: jax.Array, *, seed: int, method: str,
                         words_per_row_log2: int, interpret: bool):
    """Inject a whole domain arena in one fused pass.

    ``arena2d``: (num_blocks * 8, 512) uint32 -- every leaf of the domain
    packed block-aligned.  ``block_base``: (num_blocks,) uint32 physical
    base word per block.  ``block_thr``: (num_blocks, NUM_THR_COLS)
    uint32 threshold-table rows (the per-block PC's row at the current,
    possibly traced, voltage).  One ``pallas_call``, grid over blocks.
    """
    m, n = arena2d.shape
    assert n == BLOCK_LANES and m % BLOCK_SUBLANES == 0, (m, n)
    num_blocks = m // BLOCK_SUBLANES
    assert block_base.shape == (num_blocks,), block_base.shape
    assert block_thr.shape == (num_blocks, fm.NUM_THR_COLS), block_thr.shape
    body = functools.partial(_arena_kernel, seed=seed, method=method,
                             words_per_row_log2=words_per_row_log2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                               lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                               lambda i, *_: (i, 0)),
    )
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="arena_bitflip",
    )(block_base, block_thr, arena2d)
