"""Pallas TPU kernel: fused fault-injection + SECDED(72,64) correction.

Fusing the undervolt fault model with the ECC behavioral model keeps the
mitigation path at one HBM read-modify-write per step -- the same budget
as unprotected injection (a beyond-paper optimization; the paper treats
ECC as future mitigation work and cites [57]).

Block layout matches the bitflip kernel: (8, 512) uint32 VMEM tiles,
grid over blocks.  Each block additionally stores its codeword counts
into a per-block int32 vector that stays in SMEM for the whole call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import faultmap as fm
from repro.kernels.bitflip.bitflip import (BLOCK_LANES, BLOCK_SUBLANES,
                                           BLOCK_WORDS, block_word_ids)
from repro.kernels.ecc import ref as _ref


def _kernel(x_ref, o_ref, bad_ref, *, thresholds, seed, base_word):
    x = x_ref[...]
    i = pl.program_id(0).astype(jnp.uint32)
    sub = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    wid = (np.uint32(base_word) + i * np.uint32(BLOCK_WORDS)
           + sub * np.uint32(x.shape[1]) + lane)
    out, _, bad = _ref.ecc_word_events(x, wid, seed,
                                       **_ref.thr_kwargs(thresholds))
    o_ref[...] = out
    bad_ref[pl.program_id(0)] = count_codewords(bad)


def ecc_pallas(data2d: jax.Array, *, thresholds, seed: int, base_word: int,
               interpret: bool):
    """(M, 512) uint32, M % 8 == 0 -> (corrected, per-block bad counts)."""
    m, n = data2d.shape
    assert n == BLOCK_LANES and m % BLOCK_SUBLANES == 0, (m, n)
    grid = (m // BLOCK_SUBLANES,)
    body = functools.partial(_kernel, thresholds=thresholds, seed=seed,
                             base_word=base_word)
    return pl.pallas_call(
        body,
        out_shape=(jax.ShapeDtypeStruct((m, n), jnp.uint32),
                   jax.ShapeDtypeStruct((grid[0],), jnp.int32)),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                               lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                                lambda i: (i, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        interpret=interpret,
    )(data2d)


def count_codewords(flags, stride: int = 1):
    """Number of codewords flagged in a per-lane flag tile of
    :func:`arena_ecc_word_events` (each codeword counted once, at its
    first lane)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, flags.shape, flags.ndim - 1)
    first = (lane & (2 * stride - 1)) == 0
    return jnp.sum((flags & first).astype(jnp.int32))


def _row_kwargs(thr_row, words_per_row_log2: int):
    """Threshold-table row (one entry per ``fm.COL_*``) -> the operands
    of the SECDED emulations in :mod:`repro.kernels.ecc.ref`."""
    return dict(q01_weak=thr_row[fm.COL_Q01_WEAK],
                q01_strong=thr_row[fm.COL_Q01_STRONG],
                q10_weak=thr_row[fm.COL_Q10_WEAK],
                q10_strong=thr_row[fm.COL_Q10_STRONG],
                weak_row_q=thr_row[fm.COL_WEAK_ROW_Q],
                par_q_weak=thr_row[fm.COL_PAR_Q_WEAK],
                par_q_strong=thr_row[fm.COL_PAR_Q_STRONG],
                words_per_row_log2=words_per_row_log2)


def arena_ecc_word_events(x, wid, thr_row, *, seed: int,
                          words_per_row_log2: int, stride: int = 1):
    """Fused inject+correct+telemetry for one tile from a traced
    threshold row, flags per lane (:func:`repro.kernels.ecc.ref.
    ecc_word_events`).  The form the kernels run: the arena ECC kernel,
    the decode kernels' ECC read path and their telemetry counts."""
    return _ref.ecc_word_events(
        x, wid, seed, stride=stride,
        **_row_kwargs(thr_row, words_per_row_log2))


def arena_ecc_events(x, wid, thr_row, *, seed: int,
                     words_per_row_log2: int):
    """Fused inject+correct+telemetry from a traced threshold row on
    packed words: returns (out, corrected_bool, uncorrectable_bool) per
    codeword (:func:`repro.kernels.ecc.ref.ecc_codeword_events`).  The
    jnp oracle form: the arena oracle, ``corrupt_words`` and the scrub
    telemetry."""
    return _ref.ecc_codeword_events(
        x, wid, seed, **_row_kwargs(thr_row, words_per_row_log2))


def _arena_kernel(base_ref, thr_ref, x_ref, o_ref, bad_ref, corr_ref, *,
                  seed, words_per_row_log2):
    i = pl.program_id(0)
    x = x_ref[...]
    wid = block_word_ids(base_ref[i], x.shape)
    thr_row = tuple(thr_ref[i, c] for c in range(fm.NUM_THR_COLS))
    out, corr, bad = arena_ecc_word_events(
        x, wid, thr_row, seed=seed, words_per_row_log2=words_per_row_log2)
    o_ref[...] = out
    bad_ref[i] = count_codewords(bad)
    corr_ref[i] = count_codewords(corr)


def arena_ecc_pallas(arena2d: jax.Array, block_base: jax.Array,
                     block_thr: jax.Array, *, seed: int,
                     words_per_row_log2: int, interpret: bool):
    """Fused inject+SECDED over a whole domain arena in one pass.

    Same operand contract as ``arena_bitflip_pallas`` plus per-block
    uncorrectable- and corrected-codeword count outputs (the corrected
    counts are the telemetry stream the self-healing loop consumes).
    """
    m, n = arena2d.shape
    assert n == BLOCK_LANES and m % BLOCK_SUBLANES == 0, (m, n)
    num_blocks = m // BLOCK_SUBLANES
    assert block_base.shape == (num_blocks,), block_base.shape
    assert block_thr.shape == (num_blocks, fm.NUM_THR_COLS), block_thr.shape
    body = functools.partial(_arena_kernel, seed=seed,
                             words_per_row_log2=words_per_row_log2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                               lambda i, *_: (i, 0))],
        out_specs=(pl.BlockSpec((BLOCK_SUBLANES, BLOCK_LANES),
                                lambda i, *_: (i, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
    )
    return pl.pallas_call(
        body,
        out_shape=(jax.ShapeDtypeStruct((m, n), jnp.uint32),
                   jax.ShapeDtypeStruct((num_blocks,), jnp.int32),
                   jax.ShapeDtypeStruct((num_blocks,), jnp.int32)),
        grid_spec=grid_spec,
        interpret=interpret,
    )(block_base, block_thr, arena2d)
