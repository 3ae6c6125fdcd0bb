"""Pure-jnp oracle for the fused inject+ECC kernel.

Emulates SECDED(72,64): each 64-bit codeword (two consecutive u32 words)
carries 8 parity bits.  Under undervolting the parity bits are as
vulnerable as data bits.  Behavioral emulation (we hold the pre-fault
data, so no syndrome algebra is needed):

  * 0 faults in the codeword  -> data unchanged
  * 1 fault (data or parity)  -> corrected, i.e. data restored
  * >=2 faults                -> uncorrectable: faulted data passes
                                 through and the event is counted

Word-path injection only: ECC is useful exactly in the low-rate regime
(p <= ~1e-3); near array collapse every codeword is multi-fault and ECC
buys nothing (the paper's all-faulty region).

Two formulations of the same integer math:
:func:`ecc_codeword_events` pairs the words of a codeword with a
reshape (the jnp oracle: arena and scrub references, the legacy
per-segment path), and :func:`ecc_word_events` pairs them with lane
rotations and keeps the flags per lane (what the Mosaic kernels run,
since a lane-splitting reshape does not lower there).  Tests hold the
two to bit equality.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing as H
from repro.kernels.bitflip.bitflip import lane_xor
from repro.kernels.bitflip.ref import _weak_rows, word_masks

STREAM_PARITY = 0x94D049BB

_U0 = np.uint32(0)
_U1 = np.uint32(1)


def popcount32(v):
    """SWAR popcount on uint32 lanes (portable into Pallas)."""
    v = v - ((v >> _U1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> np.uint32(2)) & np.uint32(0x33333333))
    v = (v + (v >> np.uint32(4))) & np.uint32(0x0F0F0F0F)
    return (v * np.uint32(0x01010101)) >> np.uint32(24)


def ecc_codeword_events(data_u32, wid, seed: int, *, q01_weak, q01_strong,
                        q10_weak, q10_strong, weak_row_q,
                        par_q_weak, par_q_strong, words_per_row_log2: int):
    """Returns (corrected_u32, corrected_bool, uncorrectable_bool).

    Per-codeword event flags: ``corrected`` marks single-fault codewords
    the SECDED logic silently repaired (the telemetry signal -- these
    cost nothing today but witness a row drifting weak), ``uncorrectable``
    marks multi-fault codewords whose faulted data passes through.

    ``data_u32``/``wid`` must have an even number of elements along the
    last axis (codewords are adjacent word pairs).  Threshold operands
    are uint32 scalars or per-word arrays, static or traced.
    """
    mask01, mask10 = word_masks(
        wid, seed,
        q01_weak=q01_weak, q01_strong=q01_strong,
        q10_weak=q10_weak, q10_strong=q10_strong,
        weak_row_q=weak_row_q, words_per_row_log2=words_per_row_log2)
    mask10 = mask10 & ~mask01
    faulted = (data_u32 | mask01) & ~mask10
    fault_bits = faulted ^ data_u32

    shape = data_u32.shape
    pair = shape[:-1] + (shape[-1] // 2, 2)
    fb = fault_bits.reshape(pair)
    counts = popcount32(fb[..., 0]) + popcount32(fb[..., 1])

    # Parity-bit faults: one draw per codeword, weak-row aware.
    cw_wid = wid.reshape(pair)[..., 0]
    cw_id = cw_wid >> _U1
    weak = _weak_rows(cw_wid, seed, _cw_vals(weak_row_q, pair),
                      words_per_row_log2)
    q = jnp.where(weak, _cw_vals(par_q_weak, pair), _cw_vals(par_q_strong, pair))
    par_hit = H.hash_stream(seed, STREAM_PARITY, cw_id) < q
    counts = counts + par_hit.astype(jnp.uint32)

    corrected = counts == 1
    uncorrectable = counts >= 2
    keep_faulty = jnp.repeat(uncorrectable[..., None], 2, axis=-1).reshape(shape)
    out = jnp.where(keep_faulty, faulted, data_u32)
    return out, corrected, uncorrectable


def _cw_vals(v, pair_shape):
    """Reduce a per-word threshold operand to per-codeword (scalars pass
    through; arrays take the first word of each pair)."""
    v = jnp.asarray(v)
    if v.ndim == 0:
        return v
    return jnp.broadcast_to(v, pair_shape[:-2] + (pair_shape[-2] * 2,)) \
        .reshape(pair_shape)[..., 0]


def ecc_word_events(data_u32, wid, seed: int, *, q01_weak, q01_strong,
                    q10_weak, q10_strong, weak_row_q, par_q_weak,
                    par_q_strong, words_per_row_log2: int, stride: int = 1):
    """Lane-local SECDED: returns (corrected_u32, corrected_bool,
    uncorrectable_bool), the flags per lane.

    Every lane of the last axis holds one u32 word; a word spans
    ``stride`` adjacent lanes (1 for packed words; the element count
    per word when a kernel keeps a 16-bit tile element-wise and copies
    each word into its elements' lanes).  A codeword is two adjacent
    words, so its partner word sits ``stride`` lanes away and both
    codeword flags repeat over the codeword's ``2 * stride`` lanes.
    The lane-rotation twin of :func:`ecc_codeword_events` (no
    lane-splitting reshape, so it lowers inside Mosaic kernels): with
    ``stride`` 1, ``corrected_u32`` is the same and each flag is the
    oracle's flag of the lane's codeword.  Threshold operands are
    uint32 scalars or per-lane arrays, static or traced.
    """
    mask01, mask10 = word_masks(
        wid, seed,
        q01_weak=q01_weak, q01_strong=q01_strong,
        q10_weak=q10_weak, q10_strong=q10_strong,
        weak_row_q=weak_row_q, words_per_row_log2=words_per_row_log2)
    mask10 = mask10 & ~mask01
    faulted = (data_u32 | mask01) & ~mask10
    fault_bits = faulted ^ data_u32
    counts = popcount32(fault_bits) + popcount32(
        lane_xor(fault_bits, stride))

    # Parity-bit faults: one draw per codeword (keyed by its first
    # word), weak-row aware.
    lane = jax.lax.broadcasted_iota(jnp.int32, data_u32.shape,
                                    data_u32.ndim - 1)
    first = (lane & stride) == 0

    def first_word(v):
        v = jnp.asarray(v)
        if v.ndim == 0 or v.shape[-1] == 1:
            return v
        v = jnp.broadcast_to(v, data_u32.shape)
        return jnp.where(first, v, lane_xor(v, stride))

    cw_wid = first_word(wid)
    weak = _weak_rows(cw_wid, seed, first_word(weak_row_q),
                      words_per_row_log2)
    q = jnp.where(weak, first_word(par_q_weak), first_word(par_q_strong))
    par_hit = H.hash_stream(seed, STREAM_PARITY, cw_wid >> _U1) < q
    counts = counts + par_hit.astype(jnp.uint32)

    corrected = counts == 1
    uncorrectable = counts >= 2
    out = jnp.where(uncorrectable, faulted, data_u32)
    return out, corrected, uncorrectable


def thr_kwargs(thr):
    """KernelThresholds -> the uint32 threshold operands of
    :func:`ecc_codeword_events` and :func:`ecc_word_events`."""
    return dict(
        q01_weak=np.uint32(thr.q01_weak), q01_strong=np.uint32(thr.q01_strong),
        q10_weak=np.uint32(thr.q10_weak), q10_strong=np.uint32(thr.q10_strong),
        weak_row_q=np.uint32(thr.weak_row_q),
        par_q_weak=np.uint32(thr.par_q_weak),
        par_q_strong=np.uint32(thr.par_q_strong),
        words_per_row_log2=thr.words_per_row_log2)


def inject_and_correct_u32_ref(data_u32, *, thresholds, seed: int,
                               base_word: int):
    data_u32 = jnp.asarray(data_u32, dtype=jnp.uint32)
    n = data_u32.shape[0]
    assert n % 2 == 0, "ECC reference needs an even word count"
    wid = np.uint32(base_word) + jnp.arange(n, dtype=jnp.uint32)
    out, _, bad = ecc_codeword_events(data_u32, wid, seed,
                                      **thr_kwargs(thresholds))
    return out, jnp.sum(bad.astype(jnp.int32))
