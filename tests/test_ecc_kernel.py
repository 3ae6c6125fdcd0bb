"""Per-kernel tests: fused inject+ECC kernel vs. oracle + behavior."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.faultmap import PAPER_MAP_SEED, FaultMap
from repro.core.hbm import VCU128
from repro.kernels.bitflip import ops as bops
from repro.kernels.ecc import ops as eops

FMAP = FaultMap.from_seed(VCU128, seed=PAPER_MAP_SEED)


@pytest.mark.parametrize("n", [4096, 8192, 70000, 100])
@pytest.mark.parametrize("v", [0.93, 0.90, 0.88])
def test_kernel_matches_ref(n, v):
    thr = FMAP.thresholds(v, pc=5)
    x = jnp.asarray(np.random.RandomState(1).randint(
        0, 2**31, size=n, dtype=np.int64).astype(np.uint32))
    k, badk = eops.inject_and_correct_u32(x, thresholds=thr, seed=5)
    r, badr = eops.inject_and_correct_u32(x, thresholds=thr, seed=5,
                                          use_ref=True)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    assert int(badk) == int(badr)


def test_ecc_corrects_most_faults():
    """SECDED removes all single-bit-per-codeword faults; in the word-path
    regime nearly every faulty codeword has exactly one stuck bit."""
    thr = FMAP.thresholds(0.89, pc=18)
    n = 1 << 20
    x = jnp.zeros((n,), jnp.uint32)
    raw = bops.inject_u32(x, thresholds=thr, seed=5)
    corrected, bad = eops.inject_and_correct_u32(x, thresholds=thr, seed=5)
    raw_faults = int(jnp.sum(raw != x))
    residual = int(jnp.sum(corrected != x))
    assert raw_faults > 50
    assert residual < raw_faults * 0.2
    # residual faulty words come only from uncorrectable codewords
    assert residual <= 2 * int(bad)


def test_guardband_noop():
    thr = FMAP.thresholds(1.0, pc=0)
    x = jnp.asarray(np.arange(8192), jnp.uint32)
    out, bad = eops.inject_and_correct_u32(x, thresholds=thr, seed=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    assert int(bad) == 0


def test_uncorrectable_grows_with_depth():
    n = 1 << 19
    x = jnp.zeros((n,), jnp.uint32)
    bads = []
    for v in (0.90, 0.88, 0.86):
        thr = FMAP.thresholds(v, pc=18)
        _, bad = eops.inject_and_correct_u32(x, thresholds=thr, seed=5)
        bads.append(int(bad))
    assert bads[0] <= bads[1] <= bads[2]
    assert bads[2] > bads[0]


@pytest.mark.parametrize("k", [1, 2, 4])
def test_lane_xor_is_partner_lane(k):
    """``lane_xor`` (two lane rotations and a select) returns lane
    ``j ^ k`` for every lane ``j``."""
    from repro.kernels.bitflip.bitflip import lane_xor
    x = np.arange(8 * 512, dtype=np.int32).reshape(8, 512)
    lanes = np.arange(512)
    np.testing.assert_array_equal(np.asarray(lane_xor(jnp.asarray(x), k)),
                                  x[:, lanes ^ k])


def _per_word_thresholds(v, n_words):
    """uint32 threshold operands that differ between 64-word blocks
    (two pseudo-channels alternating), one entry per packed word."""
    from repro.kernels.ecc.ref import thr_kwargs
    a = thr_kwargs(FMAP.thresholds(v, pc=18))
    b = thr_kwargs(FMAP.thresholds(v, pc=5))
    sel = (np.arange(n_words) // 64) % 2 == 1
    return {key: (a[key] if key == "words_per_row_log2"
                  else np.where(sel, a[key], b[key]).astype(np.uint32))
            for key in a}


@pytest.mark.parametrize("v", [0.86, 0.78])
@pytest.mark.parametrize("stride", [1, 2])
def test_word_events_match_codeword_oracle(stride, v):
    """The lane-rotation SECDED the kernels run (``ecc_word_events``)
    is bit-identical to the reshape-paired oracle
    (``ecc_codeword_events``) on a bf16 tile: packed words (stride 1),
    and the element-wise layout the decode kernels keep, in which each
    lane holds its element's whole word (stride 2)."""
    from repro.kernels.ecc.ref import ecc_codeword_events, ecc_word_events
    from repro.kernels.flash_attention import faulty
    rows, elems = 16, 1024
    x = jnp.asarray(np.random.RandomState(3).standard_normal(
        (rows, elems)).astype(np.float32)).astype(jnp.bfloat16)
    words = faulty._tile_to_u32(x)                      # (rows, 512)
    n_words = words.shape[1]
    wid = (np.uint32(7 << 20) + np.arange(rows * n_words, dtype=np.uint32)
           ).reshape(rows, n_words)
    thr = _per_word_thresholds(v, n_words)
    ref_out, ref_corr, ref_bad = ecc_codeword_events(
        words, jnp.asarray(wid), 5, **thr)
    assert int(ref_corr.sum()) > 0 and int(ref_bad.sum()) > 0, (
        "tile too clean to exercise both flags")
    if stride == 1:
        lanes, lane_wid = words, wid
        lane_thr = thr
    else:
        lanes = faulty._lane_words(x)                   # (rows, 1024)
        np.testing.assert_array_equal(
            np.asarray(lanes), np.repeat(np.asarray(words), 2, axis=-1))
        lane_wid = np.repeat(wid, 2, axis=-1)
        lane_thr = {key: (val if key == "words_per_row_log2"
                          else np.repeat(val, 2)) for key, val in thr.items()}
    out, corr, bad = ecc_word_events(lanes, jnp.asarray(lane_wid), 5,
                                     stride=stride, **lane_thr)
    per_lane = 2 * stride
    np.testing.assert_array_equal(
        np.asarray(corr), np.repeat(np.asarray(ref_corr), per_lane, axis=-1))
    np.testing.assert_array_equal(
        np.asarray(bad), np.repeat(np.asarray(ref_bad), per_lane, axis=-1))
    if stride == 1:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    else:
        got = faulty._lane_elems(out, jnp.bfloat16)
        want = faulty._tile_from_u32(ref_out, jnp.bfloat16, x.shape)
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint16), np.asarray(want).view(np.uint16))
