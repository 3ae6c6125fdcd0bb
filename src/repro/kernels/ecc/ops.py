"""Public jit'd wrapper around the fused inject+ECC kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitflip.bitflip import BLOCK_LANES, BLOCK_WORDS
from repro.kernels.bitflip.ops import default_interpret
from repro.kernels.ecc import ref as _ref
from repro.kernels.ecc.ecc import ecc_pallas


@functools.partial(jax.jit, static_argnames=(
    "thresholds", "seed", "base_word", "interpret", "use_ref"))
def _ecc_jit(data_u32, *, thresholds, seed, base_word, interpret, use_ref):
    n = data_u32.shape[0]
    if use_ref:
        pad2 = (-n) % 2
        padded = (jnp.concatenate([data_u32, jnp.zeros((pad2,), jnp.uint32)])
                  if pad2 else data_u32)
        out, bad = _ref.inject_and_correct_u32_ref(
            padded, thresholds=thresholds, seed=seed, base_word=base_word)
        return out[:n], bad
    pad = (-n) % BLOCK_WORDS
    padded = (jnp.concatenate([data_u32, jnp.zeros((pad,), jnp.uint32)])
              if pad else data_u32)
    out, bad = ecc_pallas(padded.reshape(-1, BLOCK_LANES),
                          thresholds=thresholds, seed=seed,
                          base_word=base_word, interpret=interpret)
    # Padded (zero) words can only contribute stuck-at-1 hits; their
    # codewords are beyond the tensor and their corrections are sliced
    # off, but their counts must not be: restrict by recomputing? No --
    # padding lives in the tensor's aligned allocation slot, so counting
    # its uncorrectable events is consistent with physical reality.
    return out.reshape(-1)[:n], jnp.sum(bad)


def inject_and_correct_u32(data_u32: jax.Array, *, thresholds, seed: int,
                           base_word: int = 0, interpret=None,
                           use_ref: bool = False):
    """Apply stuck-at faults + SECDED correction to a flat uint32 array.

    Returns (corrected array, uncorrectable codeword count).
    """
    if interpret is None:
        interpret = default_interpret()
    return _ecc_jit(data_u32, thresholds=thresholds, seed=int(seed),
                    base_word=int(base_word), interpret=bool(interpret),
                    use_ref=bool(use_ref))
