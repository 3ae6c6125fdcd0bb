"""Public jit'd wrapper around the bitflip Pallas kernel.

Handles dtype <-> uint32 views, padding to kernel-block multiples, method
dispatch (fast word-hit path vs. exact bitwise path), and interpret-mode
fallback on CPU.  The allocator aligns physical placements to BLOCK_WORDS
so the padded tail of one tensor never aliases the next tensor's physical
words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.faultmap import KernelThresholds
from repro.kernels.bitflip import ref as _ref
from repro.kernels.bitflip.bitflip import BLOCK_LANES, BLOCK_WORDS, bitflip_pallas

# Above this per-bit rate the one-stuck-bit-per-word approximation is off
# by more than ~1.6% and we switch to the exact bitwise path.
WORD_PATH_MAX_RATE = 1e-3


def default_interpret() -> bool:
    """Pallas interpret-mode default: interpret everywhere but TPU.

    The one place the kernels' wrappers consult the backend: on a TPU
    every kernel compiles through Mosaic; the CPU (tests) interprets.
    """
    return jax.default_backend() != "tpu"


def pick_method(thresholds: KernelThresholds) -> str:
    worst = max(thresholds.p01_weak, thresholds.p10_weak,
                thresholds.p01_strong, thresholds.p10_strong)
    return "word" if worst <= WORD_PATH_MAX_RATE else "bitwise"


def to_u32(x: jax.Array):
    """Flatten any-dtype array to a uint32 view + recovery metadata."""
    flat = x.reshape(-1)
    itemsize = x.dtype.itemsize
    if itemsize == 4:
        u32 = jax.lax.bitcast_convert_type(flat, jnp.uint32)
        return u32, (x.shape, x.dtype, flat.shape[0], 1)
    if itemsize == 2:
        n = flat.shape[0]
        pad = (-n) % 2
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
        u16 = jax.lax.bitcast_convert_type(flat, jnp.uint16)
        u32 = jax.lax.bitcast_convert_type(u16.reshape(-1, 2), jnp.uint32)
        return u32, (x.shape, x.dtype, n, 2)
    if itemsize == 1:
        n = flat.shape[0]
        pad = (-n) % 4
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
        u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8)
        u32 = jax.lax.bitcast_convert_type(u8.reshape(-1, 4), jnp.uint32)
        return u32, (x.shape, x.dtype, n, 4)
    raise NotImplementedError(f"itemsize {itemsize} for dtype {x.dtype}")


def from_u32(u32: jax.Array, meta):
    """Inverse of :func:`to_u32`: uint32 view -> original shape/dtype."""
    shape, dtype, n, packing = meta
    if packing == 1:
        return jax.lax.bitcast_convert_type(u32, dtype).reshape(shape)
    lanes = jax.lax.bitcast_convert_type(
        u32, jnp.uint16 if packing == 2 else jnp.uint8)  # (m, packing)
    flat = jax.lax.bitcast_convert_type(lanes.reshape(-1), dtype)
    return flat[:n].reshape(shape)


# Backwards-compatible aliases from when these were module-private.
_to_u32 = to_u32
_from_u32 = from_u32


@functools.partial(jax.jit, static_argnames=(
    "thresholds", "seed", "base_word", "method", "interpret", "use_ref"))
def _inject_u32_jit(data_u32, *, thresholds, seed, base_word, method,
                    interpret, use_ref):
    n = data_u32.shape[0]
    if use_ref:
        return _ref.inject_u32_ref(data_u32, thresholds=thresholds,
                                   seed=seed, base_word=base_word,
                                   method=method)
    pad = (-n) % BLOCK_WORDS
    padded = (jnp.concatenate([data_u32, jnp.zeros((pad,), jnp.uint32)])
              if pad else data_u32)
    out = bitflip_pallas(padded.reshape(-1, BLOCK_LANES),
                         thresholds=thresholds, seed=seed,
                         base_word=base_word, method=method,
                         interpret=interpret)
    return out.reshape(-1)[:n]


def inject_u32(data_u32: jax.Array, *, thresholds: KernelThresholds,
               seed: int, base_word: int = 0, method: str = "auto",
               interpret=None, use_ref: bool = False) -> jax.Array:
    """Apply stuck-at faults to a flat uint32 array (physical words
    [base_word, base_word + n))."""
    if method == "auto":
        method = pick_method(thresholds)
    if interpret is None:
        interpret = default_interpret()
    return _inject_u32_jit(data_u32, thresholds=thresholds, seed=int(seed),
                           base_word=int(base_word), method=method,
                           interpret=bool(interpret), use_ref=bool(use_ref))


def inject(x: jax.Array, *, thresholds: KernelThresholds, seed: int,
           base_word: int = 0, method: str = "auto", interpret=None,
           use_ref: bool = False) -> jax.Array:
    """Apply stuck-at faults to an arbitrary-dtype tensor in place of its
    physical words.  Returns a tensor of the same shape/dtype."""
    u32, meta = to_u32(x)
    out = inject_u32(u32, thresholds=thresholds, seed=seed,
                     base_word=base_word, method=method,
                     interpret=interpret, use_ref=use_ref)
    return from_u32(out, meta)
