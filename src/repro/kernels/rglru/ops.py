"""Public wrapper for the RG-LRU scan kernel: padding + dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitflip.ops import default_interpret
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.rglru.rglru import (DEFAULT_BTILE, DEFAULT_CHUNK,
                                       rglru_pallas)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "use_ref"))
def rglru_scan(a, b, h0, *, chunk: int = DEFAULT_CHUNK, interpret=None,
               use_ref: bool = False):
    """Seeded linear recurrence h_t = a_t h_{t-1} + b_t over axis 1.

    a, b: (B, S, R); h0: (B, R).  Returns (h_seq f32, h_last f32).
    """
    if use_ref:
        return rglru_scan_ref(a, b, h0)
    if interpret is None:
        interpret = default_interpret()
    bsz, s, r = a.shape
    chunk_ = min(chunk, s)
    pad_s = (-s) % chunk_
    btile = min(DEFAULT_BTILE, bsz)
    pad_b = (-bsz) % btile
    if pad_s or pad_b:
        pads3 = ((0, pad_b), (0, pad_s), (0, 0))
        a = jnp.pad(a, pads3)
        b = jnp.pad(b, pads3)
        h0 = jnp.pad(h0, ((0, pad_b), (0, 0)))
    out, hlast = rglru_pallas(a, b, h0, chunk=chunk_, btile=btile,
                              interpret=bool(interpret))
    # padded time steps have a=0,b=0 => h stays 0 after them only if...
    # they sit at the END, so the true h_last is at index s-1.
    hlast_true = out[:bsz, s - 1, :]
    return out[:bsz, :s], hlast_true
