"""Public wrapper: layout conversion, padding, GQA plumbing, interpret
fallback for the flash-attention kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitflip.ops import default_interpret
from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.flash_attention import (
    DEFAULT_BKV, DEFAULT_BQ, flash_attention_pallas)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "interpret", "use_ref", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, bq: int = DEFAULT_BQ,
                    bkv: int = DEFAULT_BKV, interpret=None,
                    use_ref: bool = False):
    """q: (B, H, S, D); k, v: (B, K, S, D).  Returns (B, H, S, D)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = float(d ** -0.5 if scale is None else scale)
    if use_ref:
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale)
    if interpret is None:
        interpret = default_interpret()
    bq_ = min(bq, sq)
    bkv_ = min(bkv, sk)
    pad_q = (-sq) % bq_
    pad_k = (-sk) % bkv_
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 scale=scale, bq=bq_, bkv=bkv_,
                                 interpret=bool(interpret))
    return out[:, :, :sq]
