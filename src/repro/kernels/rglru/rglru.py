"""Pallas TPU kernel: chunked RG-LRU linear-recurrence scan.

Grid (B_tiles, n_chunks) with the chunk dimension sequential: the
carried state h lives in VMEM scratch and flows across chunk steps.
Inside a chunk the recurrence is evaluated with a log-depth associative
scan over the (CHUNK, R) tile -- VPU-friendly elementwise ops on
(8, 128)-aligned registers, one HBM read per input element and one
write per output element (the recurrence is strictly memory-bound, so
this kernel runs at HBM roofline by construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 256
DEFAULT_BTILE = 8


def _kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, h_ref, *, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)        # (BT, C, R)
    b = b_ref[...].astype(jnp.float32)
    # fold carried state into the first step
    b = b.at[:, 0].add(a[:, 0] * h_ref[...])

    def combine(prev, nxt):
        a1, b1 = prev
        a2, b2 = nxt
        return a1 * a2, b1 * a2 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    o_ref[...] = h.astype(o_ref.dtype)
    h_ref[...] = h[:, -1]

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hlast_ref[...] = h_ref[...].astype(hlast_ref.dtype)


def rglru_pallas(a, b, h0, *, chunk: int = DEFAULT_CHUNK,
                 btile: int = DEFAULT_BTILE, interpret: bool):
    """a, b: (B, S, R); h0: (B, R).  S % chunk == 0, B % btile == 0."""
    bsz, s, r = a.shape
    btile = min(btile, bsz)
    n_chunks = s // chunk
    nb = bsz // btile
    body = functools.partial(_kernel, n_chunks=n_chunks)
    out, hlast = pl.pallas_call(
        body,
        out_shape=(jax.ShapeDtypeStruct((bsz, s, r), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, r), jnp.float32)),
        grid=(nb, n_chunks),
        in_specs=[
            pl.BlockSpec((btile, chunk, r), lambda i, j: (i, j, 0)),
            pl.BlockSpec((btile, chunk, r), lambda i, j: (i, j, 0)),
            pl.BlockSpec((btile, r), lambda i, j: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((btile, chunk, r), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((btile, r), lambda i, j: (i, 0))),
        scratch_shapes=[pltpu.VMEM((btile, r), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, b, h0)
    return out, hlast
