"""Pallas TPU kernel: flash attention forward (causal/window, GQA).

Grid (B, H, num_q_blocks, num_kv_blocks): the kv dimension is innermost
and sequential; the running (acc, m, l) streaming-softmax state lives in
VMEM scratch and survives across kv steps of the same q block.  Block
shapes are MXU-aligned ((BQ, D) x (BKV, D) contractions with D a
multiple of 128 for full-speed MXU issue).  GQA is expressed in the
BlockSpec index maps: the kv operands map head h -> h // group, so no
repeated K/V ever materializes.

This is the serving/prefill hot path; training uses the XLA chunked
path (models/layers.py) whose custom VJP implements the same algorithm.
The p-block tensors here never leave VMEM -- on the XLA path they round-
trip HBM, which is exactly the memory-term gap the §Perf log quantifies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BKV = 256


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, bq, bkv, sk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (BKV, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BKV)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    delta = q_pos - k_pos
    mask = jnp.zeros((bq, bkv), jnp.float32)
    if causal:
        mask = jnp.where(delta < 0, NEG_INF, mask)
    if window > 0:
        mask = jnp.where(delta >= window, NEG_INF, mask)
    # mask kv padding beyond the true sequence length
    mask = jnp.where(k_pos >= sk, NEG_INF, mask)
    s = s + mask

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool, window: int,
                           scale: float, bq: int = DEFAULT_BQ,
                           bkv: int = DEFAULT_BKV, interpret: bool):
    """q: (B, H, Sq, D); k/v: (B, K, Sk, D), Sq % bq == Sk % bkv == 0."""
    b, h, sq, d = q.shape
    _, kh, sk, _ = k.shape
    g = h // kh
    nq = sq // bq
    nkv = sk // bkv
    body = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, bq=bq, bkv=bkv, sk=sk)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), v.dtype),
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, q_, k_, g_=g: (b_, h_ // g_, k_, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, q_, k_, g_=g: (b_, h_ // g_, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        scratch_shapes=[
            # VMEM scratch: streaming-softmax state per q block
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
