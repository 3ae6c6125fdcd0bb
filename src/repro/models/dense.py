"""Dense GQA transformer family: llama3 / yi / gemma3 (5:1 local:global).

One scan-friendly layer kind ("global" / "local" differ only in the
sliding-window mask and cache length), period-stacked via models/stack.
Caches are ring buffers: local slots allocate only ``window`` entries --
for gemma3 that cuts decode-cache memory ~6x vs. a uniform cache (this
is also a §Perf lever).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cache as C
from repro.models import layers as L
from repro.models import stack as S
from repro.models.base import ArchConfig, ParamSpec

# ---------------------------------------------------------------------------
# attention + MLP slot (shared with hybrid/vlm/whisper families)
# ---------------------------------------------------------------------------


def attn_mlp_specs(cfg: ArchConfig, kind: str) -> Dict[str, ParamSpec]:
    d, dt = cfg.d_model, cfg.dtype
    out = {
        "ln1": ParamSpec((d,), (None,), dt, "zeros"),
        "wq": ParamSpec((d, cfg.q_dim), ("embed", "heads"), dt),
        "wk": ParamSpec((d, cfg.kv_dim), ("embed", "kv"), dt),
        "wv": ParamSpec((d, cfg.kv_dim), ("embed", "kv"), dt),
        "wo": ParamSpec((cfg.q_dim, d), ("heads", "embed"), dt),
        "ln2": ParamSpec((d,), (None,), dt, "zeros"),
    }
    if cfg.mlp_gated:
        out["wg"] = ParamSpec((d, cfg.d_ff), ("embed", "mlp"), dt)
        out["wu"] = ParamSpec((d, cfg.d_ff), ("embed", "mlp"), dt)
        out["wd"] = ParamSpec((cfg.d_ff, d), ("mlp", "embed"), dt)
    else:
        out["w1"] = ParamSpec((d, cfg.d_ff), ("embed", "mlp"), dt)
        out["w2"] = ParamSpec((cfg.d_ff, d), ("mlp", "embed"), dt)
    return out


def mlp_apply(cfg: ArchConfig, p, h):
    if cfg.mlp_gated:
        return L.gated_mlp(h, p["wg"], p["wu"], p["wd"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(
        jnp.einsum("bsd,df->bsf", h, p["w1"])).astype(h.dtype), p["w2"])


def cache_len(cfg: ArchConfig, kind: str, max_len: int) -> int:
    if kind == "local" and cfg.window > 0:
        return min(cfg.window, max_len)
    return max_len


def attn_cache_specs(cfg: ArchConfig, kind: str, batch: int,
                     max_len: int) -> Dict[str, ParamSpec]:
    ln = cache_len(cfg, kind, max_len)
    kv = (batch, ln, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": ParamSpec(kv, ("batch", "cache_seq", "kv_heads", "head_dim"),
                       cfg.dtype, "zeros"),
        "v": ParamSpec(kv, ("batch", "cache_seq", "kv_heads", "head_dim"),
                       cfg.dtype, "zeros"),
        # positions written so far; -1 = empty (kv_valid mask)
        "pos": ParamSpec((batch, ln), ("batch", "cache_seq"), jnp.int32,
                         "zeros"),
    }


def _qkv(cfg, p, h, positions):
    b, s, _ = h.shape
    q = jnp.einsum("bsd,dq->bsq", h, p["wq"]).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    k = jnp.einsum("bsd,dq->bsq", h, p["wk"]).reshape(
        b, s, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("bsd,dq->bsq", h, p["wv"]).reshape(
        b, s, cfg.n_kv_heads, cfg.head_dim)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_mlp_apply(cfg: ArchConfig, kind: str, p, x, cache,
                   positions, mode: str, pos=None, fault_ctx=None,
                   slot_ref=None):
    """One transformer block.  mode: train | prefill | decode.
    kind: global | local (sliding window) | enc (bidirectional).

    ``fault_ctx`` (decode only): a read-path injection context
    (:mod:`repro.serving.readpath`); when it covers this slot, decode
    attention runs through the fused kernel that corrupts K/V tiles as
    they are loaded from the undervolted cache domain.  ``slot_ref`` is
    the ``(slot key, period index)`` pair from the stack."""
    window = cfg.window if kind == "local" else 0
    causal = kind != "enc"
    with jax.named_scope("attention"):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, h, positions)

        if mode in ("train",) or kind == "enc":
            out = L.attention(q, k, v, q_positions=positions,
                              k_positions=positions, causal=causal,
                              window=window)
            new_cache = cache
        elif mode == "prefill":
            new_cache = C.ring_fill(cache, {"k": k, "v": v}, positions)
            out = L.attention(q, k, v, q_positions=positions,
                              k_positions=positions, causal=causal,
                              window=window)
        else:  # decode: S == 1
            covered = (fault_ctx is not None and slot_ref is not None
                       and fault_ctx.covers(slot_ref[0]))
            if covered:
                # The ctx owns both the ring write for its cache layout
                # (contiguous ring_update, or the paged pool scatter) and
                # the fused attention over it; under the paged scheduler
                # ``pos`` is the per-serving-slot position vector.
                new_cache = fault_ctx.update(slot_ref[0], cache,
                                             {"k": k, "v": v}, pos)
                out = fault_ctx.attend(slot_ref[0], slot_ref[1], q, new_cache,
                                       q_pos=pos, causal=causal, window=window)
            else:
                new_cache = C.ring_write(cache, {"k": k, "v": v}, pos)
                valid = new_cache["pos"] >= 0
                out = L.attention(q, new_cache["k"], new_cache["v"],
                                  q_positions=positions,
                                  k_positions=new_cache["pos"], causal=causal,
                                  window=window, kv_valid=valid)

        b, s, _, _ = out.shape
        x = x + jnp.einsum("bsq,qd->bsd", out.reshape(b, s, -1), p["wo"])
    with jax.named_scope("mlp"):
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(cfg, p, h2)
    return x, new_cache


# ---------------------------------------------------------------------------
# whole-model functions
# ---------------------------------------------------------------------------


def layout(cfg: ArchConfig) -> S.PeriodLayout:
    period = len(cfg.pattern) if cfg.pattern else 1
    return S.layout_from_kinds(cfg.layer_kinds(), period)


def param_specs(cfg: ArchConfig) -> Dict[str, Any]:
    return {
        # input table: replicated rows, embed-dim sharded => cheap gather
        # (a 2D-sharded table forces SPMD to all-gather it per lookup);
        # untied output head: (vocab->model, embed->data) => sharded logits
        "embed": ParamSpec((cfg.vocab, cfg.d_model), (None, "embed"),
                           cfg.dtype),
        "unembed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                             cfg.dtype),
        "stack": S.stack_specs(layout(cfg),
                               functools.partial(attn_mlp_specs, cfg)),
        "ln_f": ParamSpec((cfg.d_model,), (None,), cfg.dtype, "zeros"),
    }


def cache_specs(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, Any]:
    return S.stack_cache_specs(
        layout(cfg),
        lambda kind: attn_cache_specs(cfg, kind, batch, max_len))


def _run_stack(cfg, params, x, positions, cache, mode, pos=None,
               fault_ctx=None):
    if fault_ctx is None:
        apply_slot = lambda kind, p, xx, c: attn_mlp_apply(
            cfg, kind, p, xx, c, positions, mode, pos)
        with_ref = False
    else:
        apply_slot = lambda kind, p, xx, c, ref: attn_mlp_apply(
            cfg, kind, p, xx, c, positions, mode, pos,
            fault_ctx=fault_ctx, slot_ref=ref)
        with_ref = True
    x, new_cache = S.apply_stack(params["stack"], x, layout(cfg), apply_slot,
                                 cache=cache, remat=(cfg.remat == "block"),
                                 with_slot_ref=with_ref)
    return L.rms_norm(x, params["ln_f"], cfg.norm_eps), new_cache


def forward_train(params, batch, cfg: ArchConfig, dist=None):
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = L.embed(tokens, params["embed"])
    x, _ = _run_stack(cfg, params, x, positions, None, "train")
    loss = L.lm_head_loss(x[:, :-1], params["unembed"], tokens[:, 1:],
                          batch.get("loss_mask", None), dist)
    return loss, {"loss": loss}


def prefill(params, batch, cfg: ArchConfig, max_len: int, dist=None,
            prompt_len=None):
    """``prompt_len`` (traced int32, <= tokens.shape[1]): the real
    prompt length when ``tokens`` is padded to a length bucket.  Pad
    tokens sit at positions >= prompt_len, so real queries mask them
    causally and the real rows' numerics are bit-identical to an
    unpadded prefill; the cache rows the padding wrote are reset to
    the init state (k/v=0, pos=-1) and logits are taken at column
    prompt_len - 1 instead of -1.  Requires uniform full-length caches
    (see ``SUPPORTS_PADDED_PREFILL``)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = C.init_cache(cache_specs(cfg, b, max_len))
    x = L.embed(tokens, params["embed"])
    x, cache = _run_stack(cfg, params, x, positions, cache, "prefill")
    if prompt_len is None:
        logits = L.unembed(x[:, -1:], params["unembed"])
        return logits[:, 0], cache
    plen = jnp.asarray(prompt_len, jnp.int32)
    last = jax.lax.dynamic_index_in_dim(x, plen - 1, axis=1,
                                        keepdims=True)
    logits = L.unembed(last, params["unembed"])

    def scrub(leaf):
        ln = leaf["pos"].shape[-1]
        pad = jnp.arange(ln, dtype=jnp.int32) >= plen
        out = dict(leaf)
        out["pos"] = jnp.where(pad, -1, leaf["pos"])
        for k in ("k", "v"):
            mask = pad.reshape((1,) * (leaf[k].ndim - 3) + (ln, 1, 1))
            out[k] = jnp.where(mask, jnp.zeros((), leaf[k].dtype), leaf[k])
        return out

    cache = jax.tree_util.tree_map(
        scrub, cache,
        is_leaf=lambda t: isinstance(t, dict) and "pos" in t)
    return logits[:, 0], cache


def decode_step(params, cache, batch, pos, cfg: ArchConfig, dist=None,
                fault_ctx=None):
    """batch["tokens"]: (B, C); pos: scalar int32 absolute position
    (C=1, returns (B, vocab) logits), a (B,) per-row vector (state-arena
    serving slots at heterogeneous positions; rows with pos < 0 skip
    their ring write), or a (B, C) per-token position array (mixed
    prefill-chunk/decode serving step, returns full (B, C, vocab)
    logits -- the caller picks each slot's sample column).

    ``fault_ctx``: optional read-path injection context -- attention
    layers it covers corrupt their K/V tiles at load time instead of
    requiring the cache to be re-injected between steps."""
    tokens = batch["tokens"]
    b, c = tokens.shape
    positions = C.decode_positions(pos, b, c)
    x = L.embed(tokens, params["embed"])
    x, cache = _run_stack(cfg, params, x, positions, cache, "decode",
                          pos=pos, fault_ctx=fault_ctx)
    logits = L.unembed(x, params["unembed"])
    return (logits[:, 0] if c == 1 else logits), cache


# The serving engine's fused read-path injection understands this
# family's cache layout (ring k/v/pos leaves, slot axis "cache_seq").
SUPPORTS_READ_PATH = True
# The continuous-batching scheduler can page this family's cache: the
# decode step threads a paged ctx through attn_mlp_apply (per-slot
# position vectors, pool-page ring writes, batched paged attention).
SUPPORTS_PAGED = True
# prefill() accepts a traced ``prompt_len`` over padded token buckets
# (positions >= prompt_len are causally dead and scrubbed from the
# cache), letting the serving engine compile O(log max_len) prefill
# buckets instead of one program per distinct prompt length.
SUPPORTS_PADDED_PREFILL = True
