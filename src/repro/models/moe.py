"""DeepSeek-V2 family: MLA attention + fine-grained MoE with shared experts.

MLA (multi-head latent attention) caches only the compressed latent
(kv_lora_rank) plus the decoupled rope key -- 576 values/token for V2 --
and decodes in the *absorbed* form (queries projected into latent space),
so decode reads the small cache instead of materialized per-head K/V.

The routed FFN uses sort-based capacity dispatch inside shard_map:
activations are replicated across the model axis (they already are,
post-TP-all-reduce), every shard selects the tokens routed to its local
experts, computes them, and the combine is a single psum over the model
axis -- expert parallelism with *zero* all-to-all (a TPU-friendly
re-mapping of the usual GPU all-to-all EP; see DESIGN.md).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import cache as C
from repro.models import layers as L
from repro.models import stack as S
from repro.models.base import ArchConfig, ParamSpec
from repro.models.dist import DistContext, ensure

ROUTER_AUX_COEF = 1e-3


# ---------------------------------------------------------------------------
# MLA attention
# ---------------------------------------------------------------------------


def mla_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, dt = cfg.d_model, cfg.dtype
    h, dn, dr = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    kvl = cfg.kv_lora_rank
    out = {
        "ln1": ParamSpec((d,), (None,), dt, "zeros"),
        "w_dkv": ParamSpec((d, kvl), ("embed", None), dt),
        "ln_kv": ParamSpec((kvl,), (None,), dt, "zeros"),
        "w_kpe": ParamSpec((d, dr), ("embed", None), dt),
        "w_uk": ParamSpec((kvl, h, dn), (None, "heads", None), dt),
        "w_uv": ParamSpec((kvl, h, dn), (None, "heads", None), dt),
        "w_o": ParamSpec((h, dn, d), ("heads", None, "embed"), dt),
    }
    if cfg.q_lora_rank:
        out["w_dq"] = ParamSpec((d, cfg.q_lora_rank), ("embed", None), dt)
        out["ln_q"] = ParamSpec((cfg.q_lora_rank,), (None,), dt, "zeros")
        out["w_uq"] = ParamSpec((cfg.q_lora_rank, h, dn + dr),
                                (None, "heads", None), dt)
    else:
        out["w_q"] = ParamSpec((d, h, dn + dr), ("embed", "heads", None), dt)
    return out


def mla_cache_specs(cfg: ArchConfig, batch: int,
                    max_len: int) -> Dict[str, ParamSpec]:
    return {
        "ckv": ParamSpec((batch, max_len, cfg.kv_lora_rank),
                         ("batch", "cache_seq", "kv_lora"), cfg.dtype,
                         "zeros"),
        "kpe": ParamSpec((batch, max_len, cfg.rope_head_dim),
                         ("batch", "cache_seq", None), cfg.dtype, "zeros"),
        "pos": ParamSpec((batch, max_len), ("batch", "cache_seq"),
                         jnp.int32, "zeros"),
    }


def mla_attn(cfg: ArchConfig, p, x, cache, positions, mode, pos=None):
    b, s, _ = x.shape
    h_, dn, dr = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    scale = (dn + dr) ** -0.5
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)

    if cfg.q_lora_rank:
        cq = L.rms_norm(jnp.einsum("bsd,dq->bsq", h, p["w_dq"]), p["ln_q"],
                        cfg.norm_eps)
        q = jnp.einsum("bsq,qhe->bshe", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", h, p["w_q"])
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = L.rope(q_pe, positions, cfg.rope_theta)

    ckv = L.rms_norm(jnp.einsum("bsd,dk->bsk", h, p["w_dkv"]), p["ln_kv"],
                     cfg.norm_eps)
    kpe = L.rope(jnp.einsum("bsd,dr->bsr", h, p["w_kpe"])[:, :, None, :],
                 positions, cfg.rope_theta)[:, :, 0, :]

    if mode in ("train", "prefill"):
        k_nope = jnp.einsum("bsk,khd->bshd", ckv, p["w_uk"])
        vv = jnp.einsum("bsk,khd->bshd", ckv, p["w_uv"])
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kpe[:, :, None, :], (b, s, h_, dr))],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = L.attention(q_full, k_full, vv, q_positions=positions,
                          k_positions=positions, causal=True,
                          softmax_scale=scale)
        new_cache = cache
        if mode == "prefill":
            new_cache = C.ring_fill(cache, {"ckv": ckv, "kpe": kpe},
                                    positions)
    else:  # absorbed decode
        new_cache = C.ring_write(cache, {"ckv": ckv, "kpe": kpe}, pos)
        q_c = jnp.einsum("bshd,khd->bshk", q_nope, p["w_uk"])
        q_cat = jnp.concatenate([q_c, q_pe], axis=-1)       # (B,1,H,kvl+dr)
        k_cat = jnp.concatenate([new_cache["ckv"], new_cache["kpe"]],
                                axis=-1)[:, :, None, :]     # (B,L,1,kvl+dr)
        v_lat = new_cache["ckv"][:, :, None, :]             # (B,L,1,kvl)
        ctx = L.attention(q_cat, k_cat, v_lat, q_positions=positions,
                          k_positions=new_cache["pos"], causal=True,
                          kv_valid=new_cache["pos"] >= 0,
                          softmax_scale=scale)              # (B,1,H,kvl)
        out = jnp.einsum("bshk,khd->bshd", ctx, p["w_uv"])

    return x + jnp.einsum("bshd,hdo->bso", out, p["w_o"]), new_cache


# ---------------------------------------------------------------------------
# Routed MoE FFN (shard_map expert parallelism)
# ---------------------------------------------------------------------------


def moe_ffn_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, dt, e, f = cfg.d_model, cfg.dtype, cfg.n_experts, cfg.d_ff
    shared_f = cfg.n_shared_experts * cfg.d_ff
    return {
        "ln2": ParamSpec((d,), (None,), dt, "zeros"),
        "w_router": ParamSpec((d, e), ("embed", None), jnp.float32),
        "we_g": ParamSpec((e, d, f), ("experts", "embed", None), dt),
        "we_u": ParamSpec((e, d, f), ("experts", "embed", None), dt),
        "we_d": ParamSpec((e, f, d), ("experts", None, "embed"), dt),
        "ws_g": ParamSpec((d, shared_f), ("embed", "mlp"), dt),
        "ws_u": ParamSpec((d, shared_f), ("embed", "mlp"), dt),
        "ws_d": ParamSpec((shared_f, d), ("mlp", "embed"), dt),
    }


def moe_ffn(cfg: ArchConfig, p, x, dist: DistContext):
    """Routed experts + shared experts; returns (y, aux_loss)."""
    e, k = cfg.n_experts, cfg.top_k
    b, s, d = x.shape
    e_loc = e // dist.model_size
    assert e_loc * dist.model_size == e, (e, dist.model_size)

    # Router (replicated over the model axis; tokens sharded over batch).
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # Switch-style load-balance loss.
    frac = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1, 2))
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

    t_loc = (b // int(np.prod([dist.mesh.shape[a]
                               for a in dist.batch_axes]))) * s
    cap = max(1, int(np.ceil(cfg.capacity_factor * t_loc * k / e)))
    if s == 1:
        # Decode capacity must never drop a token: serving batches many
        # requests into one step, and their tokens compete for
        # within-expert rank -- a drop the solo (b=1) replay of the
        # same request wouldn't take breaks bit-exact replay.  Each
        # token's top_k experts are distinct, so t_loc bounds the
        # per-expert load; per-token outputs are independent of cap.
        cap = max(cap, t_loc)

    def local_fn(xl, wl, el, wg, wu, wd):
        j = jax.lax.axis_index(dist.model_axis)
        bl = xl.shape[0]
        t = bl * s
        x2 = xl.reshape(t, d)
        fe = el.reshape(t * k)
        fw = wl.reshape(t * k).astype(x2.dtype)
        e0 = j * e_loc
        loc = jnp.where((fe >= e0) & (fe < e0 + e_loc), fe - e0, e_loc)
        order = jnp.argsort(loc)                      # stable
        se = loc[order]
        rank = jnp.arange(t * k) - jnp.searchsorted(se, se, side="left")
        slot = jnp.where((se < e_loc) & (rank < cap), se * cap + rank,
                         e_loc * cap)
        tok = order // k
        buf = jnp.zeros((e_loc * cap + 1, d), x2.dtype).at[slot].set(x2[tok])
        eb = buf[: e_loc * cap].reshape(e_loc, cap, d)
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, wg,
                                      preferred_element_type=jnp.float32))
        up = jnp.einsum("ecd,edf->ecf", eb, wu,
                        preferred_element_type=jnp.float32)
        ob = jnp.einsum("ecf,efd->ecd", (gate * up).astype(x2.dtype), wd)
        of = jnp.concatenate(
            [ob.reshape(e_loc * cap, d), jnp.zeros((1, d), x2.dtype)])
        contrib = of[slot] * fw[order][:, None]
        y = jnp.zeros((t, d), x2.dtype).at[tok].add(contrib)
        y = jax.lax.psum(y, dist.model_axis)
        return y.reshape(bl, s, d)

    y = jax.shard_map(
        local_fn, mesh=dist.mesh,
        in_specs=(P(dist.batch_axes, None, None),
                  P(dist.batch_axes, None, None),
                  P(dist.batch_axes, None, None),
                  P(dist.model_axis, None, None),
                  P(dist.model_axis, None, None),
                  P(dist.model_axis, None, None)),
        out_specs=P(dist.batch_axes, None, None),
        check_vma=False,
    )(x, top_w, top_e, p["we_g"], p["we_u"], p["we_d"])
    return y, aux


def dense_ffn_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, dt = cfg.d_model, cfg.dtype
    f = cfg.d_ff_dense or 4 * d
    return {
        "ln2": ParamSpec((d,), (None,), dt, "zeros"),
        "wg": ParamSpec((d, f), ("embed", "mlp"), dt),
        "wu": ParamSpec((d, f), ("embed", "mlp"), dt),
        "wd": ParamSpec((f, d), ("mlp", "embed"), dt),
    }


# ---------------------------------------------------------------------------
# whole-model functions
# ---------------------------------------------------------------------------


def slot_specs(cfg: ArchConfig, kind: str) -> Dict[str, Any]:
    out = dict(mla_specs(cfg))
    if kind == "moe":
        out.update(moe_ffn_specs(cfg))
    else:  # densemlp: deepseek's first layer
        out.update(dense_ffn_specs(cfg))
    return out


def layout(cfg: ArchConfig) -> S.PeriodLayout:
    kinds = ("densemlp",) + ("moe",) * (cfg.n_layers - 1)
    return S.layout_from_kinds(kinds, 1, prefix_len=1)


def slot_apply(cfg, dist, kind, p, x, cache, positions, mode, pos,
               aux_acc=None):
    x, new_cache = mla_attn(cfg, p, x, cache, positions, mode, pos)
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "moe":
        routed, aux = moe_ffn(cfg, p, h2, dist)
        shared = L.gated_mlp(h2, p["ws_g"], p["ws_u"], p["ws_d"])
        x = x + routed + shared
    else:
        aux = jnp.zeros((), jnp.float32)
        x = x + L.gated_mlp(h2, p["wg"], p["wu"], p["wd"])
    return x, new_cache, aux


def param_specs(cfg: ArchConfig) -> Dict[str, Any]:
    return {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), (None, "embed"),
                           cfg.dtype),
        "unembed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                             cfg.dtype),
        "stack": S.stack_specs(layout(cfg),
                               functools.partial(slot_specs, cfg)),
        "ln_f": ParamSpec((cfg.d_model,), (None,), cfg.dtype, "zeros"),
    }


def cache_specs(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, Any]:
    return S.stack_cache_specs(
        layout(cfg), lambda kind: mla_cache_specs(cfg, batch, max_len))


def _run_stack(cfg, dist, params, x, positions, cache, mode, pos=None):
    """The scan carry is (activations, aux-loss accumulator)."""

    def apply_slot(kind, p, carry, c):
        xx, aux_sum = carry
        xx, c_new, aux = slot_apply(cfg, dist, kind, p, xx, c, positions,
                                    mode, pos)
        return (xx, aux_sum + aux), c_new

    (x, aux_total), new_cache = S.apply_stack(
        params["stack"], (x, jnp.zeros((), jnp.float32)), layout(cfg),
        apply_slot, cache=cache, remat=(cfg.remat == "block"))
    return L.rms_norm(x, params["ln_f"], cfg.norm_eps), aux_total, new_cache


def forward_train(params, batch, cfg: ArchConfig, dist=None):
    dist = ensure(dist)
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = L.embed(tokens, params["embed"])
    x, aux, _ = _run_stack(cfg, dist, params, x, positions, None, "train")
    xent = L.lm_head_loss(x[:, :-1], params["unembed"], tokens[:, 1:],
                          batch.get("loss_mask", None), dist)
    loss = xent + ROUTER_AUX_COEF * aux
    return loss, {"loss": loss, "xent": xent, "router_aux": aux}


def prefill(params, batch, cfg: ArchConfig, max_len: int, dist=None):
    dist = ensure(dist)
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = C.init_cache(cache_specs(cfg, b, max_len))
    x = L.embed(tokens, params["embed"])
    x, _, cache = _run_stack(cfg, dist, params, x, positions, cache,
                             "prefill")
    logits = L.unembed(x[:, -1:], params["unembed"])
    return logits[:, 0], cache


def decode_step(params, cache, batch, pos, cfg: ArchConfig, dist=None):
    dist = ensure(dist)
    tokens = batch["tokens"]
    b = tokens.shape[0]
    positions = C.decode_positions(pos, b, 1)
    x = L.embed(tokens, params["embed"])
    x, _, cache = _run_stack(cfg, dist, params, x, positions, cache,
                             "decode", pos=pos)
    logits = L.unembed(x, params["unembed"])
    return logits[:, 0], cache


def routing_frequency(params, tokens, cfg: ArchConfig) -> np.ndarray:
    """Per-expert routing frequency over a probe token batch.

    Cheap criticality probe for expert-weight placement: embeds the
    tokens and runs every MoE layer's router on the *embeddings* (the
    true router input is the post-attention residual; the embedding
    approximation keeps the probe O(tokens * d * e) with no cache or
    attention).  Returns a float64 (n_experts,) vector summing to 1 --
    frequently-routed experts are criticality-tiered into shallower
    (safer) arena tiers, rare experts ride the deep cheap tiers.
    """
    x = L.embed(jnp.asarray(tokens, jnp.int32), params["embed"])
    counts = np.zeros(cfg.n_experts, np.float64)
    groups = [g for c in ("prefix", "periods", "rest")
              for g in params["stack"].get(c, {}).values()]
    for grp in groups:
        if "w_router" not in grp:
            continue
        wr = grp["w_router"]  # (layers, d, e) stacked periods or (d, e)
        if wr.ndim == 2:
            wr = wr[None]
        logits = jnp.einsum("bsd,lde->lbse", x.astype(jnp.float32), wr)
        _, top_e = jax.lax.top_k(logits, cfg.top_k)
        hot = np.asarray(top_e).reshape(-1)
        counts += np.bincount(hot, minlength=cfg.n_experts)
    total = counts.sum()
    return counts / total if total else counts + 1.0 / cfg.n_experts
