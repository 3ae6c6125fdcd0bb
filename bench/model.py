"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<name>.json``) holds the model's
published sizes under their ``config.json`` names and the serving
settings it runs at.  This module turns it into the program's
``ArchConfig`` and scheduler, and draws the weights.

Weights belong to the benchmark, not to the program: every leaf is
drawn from ``--seed`` by :func:`leaf` under a key named by the leaf
and its layer, so the plain reference (``bench/reference.py``) draws
the same bits one layer at a time without reading anything the
program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Key of each weight's draw: fold_in(fold_in(seed key, id), layer).
LEAF_IDS = {"embed": 1, "unembed": 2, "ln_f": 3, "ln1": 4, "ln2": 5,
            "wq": 6, "wk": 7, "wv": 8, "wo": 9, "wg": 10, "wu": 11,
            "wd": 12}
# Norm gains are stored as offsets from 1 (the program scales by
# 1 + gamma); a spread of 0.1 keeps that path live in the comparison.
NORM_STD = 0.1


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` up to 2**64."""
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_std(name: str, shape, d_model: int) -> float:
    if name.startswith("ln"):
        return NORM_STD
    if name == "embed":
        return 1.0
    if name == "unembed":
        return 1.0 / math.sqrt(d_model)
    return 1.0 / math.sqrt(shape[0])


def leaf(key, name: str, layer, shape, d_model: int):
    """One layer's slice of weight ``name`` as served (bfloat16).

    Each weight is a random sign times 2**k times a random 7-bit
    mantissa in [1, 2), with k chosen so that the standard deviation
    (2**k x 1.5275) is within a factor of 1.42 of the leaf's.  The bf16
    bits are assembled from random bits with integer operations only,
    so no compiler rewrite of float arithmetic can move them: the
    program's whole-tree draw and the reference's one-leaf draw agree
    bit for bit."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    bits = jax.random.bits(k, shape, jnp.uint32)
    exp = 127 + round(math.log2(leaf_std(name, shape, d_model) / 1.5275))
    b16 = (((bits >> 16) & jnp.uint32(0x8000)) | jnp.uint32(exp << 7)
           | (bits & jnp.uint32(0x7F)))
    return jax.lax.bitcast_convert_type(b16.astype(jnp.uint16),
                                        jnp.bfloat16)


def arch_config(conf: dict):
    """The program's ArchConfig for a dense decoder configuration."""
    from repro.models.base import ArchConfig
    return ArchConfig(
        arch_id=conf["name"], family="dense",
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
    )


def bundle(cfg):
    from repro.models import dense
    from repro.models.base import ArchBundle
    return ArchBundle(cfg=cfg, module=dense)


def draw_params(bndl, cfg, seed: int):
    """Every weight of the program's parameter tree, drawn on the device
    in one jitted call (the seed enters as data, so one program serves
    every seed)."""
    from repro.models.base import ParamSpec
    specs = bndl.module.param_specs(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))

    def draw(key):
        out = []
        for path, spec in flat:
            name = path[-1].key
            stacked = any(getattr(p, "key", None) == "periods"
                          for p in path)
            if stacked:
                n, shape = spec.shape[0], spec.shape[1:]
                keys = jnp.arange(n, dtype=jnp.uint32)
                out.append(jax.vmap(lambda l, s=shape, nm=name: leaf(
                    key, nm, l, s, cfg.d_model))(keys))
            else:
                out.append(leaf(key, name, 0, spec.shape, cfg.d_model))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.block_until_ready(jax.jit(draw)(seed_key(seed)))


def kv_plan(domain: dict):
    """The undervolt plan of the KV cache: every pseudo-channel of the
    chip at the mix's voltage, with or without SECDED."""
    from repro.core.domains import MemoryDomain
    from repro.core.hbm import TPU_V5E
    from repro.training.undervolt import UndervoltPlan
    return UndervoltPlan(
        domains={"kv": MemoryDomain("kv", float(domain["voltage"]),
                                    tuple(range(TPU_V5E.num_pcs)),
                                    ecc=bool(domain["ecc"]))},
        policy={"kv_cache": "kv"}, geometry=TPU_V5E)


def scheduler(bndl, cfg, params, serving: dict, domain: dict):
    """The paged continuous-batching scheduler at the configuration's
    serving settings, greedy, read-path injection."""
    from repro.serving.engine import ServeConfig
    from repro.serving.scheduler import ContinuousBatchingScheduler
    sc = ServeConfig(max_len=int(serving["max_len"]),
                     max_new_tokens=1, temperature=0.0,
                     undervolt=kv_plan(domain), kv_injection="read",
                     kv_method="auto",
                     prefill_chunk=int(serving["prefill_chunk"]))
    slots = int(serving["num_slots"])
    ps = int(serving["page_slots"])
    return ContinuousBatchingScheduler(
        bndl, cfg, params, sc, num_slots=slots,
        num_pages=slots * sc.max_len // ps, page_slots=ps)
