"""Plain float32 reference of the dense decoder (llama-style GQA).

Written from the published architecture, not from the program: RMSNorm,
rotary embedding on the two halves of each head, causal grouped-query
attention, a SwiGLU MLP and an untied output head.  It imports nothing
of the program.  Weights come from the benchmark's own draw
(``bench/model.py:leaf``), one layer at a time, so the reference fits
beside nothing else on the chip.

Every matrix product runs at ``Precision.HIGHEST``.  With
``precision="fp8"`` each product's operands are first rounded to
float8 e4m3 with one scale per tensor: the control, one precision step
below the bfloat16 that the configurations serve in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import leaf, seed_key

HI = jax.lax.Precision.HIGHEST
ROWS = 512             # logits rows are computed in blocks of this many
Q_CHUNK = 512          # attention query block


def _q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x), _q8(w)
    return jnp.dot(x, w, precision=HI)


def _rms(x, gamma, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + gamma))


def _rope(x, pos, theta):
    """x: (T, H, D); rotate the first half of each head with the second."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, w, *, dims, fp8):
    """One decoder layer over a whole (T, d) float32 sequence."""
    heads, kv_heads, head_dim, eps, theta = dims
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm(h, w["wq"], fp8).reshape(t, heads, head_dim), pos, theta)
    k = _rope(_mm(h, w["wk"], fp8).reshape(t, kv_heads, head_dim), pos,
              theta)
    v = _mm(h, w["wv"], fp8).reshape(t, kv_heads, head_dim)
    g = heads // kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = np.float32(head_dim ** -0.5)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK) * scale
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    att = jax.lax.map(block, jnp.arange(t // Q_CHUNK)).reshape(t, -1)
    x = x + _mm(att, w["wo"], fp8)
    h2 = _rms(x, w["ln2"], eps)
    a = jax.nn.silu(_mm(h2, w["wg"], fp8)) * _mm(h2, w["wu"], fp8)
    return x + _mm(a, w["wd"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, ln_f, unembed, *, eps, fp8):
    return _mm(_rms(x, ln_f, eps), unembed.T, fp8)


@functools.partial(jax.jit, static_argnames=("name", "shape", "d_model"))
def _draw(key, layer, *, name, shape, d_model):
    return leaf(key, name, layer, shape, d_model).astype(jnp.float32)


def layer_shapes(conf: dict) -> dict:
    d, f = int(conf["hidden_size"]), int(conf["intermediate_size"])
    q = int(conf["num_attention_heads"]) * int(conf["head_dim"])
    kv = int(conf["num_key_value_heads"]) * int(conf["head_dim"])
    return {"ln1": (d,), "ln2": (d,), "wq": (d, q), "wk": (d, kv),
            "wv": (d, kv), "wo": (q, d), "wg": (d, f), "wu": (d, f),
            "wd": (f, d)}


def logits_rows(conf: dict, seed: int, seqs, *, precision: str = "f32"):
    """Next-token logits of each sequence at the requested positions.

    ``seqs``: list of (tokens (T,) int, rows (n,) int positions).  Every
    sequence is padded at its end to the configuration's ring length
    (causal attention leaves the real positions untouched), so one
    compiled layer serves every request.  Returns a list of (n, vocab)
    float32 device arrays."""
    fp8 = precision == "fp8"
    d = int(conf["hidden_size"])
    key = seed_key(seed)
    dims = (int(conf["num_attention_heads"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            float(conf["rms_norm_eps"]), float(conf["rope_theta"]))
    vocab = int(conf["vocab_size"])

    def draw(name, layer, shape):
        return _draw(key, jnp.uint32(layer), name=name,
                     shape=tuple(shape), d_model=d)

    embed = draw("embed", 0, (vocab, d))
    ring = int(conf["serving"]["max_len"])
    ring = -(-ring // Q_CHUNK) * Q_CHUNK
    xs = []
    for tokens, _ in seqs:
        padded = np.zeros(ring, np.int32)
        padded[:len(tokens)] = tokens
        xs.append(embed[jnp.asarray(padded)])
    del embed
    for layer in range(int(conf["num_hidden_layers"])):
        w = {n: draw(n, layer, s) for n, s in layer_shapes(conf).items()}
        xs = [_layer(x, w, dims=dims, fp8=fp8) for x in xs]
    ln_f = draw("ln_f", 0, (d,))
    unembed = draw("unembed", 0, (vocab, d))
    out = []
    for x, (_, rows) in zip(xs, seqs):
        n = len(rows)
        idx = np.full(-(-n // ROWS) * ROWS, rows[-1], np.int32)
        idx[:n] = rows
        out.append(_head(x[jnp.asarray(idx)], ln_f, unembed, eps=dims[3],
                         fp8=fp8)[:n])
    return out
