"""Operations and bytes that the served work needs, from the shapes of a
configuration file.  Counts are of useful work only: what a token's
forward pass requires, not what a fixed-shape step computes besides.
"""
from __future__ import annotations

BF16 = 2


def _dims(conf: dict):
    d = int(conf["hidden_size"])
    heads, kv = int(conf["num_attention_heads"]), int(
        conf["num_key_value_heads"])
    hd = int(conf["head_dim"])
    return (d, heads * hd, kv * hd, int(conf["intermediate_size"]),
            int(conf["num_hidden_layers"]), int(conf["vocab_size"]), heads,
            kv, hd)


def matmul_flops_per_token(conf: dict) -> int:
    """Projections and gated MLP of every layer for one token
    (2 operations per multiply-add), without the output head."""
    d, q, kv, f, layers, _, _, _, _ = _dims(conf)
    per_layer = 2 * (d * q + 2 * d * kv + q * d + 3 * d * f)
    return per_layer * layers


def head_flops(conf: dict) -> int:
    """The output head for one sampled token."""
    d, _, _, _, _, vocab, _, _, _ = _dims(conf)
    return 2 * d * vocab


def attention_flops(conf: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys,
    over every layer."""
    _, _, _, _, layers, _, heads, _, hd = _dims(conf)
    return 4 * heads * hd * int(context) * layers


def kv_bytes_per_position(conf: dict) -> int:
    """K and V of one cached position, over every layer."""
    _, _, kv, _, layers, _, _, _, _ = _dims(conf)
    return 2 * kv * BF16 * layers


def decode_attention_bytes(conf: dict, context: int) -> int:
    """What one decode lane's paged attention must move over every
    layer: K and V of its ``context`` live positions, its query in and
    its output out."""
    _, q, _, _, layers, _, _, _, _ = _dims(conf)
    return (kv_bytes_per_position(conf) * int(context)
            + 2 * q * BF16 * layers)

