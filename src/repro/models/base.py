"""Model substrate: configs, parameter specs, logical-axis sharding.

Flax-free functional modules: every architecture family exposes

    param_specs(cfg)                  -> pytree of ParamSpec
    forward_train(params, batch, cfg) -> (loss, metrics)
    cache_specs(cfg, batch, seq)      -> pytree of ParamSpec (decode state)
    decode_step(params, cache, batch, cfg) -> (logits, cache)

ParamSpec carries *logical axes* (MaxText-style); launch/mesh.py resolves
them to PartitionSpecs through per-arch rule tables, with divisibility
checking and fallbacks.  Dry-runs materialize nothing: specs become
ShapeDtypeStructs and the whole step is lowered AOT.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

# Cache-leaf layout kinds (the CacheLayout descriptor).  Every decode-state
# leaf is one of:
#   full    -- ring buffer covering the whole max_len sequence (classic KV)
#   window  -- ring buffer shorter than max_len (sliding-window attention);
#              pages window-modularly: slot(p) = p % window
#   cross   -- written once at prefill, read-only afterwards (encoder K/V
#              of enc-dec models); shareable copy-on-write across requests
#   state   -- slotless carried state (recurrent h/conv, mLSTM matrix
#              memory); faults here are persistent, not per-read
CACHE_LAYOUTS = ("full", "window", "cross", "state")


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Shape/dtype/logical-axes/init description of one parameter."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis name per dim (or None)
    dtype: Any = jnp.bfloat16
    init: str = "normal"                 # normal | zeros | ones | scaled
    scale: float = 1.0
    # cache-leaf layout override (see leaf_layout); None = infer from axes
    layout: Optional[str] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)
        assert self.layout in (None,) + CACHE_LAYOUTS, self.layout

    @property
    def aval(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def spec_avals(specs) -> Any:
    return jax.tree_util.tree_map(
        lambda s: s.aval, specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


# The logical axis naming the ring-buffer slot dimension of decode-state
# leaves.  Injection uses it to map a cache position to the arena words
# a decode step actually wrote (incremental write-path injection) and to
# the K/V rows the fused read-path attention kernel corrupts on load.
CACHE_SLOT_AXIS = "cache_seq"


def cache_slot_axes(specs) -> Any:
    """Per-leaf index of the ring-buffer slot axis, -1 for slotless
    decode state (recurrent/conv states, bookkeeping scalars).  Stacked
    period leaves (leading 'layers' axis) shift automatically because
    the axis is located by name."""
    def ax(s: ParamSpec) -> int:
        return (s.axes.index(CACHE_SLOT_AXIS)
                if CACHE_SLOT_AXIS in s.axes else -1)
    return jax.tree_util.tree_map(
        ax, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def cache_batch_axes(specs) -> Any:
    """Per-leaf index of the serving-batch axis, located by name
    ('batch'), -1 for batch-free bookkeeping leaves.  Stacked period
    leaves (leading 'layers' axis) shift automatically.  The state-
    arena scheduler scatters/slices per-request cache rows along this
    axis -- it is NOT always dim 0 (period-stacked leaves carry the
    layer stack in front)."""
    def ax(s: ParamSpec) -> int:
        return s.axes.index("batch") if "batch" in s.axes else -1
    return jax.tree_util.tree_map(
        ax, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def leaf_layout(spec: ParamSpec, max_len: int) -> str:
    """Layout kind of one cache leaf (see CACHE_LAYOUTS).

    Families may pin a kind explicitly via ParamSpec.layout (whisper's
    encoder K/V is ``cross``); otherwise leaves with a ring-slot axis
    classify as ``full``/``window`` by comparing the ring length against
    ``max_len``, and slotless leaves are carried ``state``.
    """
    if spec.layout is not None:
        return spec.layout
    if CACHE_SLOT_AXIS in spec.axes:
        ln = spec.shape[spec.axes.index(CACHE_SLOT_AXIS)]
        return "full" if ln >= max_len else "window"
    return "state"


def cache_layouts(specs, max_len: int) -> Any:
    """Per-leaf layout kind for a cache-spec tree."""
    return jax.tree_util.tree_map(
        lambda s: leaf_layout(s, max_len), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def ring_lengths(specs) -> Any:
    """Per-leaf ring length (slots along CACHE_SLOT_AXIS), 0 if slotless."""
    def ln(s: ParamSpec) -> int:
        return (s.shape[s.axes.index(CACHE_SLOT_AXIS)]
                if CACHE_SLOT_AXIS in s.axes else 0)
    return jax.tree_util.tree_map(
        ln, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def init_params(specs, key) -> Any:
    """Materialize parameters (smoke tests / examples only)."""
    flat, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for s, k in zip(flat, keys):
        if s.init == "zeros":
            leaves.append(jnp.zeros(s.shape, s.dtype))
        elif s.init == "ones":
            leaves.append(jnp.ones(s.shape, s.dtype))
        elif s.init == "const":
            leaves.append(jnp.full(s.shape, s.scale, s.dtype))
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = s.scale / math.sqrt(max(fan_in, 1))
            leaves.append(_normal_leaf(k, s.shape, s.dtype,
                                       jnp.float32(std)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal_leaf(key, shape, dtype, std):
    """float32 normal draw scaled and cast to ``dtype`` in one fused
    program: the float32 draw never materializes beside the weights
    (at published widths it is twice the size of a bf16 leaf)."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def count_params(specs) -> int:
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(int(np.prod(s.shape)) for s in flat)


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One assigned architecture (exact numbers from the public pool)."""

    arch_id: str
    family: str                  # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16

    # layer pattern, e.g. gemma3 5 local : 1 global, recurrentgemma 2 rec :
    # 1 local-attention.  None means all layers identical.
    pattern: Optional[Tuple[str, ...]] = None
    window: int = 0              # sliding-window size for local attention

    # MoE / MLA (deepseek family)
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 2.0
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 0       # MLA decoupled rope dims
    d_ff_dense: int = 0          # deepseek layer-0 dense MLP width

    # ssm / hybrid
    conv_width: int = 4
    lru_width: int = 0

    # enc-dec / vlm frontends (stubs provide precomputed embeddings)
    enc_layers: int = 0
    enc_len: int = 0             # whisper: 1500 frames; vlm: image tokens
    frontend_dim: int = 0        # vlm: ViT output width fed to the projector
    mlp_gated: bool = True       # whisper uses plain GELU MLPs

    # training
    remat: str = "block"         # none | block
    seq_len_default: int = 4096

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer kind strings, honoring the repeating pattern."""
        if self.pattern is None:
            return ("global",) * self.n_layers
        reps = -(-self.n_layers // len(self.pattern))
        return (self.pattern * reps)[: self.n_layers]


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One assigned input-shape cell."""

    name: str                    # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1),
}

# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[[], "ArchBundle"]] = {}


@dataclasses.dataclass(frozen=True)
class ArchBundle:
    """Everything the launcher needs for one architecture."""

    cfg: ArchConfig
    module: Any                       # the family module (dense, moe, ...)
    reduced: Optional[ArchConfig] = None   # smoke-test configuration
    # shape-cell applicability: long_500k only for sub-quadratic families
    skip_cells: Tuple[str, ...] = ()
    skip_reasons: Dict[str, str] = dataclasses.field(default_factory=dict)


def register(arch_id: str, fn: Callable[[], ArchBundle]) -> None:
    _REGISTRY[arch_id] = fn


def get_arch(arch_id: str) -> ArchBundle:
    if arch_id not in _REGISTRY:
        # configs register lazily on import
        import importlib
        mod = arch_id.replace("-", "_").replace(".", "_")
        importlib.import_module(f"repro.configs.{mod}")
    return _REGISTRY[arch_id]()


def list_archs() -> Tuple[str, ...]:
    from repro import configs  # noqa: F401  (triggers registration)
    return tuple(sorted(_REGISTRY))
