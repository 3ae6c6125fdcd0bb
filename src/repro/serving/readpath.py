"""Read-path injection context: wiring the fused faulty-attention kernel
into a model's decode step.

A :class:`ReadPathCtx` is built once per (traced) KV voltage from the
serving placement: for every K/V cache leaf it carries the arena
engine's ``block -> (physical base word, threshold row)`` tables, with
threshold rows already gathered at the current voltage.  The model's
decode attention calls :meth:`ReadPathCtx.attend`, which routes the
stored cache buffers through
:func:`repro.kernels.flash_attention.faulty.faulty_decode_attention` --
faults are computed on the K/V tile already in VMEM, so injection costs
zero extra HBM passes and a traced voltage sweep compiles once.

With ``inject=False`` the context still routes attention through the
fused kernel but skips the mask math entirely: the write-path serving
modes use this so every injection mode shares bit-identical attention
numerics (the scanned decode's cross-mode equality tests rely on it).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as arena
from repro.core.domains import GroupPlacement
from repro.core.faultmap import FaultMap
from repro.kernels.flash_attention import faulty

# Cache leaves the read path covers: the ring K/V buffers of the shared
# attention cache layout (models/stack.py containers x models/cache.py
# ring leaves).  Everything else (pos bookkeeping, recurrent states)
# stays on the (incremental) write path.
_KV_LEAF_RE = re.compile(
    r"^\['(prefix|periods|rest)'\]\['([^']+)'\]\['([kv])'\]$")


def supports(module) -> bool:
    """Whether a family module's decode step accepts a read-path ctx."""
    return bool(getattr(module, "SUPPORTS_READ_PATH", False))


def state_leaf_paths(specs, max_len: int) -> Tuple[str, ...]:
    """Keystr paths of the carried-``state`` cache leaves of a family.

    These are the leaves the read path can never cover: recurrent
    state (RG-LRU h/conv, mLSTM matrix memories) is rewritten whole on
    every decode step, so write-path injection re-applies the domain's
    stuck-at masks to each new value -- a fault acquired on write
    PERSISTS for the lifetime of the request (corrupt-once-on-write),
    unlike ring K/V rows which are written once and only re-masked
    idempotently.  The persistent-fault oracle test keys on this list
    to know which leaves to difference across steps.
    """
    from repro.models.base import cache_layouts
    flat, _ = jax.tree_util.tree_flatten_with_path(
        cache_layouts(specs, max_len))
    return tuple(jax.tree_util.keystr(p) for p, lay in flat
                 if lay == "state")


@dataclasses.dataclass(frozen=True)
class _LeafEntry:
    base: jax.Array           # (num_blocks,) uint32 physical block bases
    thr: jax.Array            # (num_blocks, NUM_THR_COLS) @ current voltage
    layer_words: int          # words per period index (0 = unstacked leaf)
    words_log2: int           # table granularity (arena blocks or pages)


@dataclasses.dataclass(frozen=True)
class _SlotEntry:
    k: _LeafEntry
    v: _LeafEntry
    # KV-tile size override: a page-granular placement tiles the ring by
    # the paged serving kernel's block of pages, so the flash
    # accumulation order (and hence the bits of the output) matches it
    # over the same words.
    bkv: Optional[int] = None


def _kv_leaves(placement: GroupPlacement, aval_by_path):
    """(slot key, 'k'|'v', placement leaf, aval) for every K/V leaf."""
    out = []
    for lp in placement.leaves:
        m = _KV_LEAF_RE.match(lp.path)
        if not m:
            continue
        out.append((m.group(2), m.group(3), lp, aval_by_path[lp.path],
                    m.group(1) == "periods"))
    return out


def _avals_by_path(cache_avals):
    flat, _ = jax.tree_util.tree_flatten_with_path(cache_avals)
    return {jax.tree_util.keystr(p): a for p, a in flat}


def cache_supported(placement: GroupPlacement, cache_avals) -> bool:
    """Whether every K/V leaf of this placement can ride the read path:
    word-aligned slots and (for ECC domains) codeword-aligned tiles."""
    by_path = _avals_by_path(cache_avals)
    matched = _kv_leaves(placement, by_path)
    if not matched:
        return False
    for _, _, lp, aval, stacked in matched:
        shape = aval.shape[1:] if stacked else aval.shape
        if len(shape) != 4:
            return False
        _, _, kh, d = shape
        try:
            wps = faulty.kv_words_per_slot(kh, d, aval.dtype)
        except ValueError:
            return False
        if placement.domain.ecc and wps % 2:
            return False
    return True


def kv_paths(placement: GroupPlacement) -> Tuple[str, ...]:
    """keystr paths of the leaves the read path corrupts (skipped by the
    incremental write-path injection)."""
    return tuple(lp.path for lp in placement.leaves
                 if _KV_LEAF_RE.match(lp.path))


@dataclasses.dataclass
class ReadPathCtx:
    entries: Dict[str, _SlotEntry]
    seed: int
    words_per_row_log2: int
    method: str
    ecc: bool
    inject: bool
    interpret: Optional[bool] = None

    def covers(self, slot_key: str) -> bool:
        return slot_key in self.entries

    def update(self, slot_key: str, cache, new, pos):
        """Decode cache write for this ctx's layout: plain contiguous
        ring update here; the paged serving ctx overrides it with the
        pool-page scatter.  Owning the write on the ctx keeps the
        model's decode branch cache-layout-agnostic."""
        from repro.models.cache import ring_update
        return ring_update(cache, new, pos)

    def attend(self, slot_key: str, layer_idx, q, cache, *, q_pos,
               causal: bool, window: int, scale=None):
        """Fused decode attention over a slot's ring cache.

        ``layer_idx``: traced period index for stacked slots (None for
        prefix/remainder layers); ``q_pos``: the decode token's absolute
        position -- its ring slot is exempt from corruption (the value
        still sits in the store buffer, it never round-tripped through
        undervolted HBM this step).
        """
        e = self.entries[slot_key]
        k, v, pos = cache["k"], cache["v"], cache["pos"]
        idx = jnp.uint32(0) if layer_idx is None else layer_idx.astype(
            jnp.uint32)
        clean = (q_pos % k.shape[1]).astype(jnp.int32)
        assert e.k.words_log2 == e.v.words_log2, (e.k, e.v)
        return faulty.faulty_decode_attention(
            q, k, v, pos, q_pos=q_pos,
            k_tables=(e.k.base, e.k.thr), v_tables=(e.v.base, e.v.thr),
            k_word0=idx * np.uint32(e.k.layer_words),
            v_word0=idx * np.uint32(e.v.layer_words),
            causal=causal, window=window, scale=scale, seed=self.seed,
            method=self.method, words_per_row_log2=self.words_per_row_log2,
            ecc=self.ecc, inject=self.inject, clean_slot=clean,
            bkv=e.bkv, interpret=self.interpret,
            words_log2=e.k.words_log2)


def build_ctx(placement: GroupPlacement, faultmap: FaultMap, cache_avals,
              *, voltage, method: str, inject: bool,
              interpret=None) -> ReadPathCtx:
    """Build the per-voltage context (``voltage`` may be traced: the
    threshold gather happens inside the caller's trace, so per-request
    voltage schedules re-execute one compiled decode).

    ``placement`` may be an arena-backed GroupPlacement (block-granular
    tables) or a page-granular request placement exported by the paged
    serving cache (:mod:`repro.serving.paged`) -- the kernel addressing
    is table-driven either way; a paged placement additionally pins the
    KV tile to the paged kernel's block of pages
    (:func:`repro.kernels.flash_attention.faulty.block_pages`) so the
    numerics match the paged batch kernel.
    """
    ms = getattr(placement, "map_seed", None)
    if ms is not None and ms != faultmap.seed:
        raise ValueError(
            f"kv_placement was exported from a pool whose fault map "
            f"has seed {ms}, but the replay plan's fault map has seed "
            f"{faultmap.seed}: a sharded scheduler's shards draw "
            "distinct maps, so replay a request against ITS shard's "
            "plan (sched.shard_plan(result.shard)) or the tokens "
            "would silently diverge")
    table = faultmap.threshold_table(voltage)
    tabs = arena.leaf_addr_tables(placement)
    by_path = _avals_by_path(cache_avals)
    halves: Dict[str, Dict[str, _LeafEntry]] = {}
    bkv: Dict[str, set] = {}
    for i, lp in enumerate(placement.leaves):
        m = _KV_LEAF_RE.match(lp.path)
        if not m:
            continue
        slot_key, which, stacked = m.group(2), m.group(3), \
            m.group(1) == "periods"
        aval = by_path[lp.path]
        bb, bp, lg2 = tabs[i]
        shape = aval.shape[1:] if stacked else aval.shape
        _, length, kh, d = shape
        wps = faulty.kv_words_per_slot(kh, d, aval.dtype)
        layer_words = shape[0] * length * wps if stacked else 0
        if hasattr(lp, "page_words"):
            assert lp.page_words % wps == 0, (lp.path, lp.page_words, wps)
            ps = lp.page_words // wps
            bkv.setdefault(slot_key, set()).add(
                faulty.block_pages(length // ps, ps, wps) * ps)
        halves.setdefault(slot_key, {})[which] = _LeafEntry(
            base=jnp.asarray(bb), thr=table[jnp.asarray(bp)],
            layer_words=int(layer_words), words_log2=lg2)
    assert all(len(b) == 1 for b in bkv.values()), (
        f"K and V tile a ring differently: {bkv}")
    entries = {key: _SlotEntry(k=h["k"], v=h["v"],
                               bkv=(bkv[key].pop() if key in bkv else None))
               for key, h in halves.items() if "k" in h and "v" in h}
    return ReadPathCtx(
        entries=entries, seed=faultmap.seed,
        words_per_row_log2=faultmap.words_per_row_log2, method=method,
        ecc=placement.domain.ecc, inject=inject, interpret=interpret)
