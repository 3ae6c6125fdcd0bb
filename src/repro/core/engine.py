"""Arena-based fault-injection engine: one fused pass per memory domain.

The legacy path (:mod:`repro.core.injection`) walks a placement segment
by segment, launching one Pallas call per segment per leaf with the
thresholds baked in as static jit arguments -- O(segments) launches plus
a full retrace for every distinct (voltage, PC) pair.  This module
replaces that hot path:

  * every leaf of a group is packed (block-aligned) into one flat
    *arena* buffer, using the placement's exported
    :class:`~repro.core.domains.BlockTable`;
  * the fault map's voltage->threshold table is evaluated *inside the
    trace* -- voltage may be a traced scalar -- and gathered into
    per-block threshold rows;
  * a single ``pallas_call`` with a grid over all arena blocks performs
    the read-modify-write for the entire domain, reading each block's
    physical base word and threshold row from scalar-prefetch operands.

Consequences: injecting a multi-leaf, multi-PC group costs one kernel
launch instead of hundreds, and a jitted voltage sweep (the paper's
10 mV-step methodology, online V_min search, per-request voltage
schedules) compiles exactly once.

The ``use_ref`` path is the table-driven oracle: identical mask math on
the same arena/threshold operands, pure jnp -- bit-exact with the kernel
by construction and asserted by the tests.
"""
from __future__ import annotations

import functools
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.domains import ALIGN_WORDS, GroupPlacement
from repro.core.faultmap import NUM_THR_COLS, FaultMap
from repro.core.faultmodel import V_MIN
from repro.kernels.bitflip import ops as bitflip_ops
from repro.kernels.bitflip.bitflip import (BLOCK_LANES, BLOCK_WORDS,
                                           BLOCK_WORDS_LOG2, apply_masks,
                                           arena_bitflip_pallas, arena_masks)
from repro.kernels.ecc.ecc import arena_ecc_events, arena_ecc_pallas

assert BLOCK_WORDS == ALIGN_WORDS, "arena blocks must match allocation slots"


@functools.lru_cache(maxsize=256)
def _block_arrays(placement: GroupPlacement):
    """Numpy block tables for a placement (bounded cache: a long-lived
    server may build placements for many distinct batch/length shapes).
    Kept as numpy -- they embed as constants both eagerly and under jit;
    caching device arrays here would leak tracers out of whatever trace
    first touched them."""
    table = placement.block_table()
    return (np.asarray(table.block_pc, np.int32),
            np.asarray(table.block_base, np.uint32))


def _static_value(v):
    """``float(v)`` for any concrete scalar (python, numpy, or a
    non-traced jax array), ``None`` for traced values.  Concrete jax
    scalars drive static decisions (method dispatch, guardband
    early-out) exactly like python floats."""
    try:
        return float(v)
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        return None


def resolve_method(faultmap: FaultMap, placement: GroupPlacement,
                   voltage=None) -> str:
    """Static word/bitwise dispatch for a whole domain.

    The fast word path is exact to O((32p)^2), so it is chosen only if
    *every* PC of the domain is inside its validity regime.  A traced
    voltage override cannot drive dispatch (method selects a trace);
    it falls back to the domain's configured voltage (with a trace-time
    warning) -- callers sweeping into the collapse regime should pass
    ``method='bitwise'`` explicitly.
    """
    v = _static_value(voltage)
    if v is None:
        v = placement.domain.voltage
        warnings.warn(
            f"method='auto' with a traced voltage dispatches from domain "
            f"{placement.domain.name!r}'s configured {v:.2f} V; sweeps "
            "crossing per-bit rates ~1e-3 should pass method='bitwise' "
            "explicitly", stacklevel=3)
    if any(bitflip_ops.pick_method(faultmap.thresholds(v, pc)) ==
           "bitwise" for pc in placement.domain.pc_ids):
        return "bitwise"
    return "word"


def pack_arena(tree, placement: GroupPlacement):
    """Pack a group's leaves into one (num_blocks * 8, 512) uint32 arena.

    Returns (arena2d, pack_meta).  Leaves are matched to placements by
    pytree path, exactly like the legacy path.  ``pack_meta`` records,
    in the *tree's flatten order* (placement order is keystr-sorted and
    may differ -- e.g. list index 10 sorts before 2), each leaf's arena
    slot and dtype-recovery metadata for :func:`unpack_arena`.
    """
    table = placement.block_table()
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaf_by_path = {jax.tree_util.keystr(p): leaf for p, leaf in flat}
    pieces = []
    slot_by_path = {}
    for lp, (start, n_blocks, n_words) in zip(placement.leaves,
                                              table.leaf_blocks):
        u32, meta = bitflip_ops.to_u32(leaf_by_path[lp.path])
        assert u32.shape[0] == n_words == lp.n_words, (
            lp.path, u32.shape, n_words, lp.n_words)
        pad = n_blocks * BLOCK_WORDS - n_words
        if pad:
            u32 = jnp.concatenate([u32, jnp.zeros((pad,), jnp.uint32)])
        pieces.append(u32)
        slot_by_path[lp.path] = (meta, start, n_words)
    arena = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    slots = tuple(slot_by_path[jax.tree_util.keystr(p)] for p, _ in flat)
    return arena.reshape(-1, BLOCK_LANES), (treedef, slots)


def unpack_arena(arena2d, pack_meta):
    """Inverse of :func:`pack_arena`: arena -> original pytree."""
    treedef, slots = pack_meta
    flat_arena = arena2d.reshape(-1)
    leaves = [
        bitflip_ops.from_u32(
            flat_arena[start * BLOCK_WORDS:start * BLOCK_WORDS + n_words],
            meta)
        for meta, start, n_words in slots]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _arena_oracle(arena2d, block_base, block_thr, *, seed: int, method: str,
                  words_per_row_log2: int, ecc: bool):
    """Table-driven pure-jnp oracle: same operands, same mask math.

    Returns (out, uncorrectable count, corrected count) -- counts are
    zero without ECC.
    """
    num_blocks = block_base.shape[0]
    x = arena2d.reshape(num_blocks, BLOCK_WORDS)
    wid = (block_base[:, None]
           + jnp.arange(BLOCK_WORDS, dtype=jnp.uint32)[None, :])
    thr_row = tuple(block_thr[:, c][:, None] for c in range(NUM_THR_COLS))
    if ecc:
        out, corr, bad = arena_ecc_events(
            x, wid, thr_row, seed=seed,
            words_per_row_log2=words_per_row_log2)
        return (out.reshape(arena2d.shape),
                jnp.sum(bad.astype(jnp.int32)),
                jnp.sum(corr.astype(jnp.int32)))
    mask01, mask10 = arena_masks(wid, thr_row, seed=seed, method=method,
                                 words_per_row_log2=words_per_row_log2)
    mask10 = mask10 & ~mask01
    out = (x | mask01) & ~mask10
    return (out.reshape(arena2d.shape), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32))


def inject_placement(tree, placement: GroupPlacement, faultmap: FaultMap,
                     *, voltage=None, method: str = "auto",
                     interpret: Optional[bool] = None,
                     use_ref: bool = False, with_corrected: bool = False):
    """Inject a whole group through one fused arena pass.

    ``voltage``: optional override of the domain's configured voltage.
    May be a *traced* scalar -- thresholds are computed inside the trace,
    so sweeping voltages re-executes, never recompiles.  When the
    effective voltage is static and inside the guardband the call is an
    exact no-op (identity tree); a traced voltage in the guardband is a
    numerical no-op (the threshold table gates itself to zero).

    Returns (faulted tree, uncorrectable-fault count) -- the count is
    zero unless the domain has ECC.  With ``with_corrected`` a third
    value is appended: the corrected-codeword count (ECC telemetry),
    computed from outputs the fused kernel already produces, so the
    launch budget is unchanged.
    """
    domain = placement.domain
    zero = jnp.zeros((), jnp.int32)
    if not placement.leaves:  # empty group: nothing placed, nothing to do
        return (tree, zero, zero) if with_corrected else (tree, zero)
    if voltage is None:
        voltage = domain.voltage
    sv = _static_value(voltage)
    if sv is not None and sv >= V_MIN - 1e-9:
        return (tree, zero, zero) if with_corrected else (tree, zero)
    if method == "auto":
        # ECC is word-path-only by design; don't resolve (or warn).
        method = "word" if domain.ecc else resolve_method(
            faultmap, placement, voltage)
    if interpret is None:
        interpret = bitflip_ops.default_interpret()

    block_pc, block_base = _block_arrays(placement)
    block_base = jnp.asarray(block_base)
    arena2d, pack_meta = pack_arena(tree, placement)
    block_thr = faultmap.threshold_table(voltage)[jnp.asarray(block_pc)]
    wprl2 = faultmap.words_per_row_log2

    if use_ref:
        out2d, bad, corr = _arena_oracle(
            arena2d, block_base, block_thr, seed=faultmap.seed,
            method=method, words_per_row_log2=wprl2, ecc=domain.ecc)
    elif domain.ecc:
        out2d, bad_blocks, corr_blocks = arena_ecc_pallas(
            arena2d, block_base, block_thr, seed=faultmap.seed,
            words_per_row_log2=wprl2, interpret=bool(interpret))
        bad = jnp.sum(bad_blocks)
        corr = jnp.sum(corr_blocks)
    else:
        out2d = arena_bitflip_pallas(
            arena2d, block_base, block_thr, seed=faultmap.seed,
            method=method, words_per_row_log2=wprl2,
            interpret=bool(interpret))
        bad = corr = zero
    out = unpack_arena(out2d, pack_meta)
    return (out, bad, corr) if with_corrected else (out, bad)


@functools.lru_cache(maxsize=256)
def leaf_block_tables(placement: GroupPlacement):
    """Per-leaf ``(block_base, block_pc)`` numpy arrays, in placement
    (keystr-sorted) order -- the arena engine's block tables sliced to
    one leaf, so the read path and the incremental write path can
    address a single cache buffer without packing the whole domain."""
    table = placement.block_table()
    bb = np.asarray(table.block_base, np.uint32)
    bp = np.asarray(table.block_pc, np.int32)
    return tuple((bb[s:s + n], bp[s:s + n])
                 for s, n, _ in table.leaf_blocks)


def refine_tables(block_base, block_pc, page_words: int):
    """Refine one leaf's arena block tables to page granularity.

    ``page_words`` must divide BLOCK_WORDS, so every page sits inside
    exactly one arena block and inherits that block's pseudo-channel
    (threshold row); its physical base is the block's base plus the
    page's offset inside the block.  The page tables are therefore a
    pure index transform of the block tables -- the paged KV cache
    costs zero extra placement bookkeeping.

    Returns ``(page_base, page_pc)`` numpy arrays with
    ``BLOCK_WORDS // page_words`` entries per block.
    """
    if page_words <= 0 or BLOCK_WORDS % page_words:
        raise ValueError(
            f"page_words={page_words} must positively divide the arena "
            f"block size ({BLOCK_WORDS} words)")
    per = BLOCK_WORDS // page_words
    base = (np.repeat(np.asarray(block_base, np.uint32), per)
            + np.tile(np.arange(per, dtype=np.uint32) * page_words,
                      len(block_base)))
    return base, np.repeat(np.asarray(block_pc, np.int32), per)


def leaf_addr_tables(placement):
    """Per-leaf ``(base, pc, words_log2)`` physical addressing tables.

    For an arena-backed :class:`~repro.core.domains.GroupPlacement`
    these are the block tables at BLOCK_WORDS granularity.  Placements
    whose leaves carry their own page tables (the paged serving cache's
    per-request placements, duck-typed on a ``page_base`` attribute)
    return those instead, with each leaf's page granularity.
    """
    leaves = placement.leaves
    if leaves and hasattr(leaves[0], "page_base"):
        out = []
        for lp in leaves:
            lg2 = int(lp.page_words).bit_length() - 1
            assert (1 << lg2) == lp.page_words, lp.page_words
            out.append((np.asarray(lp.page_base, np.uint32),
                        np.asarray(lp.page_pc, np.int32), lg2))
        return tuple(out)
    return tuple((bb, bp, BLOCK_WORDS_LOG2)
                 for bb, bp in leaf_block_tables(placement))


def corrupt_words(u32, off, block_base, block_thr, *, seed: int,
                  method: str, words_per_row_log2: int, ecc: bool,
                  words_log2: int = BLOCK_WORDS_LOG2):
    """Corrupt arbitrary leaf words through their arena block tables.

    The pure-jnp twin of the kernels' candidate-select addressing:
    ``off`` holds leaf word offsets (any shape matching ``u32``), the
    per-word physical id and threshold row are gathered with
    ``jnp.take`` from the leaf's ``block_base`` / per-block threshold
    rows (``block_thr``, possibly derived from a traced voltage), and
    the shared tile-level mask math is applied.  For ECC the last axis
    must hold leaf-adjacent words in even count (codeword pairs).
    ``words_log2``: granularity of the tables (arena blocks by default,
    pages for the paged serving cache).

    Returns (corrupted u32, uncorrectable count).
    """
    off = off.astype(jnp.uint32)
    jvec = (off >> np.uint32(words_log2)).astype(jnp.int32)
    wid = (jnp.take(jnp.asarray(block_base), jvec)
           + (off & np.uint32((1 << words_log2) - 1)))
    rows = jnp.take(jnp.asarray(block_thr), jvec, axis=0)
    thr = tuple(rows[..., c] for c in range(NUM_THR_COLS))
    if ecc:
        out, _, bad = arena_ecc_events(
            u32, wid, thr, seed=seed,
            words_per_row_log2=words_per_row_log2)
        return out, jnp.sum(bad.astype(jnp.int32))
    out = apply_masks(u32, wid, thr, seed=seed, method=method,
                      words_per_row_log2=words_per_row_log2)
    return out, jnp.zeros((), jnp.int32)


def ecc_event_counts(u32, off, block_base, block_thr, *, seed: int,
                     words_per_row_log2: int,
                     words_log2: int = BLOCK_WORDS_LOG2):
    """Per-codeword ECC event flags for arbitrary leaf words.

    The telemetry twin of :func:`corrupt_words`: identical table-driven
    addressing and mask math, but instead of mutating data it returns
    ``(corrected_bool, uncorrectable_bool)`` per codeword (last axis of
    ``u32`` halved).  Because stuck-at masks are deterministic in the
    physical word id, evaluating this on *clean* stored data yields
    exactly the events the fused read-path kernel observed when it
    loaded the same words this step -- a zero-extra-launch scrub.
    """
    off = off.astype(jnp.uint32)
    jvec = (off >> np.uint32(words_log2)).astype(jnp.int32)
    wid = (jnp.take(jnp.asarray(block_base), jvec)
           + (off & np.uint32((1 << words_log2) - 1)))
    rows = jnp.take(jnp.asarray(block_thr), jvec, axis=0)
    thr = tuple(rows[..., c] for c in range(NUM_THR_COLS))
    _, corrected, uncorrectable = arena_ecc_events(
        u32, wid, thr, seed=seed, words_per_row_log2=words_per_row_log2)
    return corrected, uncorrectable


def _corrupt_full_leaf(leaf, block_base, block_thr, *, seed, method,
                       wprl2, ecc, words_log2=BLOCK_WORDS_LOG2):
    u32, meta = bitflip_ops.to_u32(leaf)
    n = u32.shape[0]
    pad = (-n) % 2 if ecc else 0
    if pad:
        u32 = jnp.concatenate([u32, jnp.zeros((pad,), jnp.uint32)])
    off = jnp.arange(n + pad, dtype=jnp.uint32)
    out, bad = corrupt_words(u32, off, block_base, block_thr, seed=seed,
                             method=method, words_per_row_log2=wprl2,
                             ecc=ecc, words_log2=words_log2)
    return bitflip_ops.from_u32(out[:n], meta), bad


def _corrupt_leaf_slice(leaf, slot_axis, pos, block_base, block_thr, *,
                        seed, method, wprl2, ecc,
                        words_log2=BLOCK_WORDS_LOG2):
    """Corrupt only the slot written at absolute position ``pos``."""
    shape = leaf.shape
    ln = shape[slot_axis]
    outer = int(np.prod(shape[:slot_axis], dtype=np.int64))
    inner = int(np.prod(shape[slot_axis + 1:], dtype=np.int64))
    wpi = inner * jnp.dtype(leaf.dtype).itemsize // 4
    slot = (pos % ln).astype(jnp.int32)
    sl = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=slot_axis)
    u32, meta = bitflip_ops.to_u32(sl.reshape(outer, inner))
    u32 = u32.reshape(outer, wpi)
    off = (jnp.arange(outer, dtype=jnp.uint32)[:, None] * np.uint32(ln * wpi)
           + slot.astype(jnp.uint32) * np.uint32(wpi)
           + jnp.arange(wpi, dtype=jnp.uint32)[None, :])
    out, bad = corrupt_words(u32, off, block_base, block_thr, seed=seed,
                             method=method, words_per_row_log2=wprl2,
                             ecc=ecc, words_log2=words_log2)
    out = bitflip_ops.from_u32(out.reshape(-1), meta).reshape(sl.shape)
    return (jax.lax.dynamic_update_slice_in_dim(leaf, out, slot,
                                                axis=slot_axis), bad)


def _sliceable(leaf, slot_axis, ecc) -> bool:
    if slot_axis is None or slot_axis < 0:
        return False
    inner_bytes = (int(np.prod(leaf.shape[slot_axis + 1:], dtype=np.int64))
                   * jnp.dtype(leaf.dtype).itemsize)
    if inner_bytes % 4:
        return False                   # slot not word-aligned
    if ecc and (inner_bytes // 4) % 2:
        return False                   # slot splits an ECC codeword
    return True


def inject_placement_slice(tree, placement: GroupPlacement,
                           faultmap: FaultMap, *, slot_axes=None, pos=None,
                           voltage=None, method: str = "auto",
                           skip_paths=()):
    """Incremental write-path injection: O(touched-words), pure jnp.

    With ``pos`` a (traced) absolute position, only the ring slot
    ``pos % L`` of each leaf is corrupted -- the slice a decode step just
    wrote -- which is bit-identical to re-injecting the whole cache
    (stuck-at masks are deterministic per physical word and idempotent)
    at O(new-token) cost instead of O(cache).  Leaves without a slot
    axis (``slot_axes`` leaf < 0), with non-word-aligned slots, or whose
    slots split ECC codewords are corrupted whole (they are the small
    recurrent/bookkeeping states).  With ``pos=None`` every included
    leaf is corrupted whole (the post-prefill initialization).

    Whole-leaf corruption of carried state is the PERSISTENT-fault
    semantic of the model zoo's ``state``-layout leaves (RG-LRU
    h/conv, mLSTM matrix memories): the state is rewritten on every
    decode step, so the same deterministic per-word stuck-at masks
    re-apply to each new value -- a cell that faults on write stays
    faulted for the request's lifetime (corrupt-once-on-write), while
    a ring K/V row, written once, is only ever re-masked to the value
    it already has.

    ``skip_paths``: keystr paths handled elsewhere (e.g. K/V leaves
    corrupted on the read path by the fused attention kernel).

    Returns (tree, uncorrectable count).
    """
    domain = placement.domain
    if not placement.leaves:
        return tree, jnp.zeros((), jnp.int32)
    if voltage is None:
        voltage = domain.voltage
    sv = _static_value(voltage)
    if sv is not None and sv >= V_MIN - 1e-9:
        return tree, jnp.zeros((), jnp.int32)
    if method == "auto":
        method = "word" if domain.ecc else resolve_method(
            faultmap, placement, voltage)
    wprl2 = faultmap.words_per_row_log2
    table = faultmap.threshold_table(voltage)
    tables = {lp.path: (bb, table[jnp.asarray(bp)], lg2)
              for lp, (bb, bp, lg2) in zip(placement.leaves,
                                           leaf_addr_tables(placement))}

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    if slot_axes is None:
        ax_leaves = [-1] * len(flat)
    else:
        ax_leaves = jax.tree_util.tree_leaves(slot_axes)
        assert len(ax_leaves) == len(flat), "slot_axes must match the tree"
    out_leaves = []
    total_bad = jnp.zeros((), jnp.int32)
    skip = set(skip_paths)
    for (path, leaf), axis in zip(flat, ax_leaves):
        key = jax.tree_util.keystr(path)
        if key in skip:
            out_leaves.append(leaf)
            continue
        bb, bt, lg2 = tables[key]
        kw = dict(seed=faultmap.seed, method=method, wprl2=wprl2,
                  ecc=domain.ecc, words_log2=lg2)
        if pos is not None and _sliceable(leaf, axis, domain.ecc):
            faulted, bad = _corrupt_leaf_slice(leaf, axis, pos, bb, bt,
                                               **kw)
        else:
            faulted, bad = _corrupt_full_leaf(leaf, bb, bt, **kw)
        out_leaves.append(faulted)
        total_bad = total_bad + bad
    return jax.tree_util.tree_unflatten(treedef, out_leaves), total_bad


def _subjaxprs(params):
    """Jaxprs nested in an eqn's params (duck-typed: no jax.core
    internals, which get pruned across jax releases)."""
    stack = list(params.values())
    while stack:
        v = stack.pop()
        if hasattr(v, "eqns"):          # Jaxpr
            yield v
        elif hasattr(v, "jaxpr"):       # ClosedJaxpr
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            stack.extend(v)


def count_pallas_calls(jaxpr) -> int:
    """Number of ``pallas_call`` equations in a jaxpr (recursive).

    The arena engine's structural contract -- one launch per domain --
    is asserted with this in the tests and reported by the benchmarks.
    """
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for sub in _subjaxprs(eqn.params):
            n += count_pallas_calls(sub)
    return n


def inject_groups(groups: Dict[str, object],
                  placements: Dict[str, GroupPlacement],
                  faultmap: FaultMap, *, voltage=None, method: str = "auto",
                  interpret: Optional[bool] = None, use_ref: bool = False,
                  with_corrected: bool = False):
    """Arena-inject every group: one fused pass per domain.

    ``voltage`` as a scalar (possibly traced) overrides only domains
    *configured below the guardband* -- domains placed at or above
    V_MIN hold state the plan promises to keep safe (master params,
    optimizer moments) and are never dragged down by a sweep.  Pass a
    ``{domain name: scalar}`` dict to target domains explicitly,
    including safe ones.

    Returns (faulted groups dict, total uncorrectable count); with
    ``with_corrected`` also the total corrected-codeword count (ECC
    telemetry for the training hot path -- same launches either way).
    """
    if isinstance(voltage, dict):
        # Validate against every provided placement (callers sharing one
        # schedule dict across calls can pass their full placements map
        # with a subset of groups).
        known = {p.domain.name for p in placements.values()}
        unknown = set(voltage) - known
        if unknown:
            raise ValueError(
                f"voltage override names unknown domains {sorted(unknown)}; "
                f"placements cover {sorted(known)}")
    out: Dict[str, object] = {}
    total_bad = jnp.zeros((), jnp.int32)
    total_corr = jnp.zeros((), jnp.int32)
    for name, tree in groups.items():
        placement = placements[name]
        if isinstance(voltage, dict):
            v = voltage.get(placement.domain.name)
        elif (voltage is not None
              and placement.domain.voltage < V_MIN - 1e-9):
            v = voltage
        else:
            v = None
        faulted, bad, corr = inject_placement(
            tree, placement, faultmap, voltage=v,
            method=method, interpret=interpret, use_ref=use_ref,
            with_corrected=True)
        out[name] = faulted
        total_bad = total_bad + bad
        total_corr = total_corr + corr
    if with_corrected:
        return out, total_bad, total_corr
    return out, total_bad
