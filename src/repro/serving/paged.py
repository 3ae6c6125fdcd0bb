"""Paged KV cache: serving-cache pages carved out of HBM arena blocks.

The paper's three-factor trade-off (power x capacity x fault rate,
Fig. 6) only becomes a *serving* resource once the system can steer
which data lands on which reliability class of memory at runtime
(Voltron's observation).  This module makes the fault map that
steerable resource:

  * a :class:`PagePool` carves fixed-size KV pages out of
    ``DomainAllocator`` blocks.  Because a page size must divide the
    arena block size, every page sits inside exactly one block and
    inherits its pseudo-channel -- the per-page physical base / threshold
    tables are a pure index refinement of the arena engine's block
    tables (:func:`repro.core.engine.refine_tables`), zero extra
    bookkeeping.  Pages are handed out tier-aware: weak-block pages go
    to fault-tolerant requests first, weak-avoiding tiers get strong
    pages most-reliable-first, and exhaustion raises
    :class:`~repro.core.domains.CapacityError` for the scheduler to
    treat as queue backpressure, not a crash.
  * a :class:`PagedKVCache` owns the pooled device buffers (the pool is
    literally ``cache_specs(cfg, num_pages, page_slots)`` -- a ring
    cache whose "batch" rows are pages) and the serving-side data paths:
    scattering a prefilled request into its pages, the paged decode
    write, and the write-path fault injection of exactly the words a
    step touched.
  * a :class:`PagedServingCtx` is the decode-step hook (same protocol as
    :class:`repro.serving.readpath.ReadPathCtx`, plus the paged cache
    write): attention routes through
    :func:`repro.kernels.flash_attention.faulty.paged_decode_attention`,
    which gathers K/V tiles page-by-page via scalar-prefetched page
    tables and corrupts them in VMEM as they load.
  * :meth:`PagePool.request_placement` exports one request's pages as a
    page-granular placement of the *standalone* contiguous cache, so
    PR 3's ``generate()`` can replay the exact same physical fault map
    -- the scheduler's token-for-token acceptance contract.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as arena
from repro.core import faultmap as fm
from repro.core.domains import (ALIGN_WORDS, CapacityError, MemoryDomain,
                                Segment, resolve_tier)
from repro.core.faultmap import NUM_THR_COLS, FaultMap
from repro.kernels.bitflip.bitflip import BLOCK_WORDS
from repro.kernels.ecc.ecc import arena_ecc_events
from repro.kernels.flash_attention import faulty
from repro.models.base import cache_layouts, cache_slot_axes, spec_avals

# Chaos-injection column remap: a "row went weak at runtime" fault is
# synthesized by overriding a page's *strong* thresholds with its weak
# ones (every voltage-dependent pair), so all rows under the page start
# drawing faults at the weak rate -- same compiled graph, the override
# is a jnp.where over gathered threshold rows.
_WEAKEN_COLS = np.asarray(
    [fm.COL_Q01_WEAK, fm.COL_Q01_WEAK, fm.COL_Q10_WEAK, fm.COL_Q10_WEAK,
     fm.COL_WEAK_ROW_Q, fm.COL_T01_WEAK, fm.COL_T01_WEAK, fm.COL_T10_WEAK,
     fm.COL_T10_WEAK, fm.COL_PAR_Q_WEAK, fm.COL_PAR_Q_WEAK], np.int32)
assert _WEAKEN_COLS.shape[0] == NUM_THR_COLS

# Pool-cache leaves: the shared attention-cache layout (stack containers
# x ring k/v/pos leaves).
_LEAF_RE = re.compile(
    r"^\['(prefix|periods|rest)'\]\['([^']+)'\]\['(k|v|pos)'\]$")


class PagedLayoutError(ValueError):
    """A cache layout that cannot be paged: page size not dividing the
    arena block size, non-uniform cache lengths, ECC-incompatible page
    geometry, ...  Subclasses ``ValueError`` so config-validation
    callers can catch it generically."""


class PageSharingError(ValueError):
    """A refcounted-page protocol violation: releasing a shared page a
    holder does not hold (double release), retaining or COW-forking a
    page that is not shared, re-sharing an already-shared page, or
    ``free()``-ing a page that still has holders.  Typed so the
    scheduler's copy-on-write bookkeeping fails loudly instead of
    silently corrupting a page another tenant still maps."""


@dataclasses.dataclass(frozen=True, eq=False)
class PagedLeafPlacement:
    """Page-granular placement of one leaf of a request's *standalone*
    (contiguous, B=1) cache: entry ``j`` of the tables describes leaf
    words ``[j * page_words, (j+1) * page_words)``.  Duck-typed against
    :class:`~repro.core.domains.LeafPlacement` via the ``page_base``
    attribute (see :func:`repro.core.engine.leaf_addr_tables`)."""

    path: str
    n_words: int
    page_words: int
    page_base: np.ndarray      # (n_pages,) uint32 physical base words
    page_pc: np.ndarray        # (n_pages,) int32 owning pseudo-channel


@dataclasses.dataclass(frozen=True, eq=False)
class RequestPlacement:
    """One request's cache placement assembled from its pool pages.

    Quacks like a :class:`~repro.core.domains.GroupPlacement` for the
    serving engine (``domain`` / ``leaves`` / ``total_words``) but
    addresses physical words through per-leaf *page* tables, which is
    what lets PR 3's contiguous ``generate()`` reproduce a scheduler
    request bit-for-bit.
    """

    group: str
    domain: MemoryDomain
    leaves: Tuple[PagedLeafPlacement, ...]
    # seed of the exporting pool's fault map: a sharded scheduler's
    # shards draw distinct maps, and a replay against any other map
    # would silently diverge -- readpath.build_ctx cross-checks it
    map_seed: Optional[int] = None

    @property
    def total_words(self) -> int:
        return sum(l.n_words for l in self.leaves)


@dataclasses.dataclass(frozen=True, eq=False)
class _PoolLeaf:
    """Static metadata of one pool-cache leaf."""

    path: str
    container: str             # prefix | periods | rest
    slot_key: str              # e.g. "s0_global"
    which: str                 # k | v | pos
    stacked: bool              # leading period axis
    n_layers: int              # 1 for unstacked leaves
    wps: int                   # uint32 words per cache slot
    page_words: int            # wps * page_slots
    layer_words: int           # words per layer slice of the pool leaf
    length: int                # logical ring length (max_len or window)
    n_pages: int               # length // page_slots (leaf's table width)
    layout: str                # "full" | "window" (see base.CACHE_LAYOUTS)
    # Physical tables (None when the pool is unplaced / clean):
    page_base: Optional[np.ndarray]   # (n_layers, total_pages) uint32
    page_pc: Optional[np.ndarray]     # (n_layers, total_pages) int32
    block_base: Optional[np.ndarray]  # arena block tables of the leaf
    block_pc: Optional[np.ndarray]


def _leaf_words_per_slot(shape, slot_axis, dtype) -> int:
    inner = int(np.prod(shape[slot_axis + 1:], dtype=np.int64))
    nbytes = inner * jnp.dtype(dtype).itemsize
    if nbytes % 4:
        raise PagedLayoutError(
            f"cache slot of {inner} x {jnp.dtype(dtype).name} elements "
            "is not word-aligned; the paged cache needs whole uint32 "
            "words per slot")
    return nbytes // 4


class PagePool:
    """Host-side page allocator over one serving cache pool.

    ``num_pages`` is the usable page count; one extra *scratch* page is
    appended (``scratch_id``) as the write sink for inactive serving
    slots -- it is never handed out.  A page id is valid across every
    leaf and layer of the cache simultaneously (vLLM-style): allocating
    ``n`` pages provisions K, V and position storage for ``n *
    page_slots`` cache slots of every layer.

    Tier routing: pages whose backing arena blocks contain weak rows
    (in any leaf/layer) are classed *weak*.  Weak-avoiding tiers
    allocate strong pages most-reliable-first; tolerant tiers consume
    weak pages first and then strong pages least-reliable-first, so
    the reliable end of the pool stays available for strict traffic.
    """

    def __init__(self, module, cfg, *, max_len: int, page_slots: int,
                 num_pages: int, plan=None, shard=None):
        if not getattr(module, "SUPPORTS_PAGED", False):
            raise ValueError(
                f"family module {getattr(module, '__name__', module)!r} "
                "does not support the paged serving cache (needs ring "
                "k/v/pos cache leaves and the paged decode-step hook)")
        if page_slots <= 0 or max_len % page_slots:
            raise PagedLayoutError(
                f"page_slots={page_slots} must positively divide "
                f"max_len={max_len} (ServeConfig.max_len): a request's "
                "logical cache is a whole number of pages -- pick "
                "page_slots from the divisors of max_len")
        self.module = module
        self.cfg = cfg
        self.max_len = int(max_len)
        self.page_slots = int(page_slots)
        self.num_pages = int(num_pages)
        self.total_pages = self.num_pages + 1
        self.scratch_id = self.num_pages      # trailing page, never issued
        self.plan = plan
        # Shard index of a mesh-sharded scheduler owning this pool (None
        # for single-device pools); CapacityErrors name it so fleet
        # backpressure is attributable to the exhausted device.
        self.shard = shard

        # The pool *is* a ring cache whose batch rows are pages.
        self.pool_specs = module.cache_specs(cfg, self.total_pages,
                                             self.page_slots)
        self.pool_avals = spec_avals(self.pool_specs)

        placed = (plan is not None and plan.enabled
                  and plan.covers("kv_cache"))
        if placed:
            self.placement = plan.place(
                {"kv_cache": self.pool_avals})["kv_cache"]
            self.domain = self.placement.domain
            self.faultmap: Optional[FaultMap] = plan.fault_map()
        else:
            self.placement = None
            self.domain = None
            self.faultmap = None
        self.leaves = self._build_leaves()
        self._by_path = {l.path: l for l in self.leaves}
        # A request's page-table width is set by its *longest* ring:
        # window leaves address only the first length//page_slots table
        # entries window-modularly, so a family whose rings are all
        # windows allocates fewer pages per request (rotated-out pages
        # are never held -- the pool-level eviction win).
        self.n_logical_pages = max(l.n_pages for l in self.leaves)
        # Pages per block of the paged decode kernel on the full-length
        # rings (one block of the whole table where there are none).
        full = [l.wps for l in self.leaves
                if l.which in ("k", "v") and l.length == self.max_len]
        self.kernel_block_pages = (
            faulty.block_pages(self.n_logical_pages, self.page_slots,
                               max(full))
            if full else self.n_logical_pages)
        # words one page id provisions across every leaf and layer
        self.page_set_words = sum(l.n_layers * l.page_words
                                  for l in self.leaves)
        self.request_words = self.n_logical_pages * self.page_set_words

        weak, rate = self._page_classes()
        order = sorted(range(self.num_pages), key=lambda p: (rate[p], p))
        self._strong: List[int] = [p for p in order if not weak[p]]
        self._weak: List[int] = [p for p in order if weak[p]]
        self._weak_set = set(self._weak)
        self._rate = rate
        self._owned: set = set()
        # Copy-on-write prefix sharing: refcounted holders per shared
        # page, plus the content-hash prefix cache (prompt-prefix bytes
        # -> the shared pages storing it, insertion-ordered for LRU
        # eviction under capacity pressure).
        self._shared: Dict[int, set] = {}
        self._prefix: Dict[bytes, np.ndarray] = {}
        # Self-healing: pages retired for good (suspect rows); never
        # reinserted into the free lists, monotonically growing.
        self._quarantined: set = set()
        # Observability hook: callable(kind, **data) the scheduler
        # installs to trace pool-side events (quarantine/prefix_evict).
        self.on_event = None

    # ---- static layout ---------------------------------------------------
    def _build_leaves(self) -> Tuple[_PoolLeaf, ...]:
        flat, _ = jax.tree_util.tree_flatten_with_path(self.pool_avals)
        axes = jax.tree_util.tree_leaves(cache_slot_axes(self.pool_specs))
        by_path = {}
        for (p, aval), ax in zip(flat, axes):
            by_path[jax.tree_util.keystr(p)] = (aval, ax)
        # standalone specs tell us the request-side ring length and
        # layout kind of every leaf (full / window); state and cross
        # leaves never reach the page pool -- the scheduler routes
        # families carrying them through the per-slot state arena
        req_specs = self.module.cache_specs(self.cfg, 1, self.max_len)
        req_axes = jax.tree_util.tree_leaves(cache_slot_axes(req_specs))
        req_layouts = jax.tree_util.tree_leaves(
            cache_layouts(req_specs, self.max_len))
        req_flat, _ = jax.tree_util.tree_flatten_with_path(
            spec_avals(req_specs))
        leaf_meta = {}
        for (p, aval), ax, lay in zip(req_flat, req_axes, req_layouts):
            path = jax.tree_util.keystr(p)
            if lay in ("state", "cross"):
                raise PagedLayoutError(
                    f"cache leaf {path} has layout {lay!r}: "
                    "slotless carried state / cross-attention leaves "
                    "cannot live in the page pool (accepted layouts: "
                    "'full', 'window').  Serve this family through the "
                    "scheduler's per-slot state arena instead")
            length = aval.shape[ax]
            if self.page_slots > length:
                raise PagedLayoutError(
                    f"cache leaf {path}: page_slots={self.page_slots} "
                    f"exceeds the {lay!r} ring length {length} "
                    "(cfg.window); a page must fit inside the ring -- "
                    f"pick page_slots <= {length}")
            if length % self.page_slots:
                field = ("cfg.window" if lay == "window"
                         else "ServeConfig.max_len")
                raise PagedLayoutError(
                    f"cache leaf {path}: page_slots={self.page_slots} "
                    f"does not divide the leaf's ring length {length} "
                    f"({field}); a {lay!r} ring pages window-modularly "
                    "only when page_slots divides it -- pick page_slots "
                    f"from the divisors of {length}")
            leaf_meta[path] = (length, lay)

        placed = self.placement is not None
        tabs = (arena.leaf_block_tables(self.placement) if placed else None)
        paths = ([lp.path for lp in self.placement.leaves] if placed
                 else None)
        ecc = placed and self.domain.ecc
        out = []
        for path in sorted(by_path):
            m = _LEAF_RE.match(path)
            if not m:
                raise PagedLayoutError(
                    f"cache leaf {path} is not a ring k/v/pos leaf of "
                    "the shared attention-cache layout (containers "
                    "'prefix'/'periods'/'rest', leaves 'k'/'v'/'pos'); "
                    "the page pool accepts 'full' and 'window' ring "
                    "layouts only -- carried-state and cross-attention "
                    "leaves serve through the per-slot state arena")
            aval, ax = by_path[path]
            stacked = m.group(1) == "periods"
            if (ax != (2 if stacked else 1)):
                raise PagedLayoutError(
                    f"cache leaf {path}: slot axis {ax} is not the ring "
                    "axis the paged layout expects (axis 2 for stacked "
                    "period leaves, axis 1 otherwise)")
            length, layout = leaf_meta[path]
            n_layers = aval.shape[0] if stacked else 1
            wps = _leaf_words_per_slot(aval.shape, ax, aval.dtype)
            page_words = wps * self.page_slots
            if BLOCK_WORDS % page_words:
                raise PagedLayoutError(
                    f"cache leaf {path}: page size {page_words} words "
                    f"({self.page_slots} slots x {wps} words) does not "
                    f"divide the arena block size ({BLOCK_WORDS} words); "
                    "pick page_slots so every page sits inside one "
                    "allocation block")
            if ecc and (page_words % 2 or
                        (m.group(3) in ("k", "v") and wps % 2)):
                raise PagedLayoutError(
                    f"cache leaf {path}: ECC domains need even page and "
                    f"slot word counts (codeword pairs), got page="
                    f"{page_words} / slot={wps} words; use a head_dim/"
                    "page_slots combination giving even word counts or "
                    "drop ecc=True from the domain")
            layer_words = self.total_pages * page_words
            pb = pc = bb = bp = None
            if placed:
                bb, bp = tabs[paths.index(path)]
                pb_flat, pc_flat = arena.refine_tables(bb, bp, page_words)
                n = n_layers * self.total_pages
                pb = pb_flat[:n].reshape(n_layers, self.total_pages)
                pc = pc_flat[:n].reshape(n_layers, self.total_pages)
            out.append(_PoolLeaf(
                path=path, container=m.group(1), slot_key=m.group(2),
                which=m.group(3), stacked=stacked, n_layers=n_layers,
                wps=wps, page_words=page_words, layer_words=layer_words,
                length=length, n_pages=length // self.page_slots,
                layout=layout,
                page_base=pb, page_pc=pc, block_base=bb, block_pc=bp))
        return tuple(out)

    def _page_classes(self):
        """(weak, worst-rate) per usable page, aggregated over every
        leaf/layer slice the page id provisions.

        A page is *weak* when any of its K/V payload slices overlaps a
        weak DRAM row (the paper's C9 spatial-clustering unit) -- row
        granularity, not allocation-block granularity, because pages
        are much smaller than blocks and block-level classing would
        condemn every page that merely shares a 16 KiB block with one
        weak row.  The ``pos`` bookkeeping sliver (one word per slot,
        packed so densely that a single weak row would condemn the
        whole pool) is not counted: weak-row avoidance targets the
        payload rows that dominate a request's fault exposure."""
        weak = np.zeros(self.num_pages, bool)
        rate = np.zeros(self.num_pages, np.float64)
        if self.placement is None:
            return weak, rate
        fmap = self.faultmap
        wpc = fmap.geometry.bytes_per_pc // 4
        wpr = 1 << fmap.words_per_row_log2
        rates = fmap.predicted_rates(self.domain.voltage)
        rmasks = {int(pc): fmap.weak_row_mask(int(pc))
                  for pc in self.domain.pc_ids}
        for leaf in self.leaves:
            base = leaf.page_base[:, :self.num_pages].astype(np.int64)
            pc = leaf.page_pc[:, :self.num_pages]
            for l in range(leaf.n_layers):
                rate = np.maximum(rate, rates[pc[l]])
                if leaf.which not in ("k", "v"):
                    continue
                in_pc = base[l] - pc[l].astype(np.int64) * wpc
                r0 = in_pc // wpr
                r1 = (in_pc + leaf.page_words - 1) // wpr
                w = np.array([rmasks[int(c)][int(a):int(b) + 1].any()
                              for c, a, b in zip(pc[l], r0, r1)])
                weak |= w
        return weak, rate

    @property
    def uniform(self) -> bool:
        """True when every ring leaf is full-length (no window leaves).
        Copy-on-write prefix sharing keys on page-aligned *position*
        prefixes, which only line up across requests for full rings --
        the scheduler disables sharing for non-uniform layouts."""
        return all(l.layout == "full" for l in self.leaves)

    # ---- allocation ------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._strong) + len(self._weak)

    def alloc(self, n_pages: int, tier="cheap") -> np.ndarray:
        """Allocate ``n_pages`` page ids under ``tier``'s policy.

        Raises :class:`CapacityError` (the scheduler's backpressure
        signal) when the pool cannot supply them -- for weak-avoiding
        tiers, weak pages do not count as supply.
        """
        tier = resolve_tier(tier)
        name = self.domain.name if self.domain is not None else "page_pool"
        if tier.avoid_weak_rows:
            if len(self._strong) < n_pages:
                raise CapacityError(
                    name, n_pages * self.page_set_words * 4,
                    len(self._strong) * self.page_set_words * 4,
                    f"{n_pages} weak-free pages for tier {tier.name!r}; "
                    f"{len(self._weak)} weak pages held back",
                    shard=self.shard)
            taken = self._strong[:n_pages]
            del self._strong[:n_pages]
        else:
            if self.free_pages < n_pages:
                raise CapacityError(
                    name, n_pages * self.page_set_words * 4,
                    self.free_pages * self.page_set_words * 4,
                    f"{n_pages} pages for tier {tier.name!r}",
                    shard=self.shard)
            taken = self._weak[:n_pages]
            del self._weak[:n_pages]
            need = n_pages - len(taken)
            if need:
                # least-reliable strong pages first: keep the reliable
                # end of the pool for weak-avoiding tiers
                taken += self._strong[-need:][::-1]
                del self._strong[-need:]
        self._owned.update(taken)
        return np.asarray(taken, np.int32)

    def free(self, page_ids) -> None:
        """Return pages to the pool (double-free raises ValueError;
        freeing a page that still has sharing holders raises
        PageSharingError -- shared pages retire through release())."""
        ids = [int(p) for p in np.asarray(page_ids).reshape(-1)]
        held = [p for p in ids if p in self._shared]
        if held:
            raise PageSharingError(
                f"free() of shared pages {sorted(held)[:4]}: pages with "
                "live holders must be released per holder, not freed")
        bad = [p for p in ids if p not in self._owned]
        if bad or len(set(ids)) != len(ids):
            raise ValueError(
                f"double free of pool pages {sorted(set(bad) or set(ids))[:4]}: "
                "not currently allocated")
        for p in ids:
            self._reinsert(p)

    def _reinsert(self, p: int) -> None:
        self._owned.discard(p)
        lst = self._weak if p in self._weak_set else self._strong
        keys = [(self._rate[q], q) for q in lst]
        lst.insert(bisect.bisect_left(keys, (self._rate[p], p)), p)

    @property
    def num_weak_pages(self) -> int:
        """Pages whose backing arena blocks contain weak rows (a static
        property of this pool's fault map, not of allocation state)."""
        return len(self._weak_set)

    # ---- self-healing: quarantine + migration accounting ----------------
    @property
    def quarantined_pages(self) -> Tuple[int, ...]:
        return tuple(sorted(self._quarantined))

    def is_owned(self, pid) -> bool:
        return int(pid) in self._owned

    def is_quarantined(self, pid) -> bool:
        return int(pid) in self._quarantined

    def quarantine(self, page_ids) -> None:
        """Permanently retire pages whose backing rows turned suspect.

        Free pages leave the free lists; owned *private* pages leave the
        owned set (their tenant must already have been migrated off --
        the device-side copy is :meth:`PagedKVCache.migrate_pages`).
        Shared pages raise :class:`PageSharingError` (migrate the
        sharing holders first via :meth:`migrate`).  Already-quarantined
        pages are skipped, so quarantine grows monotonically and the
        call is idempotent under replayed suspect reports.
        """
        ids = sorted({int(q) for q in np.asarray(page_ids).reshape(-1)})
        held = [p for p in ids if p in self._shared]
        if held:
            raise PageSharingError(
                f"quarantine of shared pages {held[:4]}: pages with live "
                "holders must be migrated (migrate()) before retiring")
        fresh = []
        for p in ids:
            if p in self._quarantined:
                continue
            if not (0 <= p < self.num_pages):
                raise ValueError(f"quarantine of invalid page id {p}")
            if p in self._owned:
                self._owned.discard(p)
            elif p in self._strong:
                self._strong.remove(p)
            elif p in self._weak:
                self._weak.remove(p)
            self._quarantined.add(p)
            fresh.append(p)
        if fresh and self.on_event is not None:
            self.on_event("quarantine", pages=fresh)

    def migrate(self, src, dst) -> None:
        """Host accounting of one page migration: ``dst`` (freshly
        allocated, private) takes over ``src``'s role and ``src`` is
        quarantined.  A shared ``src`` hands its holder set and prefix-
        cache entries to ``dst``, so sharing tenants keep their pages
        without ever observing the move (their page tables were
        rewritten inside the step)."""
        src, dst = int(src), int(dst)
        if dst not in self._owned or dst in self._shared:
            raise PageSharingError(
                f"migrate target {dst} must be a freshly allocated "
                "private page")
        if src not in self._owned:
            raise ValueError(f"migrate source {src} is not allocated")
        if src in self._shared:
            self._shared[dst] = self._shared.pop(src)
            for pids in self._prefix.values():
                pids[pids == src] = dst
        self._owned.discard(src)
        self._quarantined.add(src)

    def page_rows(self, pid: int) -> Tuple[Tuple[int, int], ...]:
        """(pc, DRAM row) pairs the K/V payload of page ``pid`` overlaps
        -- the telemetry fold's page -> row map (same row math as
        :meth:`_page_classes`, ``pos`` excluded)."""
        if self.placement is None:
            return ()
        fmap = self.faultmap
        wpc = fmap.geometry.bytes_per_pc // 4
        wpr = 1 << fmap.words_per_row_log2
        out = set()
        for leaf in self.leaves:
            if leaf.which not in ("k", "v"):
                continue
            for l in range(leaf.n_layers):
                base = int(leaf.page_base[l, pid])
                pc = int(leaf.page_pc[l, pid])
                in_pc = base - pc * wpc
                for r in range(in_pc // wpr,
                               (in_pc + leaf.page_words - 1) // wpr + 1):
                    out.add((pc, r))
        return tuple(sorted(out))

    def pages_on_row(self, pc: int, row: int) -> np.ndarray:
        """Usable page ids whose K/V payload overlaps DRAM row ``row``
        of pseudo-channel ``pc`` -- the suspect-row -> victim-pages map
        the migration planner walks."""
        hits = np.zeros(self.num_pages, bool)
        if self.placement is None:
            return np.zeros((0,), np.int32)
        fmap = self.faultmap
        wpc = fmap.geometry.bytes_per_pc // 4
        wpr = 1 << fmap.words_per_row_log2
        for leaf in self.leaves:
            if leaf.which not in ("k", "v"):
                continue
            base = leaf.page_base[:, :self.num_pages].astype(np.int64)
            pcs = leaf.page_pc[:, :self.num_pages]
            for l in range(leaf.n_layers):
                in_pc = base[l] - pcs[l].astype(np.int64) * wpc
                r0 = in_pc // wpr
                r1 = (in_pc + leaf.page_words - 1) // wpr
                hits |= (pcs[l] == pc) & (r0 <= row) & (row <= r1)
        return np.flatnonzero(hits).astype(np.int32)

    def page_codewords(self) -> int:
        """SECDED codewords one page's K/V payload spans across every
        leaf and layer (the per-step observation size of a fully-read
        page, for the posterior's binomial update)."""
        return sum(l.n_layers * l.page_words // 2
                   for l in self.leaves if l.which in ("k", "v"))

    def page_blocks(self, page_ids) -> set:
        """(pc, arena block) pairs backing ``page_ids`` over every leaf
        and layer."""
        out: set = set()
        if self.placement is None:
            return out
        wpc = self.faultmap.geometry.bytes_per_pc // 4
        for leaf in self.leaves:
            for l in range(leaf.n_layers):
                for p in (int(q) for q in
                          np.asarray(page_ids).reshape(-1)):
                    base = int(leaf.page_base[l, p])
                    pc = int(leaf.page_pc[l, p])
                    out.add((pc, (base - pc * wpc) // ALIGN_WORDS))
        return out

    def live_blocks(self) -> set:
        """(pc, arena block) pairs that still back live (owned or
        shared) pages -- the :meth:`DomainAllocator.free` guard's view
        of this pool."""
        return self.page_blocks(sorted(self._owned))

    def retirable_blocks(self) -> Tuple[Segment, ...]:
        """Quarantined-page blocks with no live pages left on them, as
        block-aligned segments ready for ``DomainAllocator.quarantine``
        (a block only retires once every tenant sharing it is gone --
        pages are much smaller than allocation blocks)."""
        if self.placement is None or not self._quarantined:
            return ()
        dead = self.page_blocks(sorted(self._quarantined))
        live = self.live_blocks()
        free = self.page_blocks(self._strong + self._weak)
        wpc = self.faultmap.geometry.bytes_per_pc // 4
        return tuple(
            Segment(leaf_start_word=0, n_words=ALIGN_WORDS, pc=pc,
                    phys_base_word=pc * wpc + blk * ALIGN_WORDS)
            for pc, blk in sorted(dead - live - free))

    # ---- copy-on-write prefix sharing ------------------------------------
    @property
    def shared_pages(self) -> int:
        return len(self._shared)

    @property
    def prefix_entries(self) -> int:
        return len(self._prefix)

    def is_shared(self, pid) -> bool:
        return int(pid) in self._shared

    def share(self, page_ids, holder) -> None:
        """Convert privately-owned pages into shared pages held by
        ``holder`` (refcount 1).  Re-sharing raises PageSharingError."""
        for p in (int(q) for q in np.asarray(page_ids).reshape(-1)):
            if p not in self._owned:
                raise PageSharingError(
                    f"share of page {p}: not currently allocated")
            if p in self._shared:
                raise PageSharingError(
                    f"share of page {p}: already shared (holders="
                    f"{len(self._shared[p])}); use retain()")
            self._shared[p] = {holder}

    def retain(self, page_ids, holder) -> None:
        """Add ``holder`` to shared pages' holder sets."""
        pids = [int(q) for q in np.asarray(page_ids).reshape(-1)]
        for p in pids:
            if p not in self._shared:
                raise PageSharingError(
                    f"retain of page {p}: not a shared page")
            if holder in self._shared[p]:
                raise PageSharingError(
                    f"retain of page {p}: holder {holder!r} already "
                    "holds it")
        for p in pids:
            self._shared[p].add(holder)

    def release(self, page_ids, holder) -> None:
        """Drop ``holder``'s reference; a page whose holder set empties
        returns to the free lists (reliability-ordered recycling).
        Releasing a page the holder does not hold -- including a second
        release from the same request -- raises PageSharingError."""
        pids = [int(q) for q in np.asarray(page_ids).reshape(-1)]
        for p in pids:
            if p not in self._shared or holder not in self._shared[p]:
                raise PageSharingError(
                    f"double release of page {p} by holder {holder!r}: "
                    "not currently held")
        for p in pids:
            self._shared[p].discard(holder)
            if not self._shared[p]:
                del self._shared[p]
                self._reinsert(p)

    def cow_fork(self, src_pid, tier="cheap") -> int:
        """Allocate the private target page for copy-on-write-forking
        the shared page ``src_pid`` (first write to a partially-filled
        shared boundary page).  Forking an unshared page is a protocol
        violation and raises PageSharingError; the device-side row copy
        is :meth:`PagedKVCache.reset_and_fork`."""
        src = int(np.asarray(src_pid).reshape(()))
        if src not in self._shared:
            raise PageSharingError(
                f"cow_fork of page {src}: not a shared page (private "
                "pages are written in place, never forked)")
        return int(self.alloc(1, tier)[0])

    def match_prefix(self, tokens: np.ndarray) -> Tuple[int, np.ndarray]:
        """Longest cached prefix of ``tokens``: the full prompt first
        (partial boundary page -> COW fork), then page-aligned prefixes
        descending.  Returns (matched_len, shared page ids covering
        ceil(matched/page_slots) logical pages), or (0, empty)."""
        toks = np.ascontiguousarray(tokens, np.int32).reshape(-1)
        n = toks.shape[0]
        lengths = [n] + [k * self.page_slots
                         for k in range(n // self.page_slots, 0, -1)
                         if k * self.page_slots != n]
        for ln in lengths:
            pids = self._prefix.get(toks[:ln].tobytes())
            if pids is not None:
                return ln, pids.copy()
        return 0, np.zeros((0,), np.int32)

    def register_prefix(self, tokens: np.ndarray, page_ids) -> bool:
        """Publish ``page_ids`` as the shared storage of the prompt
        prefix ``tokens``; each page gains the entry's cache holder, so
        the prefix outlives its creating tenant until evicted.  Pages
        must already be shared (the scheduler share()s a creator's own
        pages at its prefill->decode transition).  Returns False when
        the key is already cached."""
        toks = np.ascontiguousarray(tokens, np.int32).reshape(-1)
        key = toks.tobytes()
        if key in self._prefix:
            return False
        pids = np.asarray(page_ids, np.int32).reshape(-1)
        self.retain(pids, ("__prefix__", key))
        self._prefix[key] = pids.copy()
        return True

    def evict_prefix(self) -> bool:
        """Drop the least-recently-registered prefix entry, releasing
        its cache holds (pages still mapped by live tenants survive via
        their holders).  Returns False when the cache is empty."""
        if not self._prefix:
            return False
        key = next(iter(self._prefix))
        pids = self._prefix.pop(key)
        self.release(pids, ("__prefix__", key))
        if self.on_event is not None:
            self.on_event("prefix_evict", pages=len(pids))
        return True

    # ---- exports ---------------------------------------------------------
    def request_placement(self, page_ids) -> Optional[RequestPlacement]:
        """The page-granular placement of one request's *standalone*
        (B=1, contiguous) cache: logical page ``j`` of layer ``l`` lives
        where pool page ``page_ids[j]``'s layer-``l`` slice lives.  Feed
        it to ``generate(..., kv_placement=...)`` to replay a scheduler
        request through PR 3's engine on identical physical words."""
        if self.placement is None:
            return None
        pids = np.asarray(page_ids, np.int64).reshape(-1)
        assert pids.shape[0] == self.n_logical_pages, pids.shape
        leaves = []
        for leaf in self.leaves:
            lp = pids[:leaf.n_pages]       # window leaves: leading slice
            base = leaf.page_base[:, lp].reshape(-1)       # (nl * n_lp,)
            pc = leaf.page_pc[:, lp].reshape(-1)
            leaves.append(PagedLeafPlacement(
                path=leaf.path,
                n_words=leaf.n_layers * leaf.length * leaf.wps,
                page_words=leaf.page_words,
                page_base=np.ascontiguousarray(base, np.uint32),
                page_pc=np.ascontiguousarray(pc, np.int32)))
        return RequestPlacement(
            group="kv_cache", domain=self.domain, leaves=tuple(leaves),
            map_seed=(self.faultmap.seed
                      if self.faultmap is not None else None))


# ---------------------------------------------------------------------------
# Device-side paged cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _PagedLeafEntry:
    base: jax.Array            # (n_layers, total_pages) uint32
    thr: jax.Array             # (n_layers, total_pages, NUM_THR_COLS)


@dataclasses.dataclass(frozen=True)
class _PagedSlotEntry:
    k: _PagedLeafEntry
    v: _PagedLeafEntry
    length: int = 0            # this ring's logical length (<= max_len)
    n_pages: int = 0           # leading page-table entries it addresses


@dataclasses.dataclass
class PagedServingCtx:
    """Decode-step hook for the paged serving cache.

    Same ``covers``/``update``/``attend`` protocol as
    :class:`repro.serving.readpath.ReadPathCtx`, with the cache write
    overridden to the pool-page scatter and attention to the batched
    paged kernel.  Inactive serving slots' page-table rows point at the
    pool's scratch page and their positions are stale -- their lanes
    compute masked garbage that the scheduler discards.
    """

    entries: Dict[str, _PagedSlotEntry]
    page_table: jax.Array      # (S, n_logical_pages) int32
    length: int                # logical ring length (max_len)
    page_slots: int
    seed: int
    words_per_row_log2: int
    method: str
    ecc: bool
    inject: bool
    interpret: Optional[bool] = None

    def covers(self, slot_key: str) -> bool:
        return slot_key in self.entries

    def update(self, slot_key: str, cache, new, pos):
        """Paged ring write (see :func:`repro.models.cache.paged_update`)
        of one decode token per serving slot.  Window rings write
        window-modularly through the leading ``n_pages`` table entries."""
        from repro.models.cache import paged_update
        e = self.entries[slot_key]
        return paged_update(cache, new, pos,
                            self.page_table[:, :e.n_pages],
                            e.length, self.page_slots)

    def attend(self, slot_key: str, layer_idx, q, cache, *, q_pos,
               causal: bool, window: int, scale=None):
        e = self.entries[slot_key]
        idx = (jnp.uint32(0) if layer_idx is None
               else layer_idx.astype(jnp.uint32))
        kb = jax.lax.dynamic_index_in_dim(e.k.base, idx, keepdims=False)
        kt = jax.lax.dynamic_index_in_dim(e.k.thr, idx, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(e.v.base, idx, keepdims=False)
        vt = jax.lax.dynamic_index_in_dim(e.v.thr, idx, keepdims=False)
        # the kernel derives the ring length from the table width, so a
        # window leaf hands it the leading window//page_slots entries
        with jax.named_scope("paged_decode_attention"):
            return faulty.paged_decode_attention(
                q, cache["k"], cache["v"], cache["pos"],
                self.page_table[:, :e.n_pages],
                q_pos=jnp.reshape(q_pos, (-1,)).astype(jnp.int32),
                k_tables=(kb, kt), v_tables=(vb, vt), causal=causal,
                window=window, scale=scale, seed=self.seed,
                method=self.method,
                words_per_row_log2=self.words_per_row_log2, ecc=self.ecc,
                inject=self.inject, interpret=self.interpret)


@dataclasses.dataclass
class MixedServingCtx(PagedServingCtx):
    """Mixed prefill-chunk/decode step hook: one compiled step serves
    slots in both phases.

    Decode lanes (``dec``) behave exactly like :class:`PagedServingCtx`
    on their first token column (fused paged kernel, read-path
    injection).  Prefill lanes instead run *clean* chunked prefill
    attention: K/V for positions < ``prefill_end`` are gathered from
    the slot's pages (stored clean until the prefill->decode transition
    injects them, so the numerics are bit-identical to standalone
    prefill) with arithmetic key positions -- never the stored ``pos``
    bookkeeping, which is write-path corrupt on shared prefix pages.
    Writes below ``wstart`` (positions held by copy-on-write shared
    pages) are redirected to the scratch sink.
    """

    dec: Optional[jax.Array] = None           # (S,) bool
    wstart: Optional[jax.Array] = None        # (S,) int32
    prefill_end: Optional[jax.Array] = None   # (S,) int32
    scratch_id: int = 0
    # per-slot_key pre-write window snapshot + fresh chunk K/V, stashed
    # by update() for attend() (see _stash_window)
    _window: Dict[str, tuple] = dataclasses.field(default_factory=dict)

    def _stash_window(self, slot_key: str, e: _PagedSlotEntry, cache,
                      new, pos):
        """Window rings under chunked prefill: an in-chunk ring write at
        position p overwrites slot p % window, which may still hold a
        pre-chunk position an *earlier* chunk query needs (window=8,
        chunk=4: writing pos 12 evicts pos 4, which query 10 still
        attends).  So before writing, gather the last ``window``
        pre-chunk positions from the ring in ascending-position order
        (bit-identical summation order to standalone prefill) and stash
        them together with the fresh chunk K/V; prefill lanes attend
        over the concatenation instead of re-gathering the clobbered
        ring."""
        w = e.length
        qp = jnp.asarray(pos, jnp.int32)
        if qp.ndim == 1:
            qp = qp[:, None]
        c0 = qp[:, 0]                          # chunk-start per slot
        kpos = (c0[:, None] - w
                + jnp.arange(w, dtype=jnp.int32)[None, :])   # ascending
        slot = jnp.where(kpos >= 0, kpos, 0) % w
        lp = slot // self.page_slots
        row = slot % self.page_slots
        pid = jnp.take_along_axis(self.page_table[:, :e.n_pages], lp,
                                  axis=1)
        rk = cache["k"][pid, row]              # (S, w, KH, D)
        rv = cache["v"][pid, row]
        self._window[slot_key] = (rk, rv, kpos, new["k"], new["v"], qp)

    def update(self, slot_key: str, cache, new, pos):
        from repro.models.cache import paged_update
        e = self.entries[slot_key]
        if e.length < self.length:
            self._stash_window(slot_key, e, cache, new, pos)
        return paged_update(cache, new, pos,
                            self.page_table[:, :e.n_pages],
                            e.length, self.page_slots,
                            wstart=self.wstart, scratch_id=self.scratch_id)

    def attend(self, slot_key: str, layer_idx, q, cache, *, q_pos,
               causal: bool, window: int, scale=None):
        from repro.models import layers as mlayers
        e = self.entries[slot_key]
        qp = jnp.asarray(q_pos, jnp.int32)
        s = q.shape[0]
        qp = jnp.broadcast_to(qp.reshape(s, -1), q.shape[:2])
        dec_out = PagedServingCtx.attend(
            self, slot_key, layer_idx, q[:, :1], cache,
            q_pos=jnp.maximum(qp[:, 0], 0), causal=causal, window=window,
            scale=scale)
        if e.length < self.length:
            # window ring: pre-write snapshot + fresh chunk (stashed by
            # update), both in ascending position order
            rk, rv, rkpos, fk, fv, fqp = self._window[slot_key]
            gk = jnp.concatenate([rk, fk], axis=1)
            gv = jnp.concatenate([rv, fv], axis=1)
            kpos = jnp.concatenate([rkpos, fqp], axis=1)
            kv_valid = kpos >= 0
        else:
            gk = cache["k"][self.page_table]  # (S, n_lp, ps, KH, D)
            gv = cache["v"][self.page_table]
            gk = gk.reshape((s, self.length) + gk.shape[3:])
            gv = gv.reshape((s, self.length) + gv.shape[3:])
            kpos = jnp.broadcast_to(
                jnp.arange(self.length, dtype=jnp.int32),
                (s, self.length))
            kv_valid = kpos < self.prefill_end[:, None]
        pref = mlayers.attention(q, gk, gv, q_positions=qp,
                                 k_positions=kpos, causal=causal,
                                 window=window, kv_valid=kv_valid,
                                 softmax_scale=scale)
        col0 = jnp.where(self.dec[:, None, None, None], dec_out,
                         pref[:, :1])
        return jnp.concatenate([col0, pref[:, 1:]], axis=1)


class PagedKVCache:
    """Device-side data paths of one :class:`PagePool`.

    Pure functions over the pool tree (the scheduler jits and donates
    around them): init, prefill scatter, admission-time injection, the
    per-step write-path injection, and the decode-step context.
    """

    def __init__(self, pool: PagePool, interpret: Optional[bool] = None):
        self.pool = pool
        self.interpret = interpret
        self._tables = {}
        if pool.placement is not None:
            for leaf in pool.leaves:
                self._tables[leaf.path] = (
                    jnp.asarray(leaf.page_base),
                    jnp.asarray(leaf.page_pc),
                    jnp.asarray(leaf.block_base),
                    jnp.asarray(leaf.block_pc))

    def init_pool(self):
        from repro.models.cache import init_cache
        return init_cache(self.pool.pool_specs)

    def _leaf_arrays(self, tree, leaf: _PoolLeaf):
        arr = tree[leaf.container][leaf.slot_key][leaf.which]
        return arr if leaf.stacked else arr[None]

    def _store(self, tree, leaf: _PoolLeaf, arr_l):
        tree[leaf.container][leaf.slot_key][leaf.which] = (
            arr_l if leaf.stacked else arr_l[0])

    @staticmethod
    def _tree_copy(tree):
        return jax.tree_util.tree_map(lambda x: x, tree)

    # ---- context ---------------------------------------------------------
    def make_ctx(self, page_table, voltage, *, method: str,
                 inject: bool, dec=None, wstart=None,
                 prefill_end=None, chaos=None) -> PagedServingCtx:
        """Decode-step context; passing the per-slot phase arrays
        (``dec``/``wstart``/``prefill_end``) returns the mixed
        chunked-prefill/decode variant instead.

        ``chaos`` is the fault-injection hook for self-healing tests: a
        traced ``(total_pages,)`` bool mask of pages whose rows "went
        weak at runtime" -- their K/V *read* thresholds are overridden
        column-wise to the weak rates (:data:`_WEAKEN_COLS`), so the
        fused kernel starts drawing weak-rate faults (and ECC
        corrections) from them without retracing.  Only the read path is
        chaoticized: stored data stays governed by the static map, so a
        migrated page's payload remains bit-identical to what a clean
        replay on the final placement reads back.
        """
        p = self.pool
        entries: Dict[str, Dict[str, _PagedLeafEntry]] = {}
        if p.placement is not None:
            table = p.faultmap.threshold_table(voltage)
            seed, wprl2 = p.faultmap.seed, p.faultmap.words_per_row_log2
            ecc = p.domain.ecc
        else:
            table = seed = None
            wprl2, ecc, inject = 0, False, False
        wtab = (table[:, jnp.asarray(_WEAKEN_COLS)]
                if table is not None and chaos is not None else None)
        geom: Dict[str, Tuple[int, int]] = {}
        for leaf in p.leaves:
            if leaf.which not in ("k", "v"):
                continue
            geom[leaf.slot_key] = (leaf.length, leaf.n_pages)
            if table is not None:
                pb, pc, _, _ = self._tables[leaf.path]
                thr = table[pc]
                if wtab is not None:
                    thr = jnp.where(chaos[None, :, None], wtab[pc], thr)
                e = _PagedLeafEntry(base=pb, thr=thr)
            else:
                nl, tp = leaf.n_layers, p.total_pages
                e = _PagedLeafEntry(
                    base=jnp.zeros((nl, tp), jnp.uint32),
                    thr=jnp.zeros((nl, tp, NUM_THR_COLS), jnp.uint32))
            entries.setdefault(leaf.slot_key, {})[leaf.which] = e
        kw = dict(
            entries={k: _PagedSlotEntry(k=h["k"], v=h["v"],
                                        length=geom[k][0],
                                        n_pages=geom[k][1])
                     for k, h in entries.items()},
            page_table=page_table, length=p.max_len,
            page_slots=p.page_slots, seed=(seed if seed is not None else 0),
            words_per_row_log2=wprl2, method=method, ecc=ecc,
            inject=inject, interpret=self.interpret)
        if dec is not None:
            return MixedServingCtx(dec=dec, wstart=wstart,
                                   prefill_end=prefill_end,
                                   scratch_id=p.scratch_id, **kw)
        return PagedServingCtx(**kw)

    # ---- self-healing: in-step migration + telemetry scrub ---------------
    def migrate_pages(self, tree, mig_src, mig_dst):
        """Copy page ``mig_src[i]`` -> ``mig_dst[i]`` in every leaf and
        layer -- the device half of a page migration, run *inside* the
        donated step before the decode read.  Disabled migration slots
        carry the scratch id in both arrays: copying scratch onto
        itself (including several times -- identical values per
        duplicate index) is the traced-shape no-op.  In read mode the
        buffer holds clean data, so the copy lands the exact payload a
        replay on the destination placement prefills -- the
        bit-identity contract's load-bearing property."""
        p = self.pool
        tree = self._tree_copy(tree)
        src = jnp.asarray(mig_src, jnp.int32)
        dst = jnp.asarray(mig_dst, jnp.int32)
        for leaf in p.leaves:
            arr_l = self._leaf_arrays(tree, leaf)
            vals = arr_l[:, src]                  # (nl, M, ps, ...)
            self._store(tree, leaf, arr_l.at[:, dst].set(vals))
        return tree

    def scrub_telemetry(self, tree, page_table, voltage, *, chaos=None):
        """Per-page SECDED event counts over the K/V payload of every
        page ``page_table`` references: (corrected, uncorrectable),
        each ``(total_pages,)`` int32.

        Pure jnp on the stored (clean, read-mode) buffers using the
        same deterministic mask math as the fused kernel
        (:func:`repro.kernels.ecc.ecc.arena_ecc_events` on identical
        physical word ids and thresholds), so the counts match what the
        attention read path corrects without adding a single pallas
        launch.  Patrol-scrub semantics: the whole page is scanned,
        including ring slots no request has filled yet -- a fault on a
        still-clean slot counts (slightly over the tokens actually
        attended), which is fine for telemetry whose job is detecting
        weak rows, not billing exact reads.  Pages only reachable from
        the scratch sink report zero.  ``chaos`` applies the same
        weak-column threshold override as :meth:`make_ctx`, so the
        scrub sees the synthetic row-goes-weak fault the kernel sees.
        """
        p = self.pool
        zero = jnp.zeros((p.total_pages,), jnp.int32)
        if p.placement is None or not p.domain.ecc:
            return zero, zero
        table = p.faultmap.threshold_table(voltage)
        wtab = (table[:, jnp.asarray(_WEAKEN_COLS)]
                if chaos is not None else None)
        corrected, uncorrectable = zero, zero
        for leaf in p.leaves:
            if leaf.which not in ("k", "v"):
                continue
            pb, pc, _, _ = self._tables[leaf.path]
            thr = table[pc]                 # (nl, tp, NUM_THR_COLS)
            if wtab is not None:
                thr = jnp.where(chaos[None, :, None], wtab[pc], thr)
            arr_l = self._leaf_arrays(tree, leaf)
            u32 = faulty._tile_to_u32(
                arr_l.reshape(leaf.n_layers * p.total_pages, -1))
            u32 = u32.reshape(leaf.n_layers, p.total_pages,
                              leaf.page_words)
            wid = (pb[:, :, None]
                   + jnp.arange(leaf.page_words, dtype=jnp.uint32)[None,
                                                                   None, :])
            thr_row = tuple(thr[:, :, c][:, :, None]
                            for c in range(NUM_THR_COLS))
            _, corr, bad = arena_ecc_events(
                u32, wid, thr_row, seed=p.faultmap.seed,
                words_per_row_log2=p.faultmap.words_per_row_log2)
            corrected = corrected + jnp.sum(
                corr.astype(jnp.int32), axis=(0, 2))
            uncorrectable = uncorrectable + jnp.sum(
                bad.astype(jnp.int32), axis=(0, 2))
        read = jnp.zeros((p.total_pages,), bool)
        read = read.at[page_table.reshape(-1)].set(True)
        read = read.at[p.scratch_id].set(False)
        return (jnp.where(read, corrected, 0),
                jnp.where(read, uncorrectable, 0))

    # ---- admission -------------------------------------------------------
    def scatter_request(self, tree, cache, page_ids):
        """Write a standalone (B=1) post-prefill cache into the pages
        ``page_ids`` -- pure data movement, so a freshly admitted
        request's pages hold exactly the state standalone prefill
        produces (stale tenants are fully overwritten, empty ring slots
        reset to the init state)."""
        p = self.pool
        tree = self._tree_copy(tree)
        pids = jnp.asarray(page_ids, jnp.int32)
        for leaf in p.leaves:
            arr_l = self._leaf_arrays(tree, leaf)
            src = self._leaf_arrays(cache, leaf)             # (nl, 1, L, ...)
            tail = src.shape[3:]
            src = src.reshape((leaf.n_layers, leaf.n_pages,
                               p.page_slots) + tail)
            self._store(tree, leaf,
                        arr_l.at[:, pids[:leaf.n_pages]].set(src))
        return tree

    def reset_and_fork(self, tree, page_ids, fork_src, fork_dst,
                       fork_rows, fork_pos0):
        """Chunked-prefill admission: reset ``page_ids`` to the init
        state (stale-tenant scrub: pos -> -1, values -> 0), then
        copy-on-write-fork the partially-filled shared boundary page
        ``fork_src`` into the private page ``fork_dst``: rows below
        ``fork_rows`` copy the shared page's K/V (clean by the sharing
        protocol) with positions synthesized arithmetically from
        ``fork_pos0`` (the stored ``pos`` of a shared page is its
        creator's write-path corruption -- never copied), rows at or
        above reset to init.  Shared entries of an admission's page
        table are passed as the scratch id (resetting the scratch sink
        is harmless), which keeps the traced shapes fixed; a disabled
        fork points both ``fork_src`` and ``fork_dst`` at scratch."""
        p = self.pool
        tree = self._tree_copy(tree)
        pids = jnp.asarray(page_ids, jnp.int32)
        dst = jnp.asarray(fork_dst, jnp.int32)
        rows = jnp.arange(p.page_slots, dtype=jnp.int32)
        keep = rows < jnp.asarray(fork_rows, jnp.int32)
        for leaf in p.leaves:
            arr_l = self._leaf_arrays(tree, leaf)
            if leaf.which == "pos":
                arr_l = arr_l.at[:, pids].set(-1)
                fp = jnp.where(keep,
                               jnp.asarray(fork_pos0, jnp.int32) + rows, -1)
                fork = jnp.broadcast_to(fp, (leaf.n_layers, p.page_slots))
            else:
                arr_l = arr_l.at[:, pids].set(0)
                srcv = jax.lax.dynamic_index_in_dim(
                    arr_l, jnp.asarray(fork_src, jnp.int32), axis=1,
                    keepdims=False)                       # (nl, ps, ...)
                mask = keep.reshape((1, p.page_slots)
                                    + (1,) * (srcv.ndim - 2))
                fork = jnp.where(mask, srcv, 0)
            self._store(tree, leaf,
                        arr_l.at[:, dst].set(fork.astype(arr_l.dtype)))
        return tree

    def inject_pages(self, tree, page_ids, voltage, *, method: str,
                     skip_kv: bool):
        """Whole-page injection of one request's pages -- the paged twin
        of the engine's post-prefill ``init_inject`` (same physical
        words, same masks).  ``skip_kv``: in read mode the K/V leaves
        stay clean in the buffer (the read path corrupts them at load);
        only bookkeeping (``pos``) takes write-path faults."""
        p = self.pool
        if p.placement is None:
            return tree
        tree = self._tree_copy(tree)
        table = p.faultmap.threshold_table(voltage)
        pids = jnp.asarray(page_ids, jnp.int32)
        n_lp = pids.shape[0]
        for leaf in p.leaves:
            if skip_kv and leaf.which in ("k", "v"):
                continue
            _, _, bb, bp = self._tables[leaf.path]
            bt = table[bp]
            arr_l = self._leaf_arrays(tree, leaf)
            vals = arr_l[:, pids]                    # (nl, n_lp, ps, ...)
            shape = vals.shape
            u32 = faulty._tile_to_u32(
                vals.reshape(leaf.n_layers * n_lp, -1))
            u32 = u32.reshape(leaf.n_layers, n_lp, leaf.page_words)
            off = (jnp.arange(leaf.n_layers, dtype=jnp.uint32)[:, None, None]
                   * np.uint32(leaf.layer_words)
                   + pids.astype(jnp.uint32)[None, :, None]
                   * np.uint32(leaf.page_words)
                   + jnp.arange(leaf.page_words, dtype=jnp.uint32)[None,
                                                                   None, :])
            out, _ = arena.corrupt_words(
                u32, off, bb, bt, seed=p.faultmap.seed, method=method,
                words_per_row_log2=p.faultmap.words_per_row_log2,
                ecc=p.domain.ecc)
            out = faulty._tile_from_u32(
                out.reshape(leaf.n_layers * n_lp, -1), vals.dtype,
                (leaf.n_layers * n_lp,) + shape[2:]).reshape(shape)
            self._store(tree, leaf, arr_l.at[:, pids].set(out))
        return tree

    # ---- per-step write path ---------------------------------------------
    def post_step_inject(self, tree, page_table, q_pos, voltage, *,
                         mode: str, method: str):
        """Write-path injection of exactly the words a decode step
        wrote: the (pid, row) slot of every active serving slot, in
        every layer.  In read mode only the ``pos`` bookkeeping is
        covered (K/V corruption happens at load); ECC domains corrupt
        the whole ``pos`` pages instead (single positions split
        codewords), matching the standalone engine's fallback.
        """
        p = self.pool
        if p.placement is None:
            return tree
        tree = self._tree_copy(tree)
        table = p.faultmap.threshold_table(voltage)
        kw = dict(seed=p.faultmap.seed, method=method,
                  words_per_row_log2=p.faultmap.words_per_row_log2)
        qp = jnp.reshape(q_pos, (-1,)).astype(jnp.int32)
        n_s = qp.shape[0]
        for leaf in p.leaves:
            if mode == "read" and leaf.which in ("k", "v"):
                continue
            # window-modular: each leaf's ring slot for position p is
            # p % length, addressed through the leading length//ps
            # entries of the request's page table
            slot = qp % leaf.length
            lp = slot // p.page_slots
            row = slot % p.page_slots
            pid = jnp.take_along_axis(page_table, lp[:, None],
                                      axis=1)[:, 0]
            _, _, bb, bp = self._tables[leaf.path]
            bt = table[bp]
            arr_l = self._leaf_arrays(tree, leaf)
            if leaf.which == "pos" and p.domain.ecc:
                # single positions split ECC codewords: corrupt the
                # whole pos pages (cheap -- pos is 1 word per slot)
                ptab_l = page_table[:, :leaf.n_pages]
                vals = arr_l[:, ptab_l]          # (nl, S, n_lp, ps)
                u32 = jax.lax.bitcast_convert_type(vals, jnp.uint32)
                off = (jnp.arange(leaf.n_layers,
                                  dtype=jnp.uint32)[:, None, None, None]
                       * np.uint32(leaf.layer_words)
                       + ptab_l.astype(jnp.uint32)[None, :, :, None]
                       * np.uint32(leaf.page_words)
                       + jnp.arange(p.page_slots,
                                    dtype=jnp.uint32)[None, None, None, :])
                out, _ = arena.corrupt_words(u32, off, bb, bt, ecc=True,
                                             **kw)
                out = jax.lax.bitcast_convert_type(out, vals.dtype)
                self._store(tree, leaf,
                            arr_l.at[:, ptab_l].set(out))
                continue
            vals = arr_l[:, pid, row]            # (nl, S, ...)
            shape = vals.shape
            u32 = faulty._tile_to_u32(
                vals.reshape(leaf.n_layers * n_s, -1))
            u32 = u32.reshape(leaf.n_layers, n_s, leaf.wps)
            off = (jnp.arange(leaf.n_layers, dtype=jnp.uint32)[:, None, None]
                   * np.uint32(leaf.layer_words)
                   + (pid.astype(jnp.uint32) * np.uint32(p.page_slots)
                      + row.astype(jnp.uint32))[None, :, None]
                   * np.uint32(leaf.wps)
                   + jnp.arange(leaf.wps, dtype=jnp.uint32)[None, None, :])
            out, _ = arena.corrupt_words(u32, off, bb, bt,
                                         ecc=p.domain.ecc, **kw)
            out = faulty._tile_from_u32(
                out.reshape(leaf.n_layers * n_s, -1), vals.dtype,
                (leaf.n_layers * n_s,) + shape[2:]).reshape(shape)
            self._store(tree, leaf, arr_l.at[:, pid, row].set(out))
        return tree
