"""Continuous-batching serving scheduler over the reliability-aware
paged KV cache, optionally sharded across a 1-D ``serve`` device mesh.

PR 3's serving path decodes one fixed, contiguously placed batch at a
time: admission happens once, at ``generate()``, and capacity is
whatever that batch's placement grabbed.  This module replaces that
with an admission -> prefill -> decode -> retire loop over concurrent
requests:

  * requests wait in a FIFO queue; admission takes a free serving slot
    plus ``max_len / page_slots`` pool pages matching the request's
    criticality tier (weak-block pages go to tolerant requests first).
    :class:`~repro.core.domains.CapacityError` from the page pool -- or
    from the admission governor -- is *backpressure*: the request simply
    waits for pages to be retired, it never crashes the loop.
  * prefill is *chunked into the decode step*: each compiled step
    consumes up to ``ServeConfig.prefill_chunk`` prompt tokens for
    every prefilling slot (written through the paged path, attended
    with clean gathered attention) while decoding slots advance one
    token through the fused paged kernel.  There is no separate
    prefill program, so the compile count is flat in prompt length
    *and* traffic -- ONE jitted donated step serves any mix of phases,
    lengths and tiers, and the per-step KV voltage stays a traced
    scalar the admission governor can re-plan without a recompile.
  * prompt prefixes are shared copy-on-write: an admitted prompt is
    matched against the pool's content-hash prefix cache and maps the
    longest page-aligned cached prefix read-only (per-page refcounts);
    a partially-filled boundary page is forked onto a private page
    before first write.  Pages that may become shared are allocated
    under the strictest placement tier (``shared_prefix``: weak-free
    blocks, most-reliable pseudo-channels first), because one
    corrupted shared page would poison every tenant mapping it.
  * retirement releases per-page references; pages whose holder sets
    empty return to the pool (reliability-ordered recycling), turning
    capacity reclaimed by tolerating weak blocks -- and by not storing
    shared prefixes twice -- directly into extra concurrent traffic.

Mesh sharding (``mesh=`` + ``launch.mesh.make_serve_mesh``): the slot
array, page pool and page tables are partitioned over the mesh's
``serve`` axis.  Each shard owns its own arena blocks, its own
*independently seeded* :class:`~repro.core.faultmap.FaultMap` (the
per-part margin variation real fleets exhibit: distinct weak-row draws
AND distinct per-PC threshold calibrations), its own governor and its
own voltage setpoint -- heterogeneous fleets undervolt some stacks
deeper than others, aggregated by :func:`repro.training.governor.
fleet_report`.  The donated decode step stays ONE jitted program: a
``shard_map`` whose body switches on ``lax.axis_index('serve')`` into
the shard's seed-specialized branch.  Kernel seeds are folded into the
pallas bodies at trace time throughout the stack (hash streams,
per-plane mask seeds), so per-shard maps are obtained by branch
specialization, never by tracing a seed -- one trace
(``decode_traces == 1``), one pallas launch per shard, and the
compiled step contains **zero collectives**: prefill chunks, paged
decode attention and COW prefix sharing are shard-local by
construction; only the sampled token lanes return to the host.

Token-equivalence contract (asserted in tests/test_scheduler.py and
tests/test_sharded_scheduler.py): every request's tokens are
bit-identical to running it alone through PR 3's ``generate()`` with
the request's page placement (:meth:`PagePool.request_placement`) on
*its shard's* fault map -- greedy and sampled, read and write injection
modes, with and without ECC, shared prefix or not, at every shard
count.  The mechanism behind sharing-compatible injection: shared
pages store *clean* K/V in every mode and the decode kernel's
read-path masks are applied at load in every mode -- the stuck-at
masks and the ECC round are idempotent, so privately-stored-corrupt
pages re-mask to themselves while clean shared pages corrupt to
exactly the standalone stored values.  The one exclusion is a
*governor-driven* run whose voltage actually moves mid-request: the
domain rail is global per shard, so a re-plan triggered by a later
admission also retunes the in-flight requests' thresholds, and a
standalone replay (one constant ``kv_voltage``) cannot reproduce that
trajectory -- ``RequestResult.voltage`` records the admission-time
re-plan, not a promise that the whole lifetime ran there.
``kv_injection='rewrite'`` (the legacy full-cache oracle) cannot
address pages and is rejected up front.  Prompts longer than
``max_len`` are rejected at submit: chunked prefill writes the prompt
through the ring in place and cannot rotate it the way the standalone
prefill's tail-keep does.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.domains import ALIGN_WORDS, CapacityError, DomainAllocator
from repro.core.engine import _static_value, resolve_method
from repro.core.faultmodel import V_MIN, V_NOM
from repro.core.hbm import fleet_map_seeds
from repro.models.base import ArchBundle, ArchConfig, cache_layouts
from repro.obs import trace as obs_trace
from repro.obs.metrics import (MetricsRegistry, ObsConfig,
                               init_step_counters, step_counter_delta)
from repro.obs.trace import EventTrace, step_spans
from repro.serving.engine import ServeConfig, sample_tokens
from repro.serving.paged import PagedKVCache, PagePool, RequestPlacement


class ShardLayoutError(ValueError):
    """A shard layout that cannot partition the scheduler cleanly:
    capacity or page pool not divisible by the shard count, a mesh
    without the serve axis, or colliding per-shard fault-map seeds."""


def validate_shard_layout(n_shards: int, num_slots: int, num_pages: int,
                          *, base_seed: int = 0,
                          seeds: Optional[Sequence[int]] = None,
                          setpoints: Optional[Sequence[float]] = None,
                          ) -> Tuple[Tuple[int, ...],
                                     Tuple[Optional[float], ...]]:
    """Check a serve-mesh layout and resolve per-shard seeds/setpoints.

    Pure host logic (unit-testable without devices): slots and pages
    must split evenly so every shard runs the same compiled shapes,
    and per-shard fault-map seeds must be distinct -- two shards
    sharing a seed would silently model one physical HBM part twice.
    """
    if n_shards < 1:
        raise ShardLayoutError(f"n_shards={n_shards} must be >= 1")
    if num_slots % n_shards:
        raise ShardLayoutError(
            f"ServeConfig capacity num_slots={num_slots} is not "
            f"divisible by the shard count {n_shards}: every shard owns "
            "an equal fixed-capacity slot range (pick num_slots = "
            f"{n_shards} * slots_per_shard)")
    if num_pages % n_shards:
        raise ShardLayoutError(
            f"num_pages={num_pages} is not divisible by the shard "
            f"count {n_shards}: the page pool is partitioned into "
            "equal per-shard arenas (pick num_pages = "
            f"{n_shards} * pages_per_shard)")
    if seeds is None:
        seeds = fleet_map_seeds(base_seed, n_shards)
    seeds = tuple(int(s) for s in seeds)
    if len(seeds) != n_shards:
        raise ShardLayoutError(
            f"shard_seeds has {len(seeds)} entries for {n_shards} "
            "shards: pass exactly one fault-map seed per shard")
    dup = [s for s, c in collections.Counter(seeds).items() if c > 1]
    if dup:
        raise ShardLayoutError(
            f"per-shard fault-map seed collision: seed(s) {sorted(dup)} "
            "appear on more than one shard; every shard models an "
            "independent physical HBM part and must draw its own map "
            "(use core.hbm.fleet_map_seeds or pass distinct seeds)")
    if setpoints is None:
        sp: Tuple[Optional[float], ...] = (None,) * n_shards
    else:
        if len(setpoints) != n_shards:
            raise ShardLayoutError(
                f"shard_setpoints has {len(setpoints)} entries for "
                f"{n_shards} shards: pass one governor setpoint per "
                "shard (or None)")
        sp = tuple(None if s is None else float(s) for s in setpoints)
    return seeds, sp


@dataclasses.dataclass
class Request:
    """One serving request.

    ``max_new_tokens`` defaults to the scheduler's ServeConfig value;
    ``tier`` routes page allocation (a name from
    ``repro.core.domains.TIERS`` or a CriticalityTier); ``key`` is the
    request's sampling PRNGKey (defaults to PRNGKey(0), exactly like
    ``generate``)."""

    rid: Any
    tokens: Any                       # prompt token ids, shape (prompt_len,)
    max_new_tokens: Optional[int] = None
    tier: Any = "cheap"
    key: Optional[jax.Array] = None
    # Modality inputs beyond tokens, UNBATCHED (whisper ``frames`` of
    # shape (enc_len, d_model), VLM ``patches`` of (enc_len,
    # frontend_dim)); the scheduler adds the batch axis at admission.
    # Only consumed on the state-arena route; the paged route serves
    # token-only families and rejects extras.
    extras: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class RequestResult:
    rid: Any
    tokens: np.ndarray                # (1, max_new_tokens), like generate()
    page_ids: np.ndarray
    placement: Optional[RequestPlacement]
    voltage: Optional[float]          # KV-domain voltage at admission
    ttft_steps: Optional[int] = None  # steps from admission to token 0
    pages_shared: int = 0             # prefix pages mapped read-only
    shard: int = 0                    # mesh shard that served the request
    # Host record (time.perf_counter seconds, the request spans' stamps):
    # the serving slot (global: shard * slots_per_shard + slot), when
    # the request was submitted and placed, and when each token reached
    # the host.
    slot: Optional[int] = None
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_tokens: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class SelfHealConfig:
    """Policy of the self-healing loop: online ECC telemetry -> live
    fault-map posterior -> in-step page migration -> block quarantine.

    Requires an ECC'd KV domain in ``kv_injection='read'`` mode: the
    SECDED correction counters *are* the telemetry signal, and read-mode
    storage (clean buffers, corruption applied at load) is what makes a
    migrated page's payload bit-identical to a standalone replay on its
    final placement.

    ``max_migrations`` sizes the per-shard in-step migration slots (the
    donated step always carries that many src/dst lanes; idle lanes
    point at the scratch page).  ``migrate_tier`` places migration
    targets (strictest first -- a page is being moved *because* its row
    went bad); ``fallback_tier`` is tried when the strict tier is
    exhausted.  ``setpoint_cap`` bounds the graceful-degradation
    escalation: when admission fails under quarantine pressure, the
    shard's rate setpoint is raised x10 (up to the cap) instead of
    crashing the loop.
    """

    suspect_threshold: float = 0.9
    max_migrations: int = 4
    migrate_tier: Any = "shared_prefix"
    fallback_tier: Any = "cheap"
    setpoint_cap: float = 1.0


@dataclasses.dataclass
class _AdmitPlan:
    """Host-side page plan of one admission."""

    row: np.ndarray                   # (n_logical_pages,) page-table row
    retained: np.ndarray              # shared prefix pages mapped read-only
    eligible: bool                    # may register / extend the prefix cache
    matched: int                      # shared prefix length (tokens)
    fs: int                           # retained page count (full pages)
    cover: int                        # pages holding prompt rows
    fork_src: int                     # shared boundary page (scratch = none)
    fork_rows: int                    # clean rows to COW-copy
    cursor0: int                      # first prompt position to prefill
    wstart0: int                      # write floor (shared rows are r/o)


@dataclasses.dataclass
class _Shard:
    """Per-shard runtime: the shard's own arena-backed page pool (its
    fault map drawn from the shard's seed), paged-cache helper, voltage
    governor + setpoint, injection method resolved against the shard's
    map, and the donated admission-time jits specialized to the shard's
    slice of the stacked pool state."""

    index: int
    seed: Optional[int]
    plan: Any
    pool: PagePool
    kvc: PagedKVCache
    governor: Any
    setpoint: Optional[float]
    method: str
    voltage: float
    admit_reset: Any = None
    transition_pool: Any = None
    # Self-healing runtime (None unless SelfHealConfig is passed):
    posterior: Any = None             # FaultMapPosterior over this map
    allocator: Any = None             # adopted DomainAllocator (quarantine)
    suspects: Any = None              # current suspect (pc, row) set
    retired_blocks: Any = None        # (pc, blk) already quarantined
    migrations: int = 0
    migration_stalls: int = 0
    setpoint_escalations: int = 0


class ContinuousBatchingScheduler:
    """Serve overlapping requests through one compiled mixed
    prefill/decode step.

    ``num_slots`` bounds concurrent requests (the compiled step's batch
    width); ``num_pages`` x ``page_slots`` sizes the shared KV pool;
    ``max_active`` optionally throttles admissions below ``num_slots``
    (benchmarks use it to sweep concurrency on one compiled step).

    With ``mesh`` (a 1-D serve mesh from ``make_serve_mesh``), slots
    and pages are global totals split evenly across the mesh's shards;
    each shard draws its own fault map from ``shard_seeds`` (default:
    ``fleet_map_seeds`` of the plan's seed, so shard 0 reproduces the
    single-device map) and, under a governor, admits against its own
    ``shard_setpoints`` entry -- a heterogeneous-voltage fleet on one
    compiled step.

    This class is also the zoo's single serving front door: families
    whose cache does not page (no ``SUPPORTS_PAGED`` on the module --
    MoE/MLA, recurrent-state hybrids, xLSTM, whisper, VLM) are
    dispatched from ``__new__`` to the state-arena route
    (:class:`repro.serving.statearena.StateArenaScheduler`), which
    honors the same construction surface and the same contracts (one
    donated step, flat trace/launch budgets, bit-exact solo replay).
    """

    def __new__(cls, bundle: Optional[ArchBundle] = None, *args,
                **kwargs):
        if (cls is ContinuousBatchingScheduler and bundle is not None
                and not getattr(bundle.module, "SUPPORTS_PAGED", False)):
            from repro.serving.statearena import StateArenaScheduler
            return object.__new__(StateArenaScheduler)
        return object.__new__(cls)

    def __init__(self, bundle: ArchBundle, cfg: ArchConfig, params,
                 sc: ServeConfig, *, num_slots: int, num_pages: int,
                 page_slots: int, max_active: Optional[int] = None,
                 dist=None, interpret: Optional[bool] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 mesh_axis: str = "serve",
                 shard_seeds: Optional[Sequence[int]] = None,
                 shard_setpoints: Optional[Sequence[float]] = None,
                 self_heal: Optional[SelfHealConfig] = None,
                 obs: Optional[ObsConfig] = None):
        if sc.kv_injection == "rewrite":
            raise ValueError(
                "kv_injection='rewrite' re-injects whole contiguous "
                "caches every token; the scheduler's caches are paged "
                "and the legacy segment walker cannot address pages. "
                "Use 'read' (fused, default via 'auto') or 'write' "
                "(incremental), or serve one-shot batches through "
                "generate() if you need the rewrite oracle")
        if sc.kv_injection not in ("auto", "read", "write"):
            raise ValueError(f"unknown kv_injection {sc.kv_injection!r}")
        self.bundle = bundle
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self.dist = dist
        self.mesh = mesh
        self._axis = mesh_axis
        if mesh is not None:
            if mesh_axis not in mesh.axis_names:
                raise ShardLayoutError(
                    f"mesh axis {mesh_axis!r} missing from mesh axes "
                    f"{tuple(mesh.axis_names)}: build the serving mesh "
                    "with launch.mesh.make_serve_mesh (1-D, axis "
                    "'serve') or pass mesh_axis=<your axis>")
            other = [a for a in mesh.axis_names
                     if a != mesh_axis and mesh.shape[a] > 1]
            if other:
                raise ShardLayoutError(
                    f"serve mesh must be 1-D: axes {other} have size > "
                    "1 besides the serve axis (model-parallel axes are "
                    "not supported by the sharded scheduler)")
            if dist is not None:
                raise ShardLayoutError(
                    "mesh and dist are mutually exclusive: the serve "
                    "mesh shards requests (data parallel); per-shard "
                    "model parallelism is not supported")
            self.n_shards = int(mesh.shape[mesh_axis])
        else:
            if shard_seeds is not None or shard_setpoints is not None:
                raise ShardLayoutError(
                    "shard_seeds/shard_setpoints require a serve mesh "
                    "(pass mesh=make_serve_mesh(n))")
            self.n_shards = 1
        self.num_slots = int(num_slots)
        self.max_active = int(num_slots if max_active is None
                              else max_active)
        if self.num_slots < 1 or not 1 <= self.max_active <= self.num_slots:
            raise ValueError(
                f"need 1 <= max_active ({self.max_active}) <= num_slots "
                f"({self.num_slots})")
        self.chunk = int(sc.prefill_chunk)
        if self.chunk < 1:
            raise ValueError(
                f"prefill_chunk={sc.prefill_chunk} must be >= 1: every "
                "step consumes at least one prompt token per prefilling "
                "slot")

        plan = (sc.undervolt
                if sc.undervolt is not None and sc.undervolt.enabled
                else None)
        base_seed = plan.map_seed if plan is not None else 0
        seeds, setpoints = validate_shard_layout(
            self.n_shards, self.num_slots, int(num_pages),
            base_seed=base_seed, seeds=shard_seeds,
            setpoints=shard_setpoints)
        self.shard_seeds = seeds
        self.slots_per_shard = self.num_slots // self.n_shards
        self.pages_per_shard = int(num_pages) // self.n_shards

        # ---- voltage control / injection mode (mirrors generate()) ----
        # Shard 0 carries the base plan exactly; the global checks below
        # run against it, then each shard re-resolves what depends on
        # its own fault map (method dispatch, governor frontier).
        self.governor = sc.governor
        pool0 = PagePool(bundle.module, cfg, max_len=sc.max_len,
                         page_slots=page_slots,
                         num_pages=self.pages_per_shard, plan=plan,
                         shard=(0 if mesh is not None else None))
        placed = pool0.placement is not None
        if self.governor is not None:
            if sc.kv_voltage is not None:
                raise ValueError(
                    "ServeConfig.governor and kv_voltage are mutually "
                    "exclusive voltage controls")
            if sc.undervolt is None or self.governor.plan is not sc.undervolt:
                raise ValueError(
                    "sc.governor must be built from sc.undervolt (its "
                    "frontier/capacity tables belong to that plan's "
                    "fault map and domains)")
            if not placed:
                raise ValueError(
                    "ServeConfig.governor is set but the undervolt plan "
                    "does not place 'kv_cache' (or is disabled): "
                    "admission governance would silently be a no-op")
            if self.governor.config.domain != pool0.domain.name:
                raise ValueError(
                    f"sc.governor governs domain "
                    f"{self.governor.config.domain!r} but the KV cache "
                    f"is placed in domain {pool0.domain.name!r}")
        if any(s is not None for s in setpoints) and self.governor is None:
            raise ShardLayoutError(
                "shard_setpoints need an admission governor "
                "(ServeConfig.governor): setpoints are per-shard "
                "governor walk targets")
        eff_v = sc.kv_voltage if sc.kv_voltage is not None else (
            pool0.domain.voltage if placed else None)
        sv = _static_value(eff_v) if eff_v is not None else None
        self.active = placed and (
            self.governor is not None
            or eff_v is None
            or sv is None                       # traced: assume live
            or sv < V_MIN - 1e-9)
        mode = sc.kv_injection
        if mode == "auto":
            mode = "read"
        self.mode = mode
        if self.active and sc.kv_method == "auto":
            if self.governor is not None:
                raise ValueError(
                    "kv_method='auto' cannot dispatch under an admission "
                    "governor (the KV voltage is re-planned per "
                    "admission); pass kv_method='word' or 'bitwise' "
                    "explicitly")
            if sv is None:
                raise ValueError(
                    "kv_method='auto' cannot dispatch from a traced "
                    "kv_voltage (method selection is static); pass "
                    "kv_method='word' or 'bitwise' explicitly for "
                    "traced voltage schedules")
        volt0 = float(sv) if sv is not None else (
            eff_v if eff_v is not None else 0.0)

        # ---- per-shard pools, fault maps, governors -------------------
        self._shards: List[_Shard] = []
        for k, seed in enumerate(seeds):
            if plan is None:
                plan_k = None
            elif int(seed) == int(plan.map_seed):
                plan_k = plan
            else:
                plan_k = dataclasses.replace(plan, map_seed=int(seed))
            pool_k = pool0 if (k == 0 and plan_k is plan) else PagePool(
                bundle.module, cfg, max_len=sc.max_len,
                page_slots=page_slots, num_pages=self.pages_per_shard,
                plan=plan_k, shard=(k if mesh is not None else None))
            gov_k = None
            if self.governor is not None:
                if plan_k is plan:
                    gov_k = self.governor
                else:
                    from repro.training.governor import VoltageGovernor
                    gov_k = VoltageGovernor(plan_k, self.governor.config)
            method_k = sc.kv_method
            if self.active and method_k == "auto":
                method_k = ("word" if pool_k.domain.ecc
                            else resolve_method(pool_k.faultmap,
                                                pool_k.placement, sv))
            self._shards.append(_Shard(
                index=k, seed=(int(seed) if plan is not None else None),
                plan=plan_k, pool=pool_k,
                kvc=PagedKVCache(pool_k, interpret=interpret),
                governor=gov_k, setpoint=setpoints[k], method=method_k,
                voltage=volt0))
        self.pool = self._shards[0].pool       # single-device back-compat
        self.kvc = self._shards[0].kvc
        self.method = self._shards[0].method

        # ---- self-healing loop (telemetry -> posterior -> migration) --
        self._heal = self_heal
        self._mig_slots = (self_heal.max_migrations
                           if self_heal is not None else 0)
        if self_heal is not None:
            if not placed or not pool0.domain.ecc:
                raise ValueError(
                    "self_heal needs an ECC'd KV-cache placement: the "
                    "SECDED correction counters are the telemetry "
                    "signal (place kv_cache in a domain with ecc=True)")
            if self.mode != "read":
                raise ValueError(
                    f"self_heal needs kv_injection='read' (got "
                    f"{self.mode!r}): read-mode pages store clean data, "
                    "which is what makes an in-step page copy land the "
                    "exact payload a replay on the final placement "
                    "prefills")
            if self_heal.max_migrations < 1:
                raise ValueError(
                    f"self_heal.max_migrations="
                    f"{self_heal.max_migrations} must be >= 1")
            from repro.core.faultmap_posterior import FaultMapPosterior
            for sh in self._shards:
                sh.posterior = FaultMapPosterior(sh.pool.faultmap)
                sh.suspects = set()
                sh.retired_blocks = set()
                # Long-lived ownership of the pool's arena blocks:
                # place() discards its internal allocators, so block
                # retirement adopts the placement into a fresh one and
                # registers the pool as the free()/quarantine() guard.
                alloc = DomainAllocator(sh.pool.faultmap.geometry,
                                        sh.pool.domain, sh.pool.faultmap)
                alloc.adopt(sh.pool.placement)
                alloc.register_pool(sh.pool)
                sh.allocator = alloc
        self._pending_mig: List[List[Tuple[int, int]]] = [
            [] for _ in range(self.n_shards)]
        self._telem_last = np.zeros(
            (self.n_shards, self._shards[0].pool.total_pages), np.int64)
        self._telem_u_last = self._telem_last.copy()

        # ---- bookkeeping (global slot id g = shard * S + slot) --------
        self.queue: collections.deque = collections.deque()
        self.results: Dict[Any, RequestResult] = {}
        s = self.num_slots
        self._slots: List[Optional[Any]] = [None] * s
        self._slot_priv: List[Optional[np.ndarray]] = [None] * s
        self._slot_shared: List[Optional[np.ndarray]] = [None] * s
        self._slot_plan: List[Optional[_AdmitPlan]] = [None] * s
        self._ptoks: List[Optional[np.ndarray]] = [None] * s
        self._dec_h = [True] * s
        self._cursor_h = [0] * s
        self._plen_h = [0] * s
        self._admit_step: Dict[Any, int] = {}
        self._out: Dict[Any, List[int]] = {}
        self._remaining: Dict[Any, int] = {}
        self._meta: Dict[Any, RequestResult] = {}
        self.steps = 0
        self.admitted = 0
        self.peak_active = 0
        self.traces: List[int] = []

        # ---- observability plane (metrics + event trace) --------------
        # Resolution order: explicit ctor kwarg > ServeConfig.obs >
        # default-on ObsConfig().  Counters ride the donated state as
        # one (n_shards, N_STEP_COUNTERS) int32 leaf -- accumulated
        # with pure jnp inside the compiled step (zero extra pallas
        # launches); events and latency are host-side only.
        self.obs = (obs if obs is not None
                    else sc.obs if sc.obs is not None else ObsConfig())
        self.layout_kinds = tuple(sorted(set(
            jax.tree_util.tree_leaves(cache_layouts(
                bundle.module.cache_specs(cfg, 1, sc.max_len),
                sc.max_len)))))
        self.metrics: Optional[MetricsRegistry] = None
        self.trace: Optional[EventTrace] = None
        if self.obs.enabled:
            self.metrics = MetricsRegistry(
                self.n_shards, self._shards[0].pool, config=self.obs,
                layouts=self.layout_kinds)
            self.trace = EventTrace(capacity=self.obs.trace_capacity)
            for sh in self._shards:
                sh.pool.on_event = functools.partial(
                    self._pool_event, sh.index)
        self._init_spans()

        self.state = self._init_state()
        if mesh is not None:
            from repro.launch.sharding import serve_sharding
            self.state = jax.device_put(self.state,
                                        serve_sharding(mesh, self._axis))
            spec = jax.sharding.PartitionSpec(self._axis)
            rep = jax.sharding.PartitionSpec()
            # One weight replica per shard device, placed once: the step
            # takes params replicated, and params left on one device
            # would be re-sent to every device on every step.  (A no-op
            # for params the caller already placed this way.)
            self.params = jax.device_put(
                params, jax.sharding.NamedSharding(mesh, rep))
            self._step = jax.jit(
                jax.shard_map(self._shard_body, mesh=mesh,
                              in_specs=(rep, spec, spec),
                              out_specs=(spec, spec), check_vma=False),
                donate_argnums=(1,))
        else:
            self._step = jax.jit(self._step_fn, donate_argnums=(1,))
        for k, sh in enumerate(self._shards):
            sh.admit_reset = jax.jit(
                functools.partial(self._admit_reset_fn, k),
                donate_argnums=(0,))
            sh.transition_pool = jax.jit(
                functools.partial(self._transition_pool_fn, k),
                donate_argnums=(0,))

    # ---- compiled pieces --------------------------------------------------
    def _init_state(self):
        n, s, c = self.n_shards, self.slots_per_shard, self.chunk
        pools = [sh.kvc.init_pool() for sh in self._shards]
        p = self._shards[0].pool
        out = {
            "pool": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *pools),
            "ptab": jnp.full((n, s, p.n_logical_pages),
                             p.scratch_id, jnp.int32),
            "qpos": jnp.zeros((n, s), jnp.int32),
            "tok": jnp.zeros((n, s, c), jnp.int32),
            "keys": jnp.zeros((n, s, 2), jnp.uint32),
            "active": jnp.zeros((n, s), bool),
            # per-slot phase: decoding (True) vs chunked-prefilling
            "dec": jnp.ones((n, s), bool),
            "cursor": jnp.zeros((n, s), jnp.int32),
            "plen": jnp.zeros((n, s), jnp.int32),
            "wstart": jnp.zeros((n, s), jnp.int32),
            # Self-healing lanes (all donated with the step; idle
            # migration slots carry the scratch sentinel).  "telem" /
            # "telem_u" accumulate per-page SECDED corrected /
            # uncorrectable counts; "chaos" is the row-goes-weak fault-
            # injection mask (per page, host-set, read-path only).
            "telem": jnp.zeros((n, p.total_pages), jnp.int32),
            "telem_u": jnp.zeros((n, p.total_pages), jnp.int32),
            "chaos": jnp.zeros((n, p.total_pages), bool),
            "mig_src": jnp.full((n, self._mig_slots), p.scratch_id,
                                jnp.int32),
            "mig_dst": jnp.full((n, self._mig_slots), p.scratch_id,
                                jnp.int32),
        }
        if self.obs.enabled:
            # In-step metric counters (see obs.metrics.STEP_COUNTERS):
            # donated with the rest of the state, diffed on host.
            out["mtr"] = init_step_counters(n)
        return out

    def _sample_one(self, logits, key):
        """Standalone-identical sampling on one (1, vocab) logits row
        (the engine's shared implementation, so the bit-equality
        contract has a single sampling code path)."""
        return sample_tokens(logits, key, self.sc.temperature)

    def _shard_step(self, k, params, state, v):
        """One shard's mixed prefill/decode step on its local state
        (leaves unstacked: pool (...), ptab (S, n_lp), ...).  Closes
        over the shard's kvc -- its fault map's seed and calibration
        constants fold into this branch at trace time, which is exactly
        how distinct shards get distinct weak-row draws and threshold
        tables inside ONE compiled program."""
        sh = self._shards[k]
        module = self.bundle.module
        c = self.chunk
        s = self.slots_per_shard
        act, dec = state["active"], state["dec"]
        cursor, plen = state["cursor"], state["plen"]
        ptab, pool_in = state["ptab"], state["pool"]
        chaos = state["chaos"] if self._heal is not None else None
        if self._heal is not None:
            # In-step page migration: copy suspect pages to their
            # healthy targets, then rewrite every page-table entry
            # naming a source -- BEFORE the decode read, so this step
            # already attends (and writes) through the new placement.
            # Idle lanes are scratch->scratch copies; the sentinel must
            # be excluded from the rewrite match because inactive page-
            # table rows legitimately hold the scratch id.
            src, dst = state["mig_src"], state["mig_dst"]
            pool_in = sh.kvc.migrate_pages(pool_in, src, dst)
            moving = (src != sh.pool.scratch_id)
            eq = ((ptab[:, :, None] == src[None, None, :])
                  & moving[None, None, :])
            repl = jnp.where(eq, dst[None, None, :], 0).sum(-1)
            ptab = jnp.where(eq.any(-1), repl.astype(ptab.dtype), ptab)
        cols = jnp.arange(c, dtype=jnp.int32)
        # Token-lane positions: decode lanes use column 0 only, prefill
        # lanes are this step's prompt chunk; -1 lanes are causally
        # dead and their cache writes are suppressed.
        pref_pos = cursor[:, None] + cols[None, :]
        pref_pos = jnp.where(pref_pos < plen[:, None], pref_pos, -1)
        dec_pos = jnp.where(cols[None, :] == 0, state["qpos"][:, None], -1)
        pos = jnp.where(dec[:, None], dec_pos, pref_pos)
        prefill_end = jnp.where(act & ~dec,
                                jnp.minimum(cursor + c, plen), 0)
        # Read-path masks run in EVERY mode: idempotent on privately
        # stored-corrupt pages, and the only way clean shared pages can
        # read as each tenant's standalone stored-corrupt values.
        ctx = sh.kvc.make_ctx(
            ptab, v, method=sh.method, inject=self.active,
            dec=dec, wstart=state["wstart"], prefill_end=prefill_end,
            chaos=chaos)
        ks = jax.vmap(jax.random.split)(state["keys"])
        new_keys, ki = ks[:, 0], ks[:, 1]
        logits, pool = module.decode_step(
            params, pool_in, {"tokens": state["tok"]}, pos,
            self.cfg, self.dist, fault_ctx=ctx)
        if self.active and self.mode in ("read", "write"):
            # write-path injection covers only decoding slots' writes;
            # prefill writes stay clean until the transition injection
            ptab_inj = jnp.where(dec[:, None], ptab,
                                 sh.pool.scratch_id)
            with jax.named_scope("write_path_inject"):
                pool = sh.kvc.post_step_inject(
                    pool, ptab_inj, state["qpos"], v, mode=self.mode,
                    method=sh.method)
        # sample column: decode lanes at 0, a finishing prefill at its
        # last prompt lane (the standalone post-prefill logits row)
        fin = act & ~dec & (plen - cursor <= c)
        sampling = act & (dec | fin)
        with jax.named_scope("sample"):
            if c == 1:
                lg = logits
            else:
                col = jnp.where(dec, 0,
                                jnp.clip(plen - 1 - cursor, 0, c - 1))
                lg = jnp.take_along_axis(logits, col[:, None, None],
                                         axis=1)[:, 0]
            nt = jax.vmap(lambda l, kk: self._sample_one(l[None], kk)[0])(
                lg, ki)[:, None]
        pad = jnp.zeros((s, c - 1), jnp.int32)
        nt_row = jnp.concatenate([nt, pad], axis=1) if c > 1 else nt
        telem, telem_u = state["telem"], state["telem_u"]
        if self._heal is not None:
            # Telemetry scrub: per-page SECDED event counts over every
            # referenced page, accumulated into the donated counters
            # (pure jnp on the stored buffers -- same mask math as the
            # kernel, zero extra pallas launches, read on host at the
            # existing token gather).
            corr, bad = sh.kvc.scrub_telemetry(pool, ptab, v,
                                               chaos=chaos)
            telem = telem + corr
            telem_u = telem_u + bad
        new_state = {
            "pool": pool,
            "ptab": ptab,
            "qpos": state["qpos"] + (act & dec).astype(jnp.int32),
            "tok": jnp.where(sampling[:, None], nt_row, state["tok"]),
            # keys advance only where a token was sampled, so a
            # request's key trajectory matches standalone generate()
            "keys": jnp.where(sampling[:, None], new_keys, state["keys"]),
            "active": act,
            "dec": dec,       # the prefill->decode flip happens on host
            "cursor": jnp.where(act & ~dec,
                                jnp.minimum(cursor + c, plen), cursor),
            "plen": plen,
            "wstart": state["wstart"],
            "telem": telem,
            "telem_u": telem_u,
            "chaos": state["chaos"],
            "mig_src": state["mig_src"],
            "mig_dst": state["mig_dst"],
        }
        if self.obs.enabled:
            # In-step metrics: pure jnp over masks already live in this
            # trace (no extra launches, no host sync) -- computed from
            # the PRE-step phase/cursor values, matching what this step
            # actually did.
            new_state["mtr"] = state["mtr"] + step_counter_delta(
                act=act, dec=dec, cursor=cursor, plen=plen,
                wstart=state["wstart"], chunk=c,
                n_logical_pages=sh.pool.n_logical_pages,
                mig_src=state["mig_src"], scratch_id=sh.pool.scratch_id)
        return new_state, nt

    def _step_fn(self, params, state, v):
        """Reference all-shard step on the stacked state (the mesh-less
        execution path, and the jaxpr surface tests/benchmarks count
        pallas launches on -- one launch per shard).  ``v`` may be a
        scalar (broadcast: homogeneous fleet) or a (n_shards,) vector
        of per-shard voltages."""
        self.traces.append(1)
        v = jnp.broadcast_to(jnp.asarray(v, jnp.float32),
                             (self.n_shards,))
        outs = [self._shard_step(
                    k, params,
                    jax.tree_util.tree_map(lambda x: x[k], state), v[k])
                for k in range(self.n_shards)]
        new_state = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[o[0] for o in outs])
        return new_state, jnp.stack([o[1] for o in outs])

    def _shard_body(self, params, state, v):
        """shard_map body: every device runs its own shard's branch of
        one switch on the serve-axis index.  Each branch is
        seed-specialized (static fault-map constants), state slices are
        shard-local, and nothing crosses the mesh -- the compiled step
        has zero collectives."""
        self.traces.append(1)
        idx = jax.lax.axis_index(self._axis)
        local = jax.tree_util.tree_map(lambda x: x[0], state)
        branches = [functools.partial(self._shard_step, k)
                    for k in range(self.n_shards)]
        new_local, nt = jax.lax.switch(idx, branches, params, local, v[0])
        return (jax.tree_util.tree_map(lambda x: x[None], new_local),
                nt[None])

    def _admit_reset_fn(self, k, pool_tree, reset_ids, fork_src,
                        fork_dst, fork_rows, fork_pos0):
        sh = self._shards[k]
        sub = jax.tree_util.tree_map(lambda x: x[k], pool_tree)
        sub = sh.kvc.reset_and_fork(sub, reset_ids, fork_src, fork_dst,
                                    fork_rows, fork_pos0)
        return jax.tree_util.tree_map(lambda x, y: x.at[k].set(y),
                                      pool_tree, sub)

    def _transition_pool_fn(self, k, pool_tree, priv, shared, v):
        """Prefill->decode transition injection: the paged twin of the
        standalone engine's post-prefill ``init_inject`` over the whole
        cache.  Private pages take the mode's full treatment; pages
        that are (or just became) shared keep their K/V clean in every
        mode -- the kernel's always-on read-path masks reproduce the
        standalone stored corruption at load -- and only their ``pos``
        bookkeeping takes write-path faults (same physical words and
        values for every tenant, so replays agree)."""
        sh = self._shards[k]
        sub = jax.tree_util.tree_map(lambda x: x[k], pool_tree)
        sub = sh.kvc.inject_pages(
            sub, priv, v, method=sh.method,
            skip_kv=(self.mode == "read"))
        sub = sh.kvc.inject_pages(sub, shared, v, method=sh.method,
                                  skip_kv=True)
        return jax.tree_util.tree_map(lambda x, y: x.at[k].set(y),
                                      pool_tree, sub)

    # ---- host loop --------------------------------------------------------
    def submit(self, request: Request) -> None:
        n_new = (request.max_new_tokens
                 if request.max_new_tokens is not None
                 else self.sc.max_new_tokens)
        if int(n_new) < 1:
            raise ValueError(
                f"request {request.rid!r}: max_new_tokens={n_new} must "
                "be >= 1 (every admitted request samples at least the "
                "prefill token)")
        plen = int(np.asarray(request.tokens).reshape(-1).shape[0])
        if plen < 1:
            raise ValueError(
                f"request {request.rid!r}: empty prompt")
        if request.extras:
            raise ValueError(
                f"request {request.rid!r}: extras "
                f"{sorted(request.extras)} on the paged route; the "
                f"{self.cfg.family!r} family is token-only (modality "
                "extras belong to state-arena families: whisper frames, "
                "vlm patches)")
        if plen > self.sc.max_len:
            raise ValueError(
                f"request {request.rid!r}: prompt length {plen} exceeds "
                f"max_len={self.sc.max_len}; chunked prefill writes the "
                "prompt through the paged ring in place and cannot "
                "rotate it (serve long prompts through generate())")
        self._t_submit[request.rid] = time.perf_counter_ns()
        self.queue.append(request)

    @property
    def n_active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    def shard_active(self, k: int) -> int:
        s = self.slots_per_shard
        return sum(1 for r in self._slots[k * s:(k + 1) * s]
                   if r is not None)

    def shard_plan(self, k: int):
        """Shard ``k``'s undervolt plan (its own fault-map seed) -- the
        plan a standalone ``generate()`` replay of a request served on
        shard ``k`` must run against."""
        return self._shards[k].plan

    @property
    def _voltage(self) -> float:
        return self._shards[0].voltage

    @_voltage.setter
    def _voltage(self, v: float) -> None:
        # homogeneous override (benchmarks set a fleet-wide schedule)
        for sh in self._shards:
            sh.voltage = float(v)

    def _volt_vec(self):
        return jnp.asarray([sh.voltage for sh in self._shards],
                           jnp.float32)

    def _free_slot_in(self, k: int) -> Optional[int]:
        s = self.slots_per_shard
        for g in range(k * s, (k + 1) * s):
            if self._slots[g] is None:
                return g
        return None

    def _plan_pages(self, k: int, req: Request, prompt: np.ndarray,
                    n_new: int) -> _AdmitPlan:
        """Match the prompt against shard ``k``'s prefix cache, retain
        the shared pages, and allocate the rest: prospective-shared
        pages (those that will hold prompt rows and be published at the
        transition) under the strictest ``shared_prefix`` tier, the
        remainder under the request's own tier.  Raises CapacityError
        with every side effect rolled back."""
        p = self._shards[k].pool
        ps = p.page_slots
        plen = prompt.shape[0]
        holder = ("__req__", req.rid)
        # no sharing when generation would wrap the ring into the
        # read-only prefix pages, and none at all on non-uniform
        # (window) layouts: COW prefix matching keys on page-aligned
        # position prefixes, which only line up across requests when
        # every ring is full-length (window tables are position-modular)
        eligible = (bool(self.sc.share_prefix) and p.uniform
                    and plen + n_new <= p.max_len)
        if eligible:
            matched, spids = p.match_prefix(prompt)
        else:
            matched, spids = 0, np.zeros((0,), np.int32)
        fs, r = matched // ps, matched % ps
        # partial matches are page-aligned by construction; only a
        # full-prompt match can end inside a page (COW boundary fork)
        assert r == 0 or matched == plen
        cover = -(-plen // ps)
        retained = spids[:fs].astype(np.int32)
        if fs:
            p.retain(retained, holder)
        try:
            fork_dst = -1
            if r:
                fork_dst = p.cow_fork(int(spids[fs]), "shared_prefix")
            try:
                n_share = cover - fs - (1 if r else 0)
                share_new = (p.alloc(n_share, "shared_prefix")
                             if eligible and n_share else
                             np.zeros((0,), np.int32))
                try:
                    n_rest = (p.n_logical_pages - cover if eligible
                              else p.n_logical_pages)
                    rest = p.alloc(n_rest, req.tier)
                except CapacityError:
                    if len(share_new):
                        p.free(share_new)
                    raise
            except CapacityError:
                if fork_dst >= 0:
                    p.free([fork_dst])
                raise
        except CapacityError:
            if fs:
                p.release(retained, holder)
            raise
        fork = (np.array([fork_dst], np.int32) if r
                else np.zeros((0,), np.int32))
        row = np.concatenate([retained, fork, share_new, rest])
        assert row.shape[0] == p.n_logical_pages
        return _AdmitPlan(
            row=row, retained=retained, eligible=eligible,
            matched=matched, fs=fs, cover=(cover if eligible else 0),
            fork_src=(int(spids[fs]) if r else p.scratch_id),
            fork_rows=r,
            cursor0=(matched if matched < plen else plen - 1),
            wstart0=(matched if matched < plen else plen))

    def _rollback(self, k: int, plan: _AdmitPlan, rid) -> None:
        p = self._shards[k].pool
        if plan.fs:
            p.release(plan.retained, ("__req__", rid))
        p.free(plan.row[plan.fs:])

    def _shard_order(self) -> List[int]:
        """Admission routing: shards with a free slot, most free pages
        first (ties to the lowest index) -- page-level load balancing
        that also spreads tenants across fault maps."""
        order = [k for k in range(self.n_shards)
                 if self._free_slot_in(k) is not None]
        order.sort(key=lambda k: (-self._shards[k].pool.free_pages, k))
        return order

    def _try_admit_on(self, k: int, req: Request, prompt: np.ndarray,
                      n_new: int) -> bool:
        """One shard's full admission attempt (pages, then governor),
        rolled back and reported as False on backpressure."""
        g = self._free_slot_in(k)
        if g is None:
            return False
        plan = None
        while plan is None:
            try:
                plan = self._plan_pages(k, req, prompt, n_new)
            except CapacityError:
                if not self._shards[k].pool.evict_prefix():
                    return False               # backpressure on this shard
        sh = self._shards[k]
        if sh.governor is not None:
            # the governed domain must keep the WHOLE post-admission
            # working set of ITS shard usable (the scheduler's analog
            # of generate()'s whole-batch bytes), not just the new
            # request's cache
            need = (self.shard_active(k) + 1) * sh.pool.request_words * 4
            try:
                sh.voltage = sh.governor.admit(need,
                                               setpoint=sh.setpoint)
            except CapacityError:
                # Graceful degradation under quarantine pressure: relax
                # the shard's rate setpoint one decade and retry before
                # reporting backpressure.
                if not self._escalate_setpoint(k):
                    self._rollback(k, plan, req.rid)
                    return False
                try:
                    sh.voltage = sh.governor.admit(need,
                                                   setpoint=sh.setpoint)
                except CapacityError:
                    self._rollback(k, plan, req.rid)
                    return False
        self.queue.popleft()
        self._admit(req, g, plan, prompt, n_new)
        return True

    def admit_pending(self) -> int:
        """Admit queued requests FIFO until every shard's slots, page
        pool, or governor pushes back (evicting idle prefix-cache
        entries before giving up).  Returns the number admitted."""
        n = 0
        with self._span["sched.admit"]:
            while self.queue and self.n_active < self.max_active:
                req = self.queue[0]
                prompt = np.asarray(req.tokens, np.int32).reshape(-1)
                n_new = int(req.max_new_tokens
                            if req.max_new_tokens is not None
                            else self.sc.max_new_tokens)
                if not any(self._try_admit_on(k, req, prompt, n_new)
                           for k in self._shard_order()):
                    self._emit("backpressure", rid=req.rid,
                               queued=len(self.queue),
                               active=self.n_active)
                    break                      # backpressure: wait
                n += 1
        return n

    def _admit(self, req: Request, g: int, plan: _AdmitPlan,
               prompt: np.ndarray, n_new: int) -> None:
        k, s = divmod(g, self.slots_per_shard)
        sh = self._shards[k]
        p = sh.pool
        plen = prompt.shape[0]
        # scrub the freshly allocated pages (stale-tenant data) and COW-
        # copy the shared boundary page's clean prompt rows; retained
        # shared entries are passed as scratch (reset there is a no-op)
        reset_row = plan.row.copy()
        reset_row[:plan.fs] = p.scratch_id
        st = self.state
        pool_tree = sh.admit_reset(
            st["pool"], jnp.asarray(reset_row),
            jnp.int32(plan.fork_src),
            jnp.int32(plan.row[plan.fs] if plan.fork_rows
                      else p.scratch_id),
            jnp.int32(plan.fork_rows), jnp.int32(plan.fs * p.page_slots))
        key = req.key if req.key is not None else jax.random.PRNGKey(0)
        self.state = {
            **st,
            "pool": pool_tree,
            "ptab": st["ptab"].at[k, s].set(jnp.asarray(plan.row)),
            "qpos": st["qpos"].at[k, s].set(plen),
            "keys": st["keys"].at[k, s].set(key),
            "active": st["active"].at[k, s].set(True),
            "dec": st["dec"].at[k, s].set(False),
            "cursor": st["cursor"].at[k, s].set(plan.cursor0),
            "plen": st["plen"].at[k, s].set(plen),
            "wstart": st["wstart"].at[k, s].set(plan.wstart0),
        }
        self._slots[g] = req.rid
        self._slot_shared[g] = plan.retained.copy()
        self._slot_priv[g] = plan.row[plan.fs:].copy()
        self._slot_plan[g] = plan
        self._ptoks[g] = prompt
        self._dec_h[g] = False
        self._cursor_h[g] = plan.cursor0
        self._plen_h[g] = plen
        self._admit_step[req.rid] = self.steps
        self._out[req.rid] = []
        self._remaining[req.rid] = n_new
        self._meta[req.rid] = RequestResult(
            rid=req.rid, tokens=None, page_ids=plan.row.copy(),
            placement=p.request_placement(plan.row),
            voltage=(sh.voltage if p.placement is not None else None),
            pages_shared=plan.fs, shard=k)
        self._stamp_admit(req.rid, g)
        self.admitted += 1
        self.peak_active = max(self.peak_active, self.n_active)
        self._emit("admission", shard=k, rid=req.rid, plen=int(plen),
                   n_new=int(n_new), pages_shared=int(plan.fs),
                   voltage=(float(sh.voltage)
                            if p.placement is not None else None))
        if plan.fork_rows:
            self._emit("cow_fork", shard=k, rid=req.rid,
                       src=int(plan.fork_src),
                       dst=int(plan.row[plan.fs]),
                       rows=int(plan.fork_rows))

    def _transition(self, g: int) -> None:
        """Prefill finished this step: publish shareable pages, inject
        the request's pages (the standalone ``init_inject`` twin), and
        flip the slot to the decode phase."""
        k, s = divmod(g, self.slots_per_shard)
        rid = self._slots[g]
        plan = self._slot_plan[g]
        sh = self._shards[k]
        p = sh.pool
        if plan.eligible:
            own = plan.row[plan.fs:plan.cover]
            if len(own):
                p.share(own, ("__req__", rid))
                self._slot_shared[g] = np.concatenate(
                    [self._slot_shared[g], own])
                self._slot_priv[g] = plan.row[plan.cover:].copy()
            prompt = self._ptoks[g]
            plen = prompt.shape[0]
            lengths = list(range(p.page_slots, plen, p.page_slots))
            for ln in lengths + [plen]:
                p.register_prefix(prompt[:ln],
                                  plan.row[:-(-ln // p.page_slots)])
        st = self.state
        new_state = {**st, "dec": st["dec"].at[k, s].set(True)}
        if self.active:
            pad = np.full(p.n_logical_pages, p.scratch_id, np.int32)
            priv = pad.copy()
            priv[:len(self._slot_priv[g])] = self._slot_priv[g]
            shared = pad.copy()
            nsh = plan.cover if plan.eligible else 0
            shared[:nsh] = plan.row[:nsh]
            new_state["pool"] = sh.transition_pool(
                st["pool"], jnp.asarray(priv), jnp.asarray(shared),
                jnp.float32(sh.voltage))
        self.state = new_state
        self._dec_h[g] = True

    def _collect(self, g: int, rid, token: int) -> None:
        out = self._out[rid]
        if not out:
            self._meta[rid].ttft_steps = (self.steps
                                          - self._admit_step[rid])
        self._stamp_token(rid)
        out.append(int(token))
        self._remaining[rid] -= 1
        if self._remaining[rid] == 0:
            self._retire(g)

    def _retire(self, g: int) -> None:
        k, s = divmod(g, self.slots_per_shard)
        sh = self._shards[k]
        rid = self._slots[g]
        res = self._meta.pop(rid)
        res.tokens = np.asarray(self._out.pop(rid), np.int32)[None, :]
        self.results[rid] = res
        self._emit("retirement", shard=k, rid=rid,
                   tokens=int(res.tokens.shape[1]),
                   ttft_steps=res.ttft_steps)
        if len(self._slot_shared[g]):
            sh.pool.release(self._slot_shared[g], ("__req__", rid))
        if len(self._slot_priv[g]):
            sh.pool.free(self._slot_priv[g])
        del self._remaining[rid]
        del self._admit_step[rid]
        self._slots[g] = None
        self._slot_priv[g] = None
        self._slot_shared[g] = None
        self._slot_plan[g] = None
        self._ptoks[g] = None
        self._dec_h[g] = True
        st = self.state
        self.state = {
            **st,
            "ptab": st["ptab"].at[k, s].set(sh.pool.scratch_id),
            "active": st["active"].at[k, s].set(False),
            "dec": st["dec"].at[k, s].set(True),
        }

    def _feed_chunks(self) -> None:
        """Host -> device refresh of the prompt-chunk token lanes of
        every prefilling slot (decoding slots keep their sampled
        token in lane 0)."""
        idx = [g for g, r in enumerate(self._slots)
               if r is not None and not self._dec_h[g]]
        if not idx:
            return
        rows = np.zeros((len(idx), self.chunk), np.int32)
        for j, g in enumerate(idx):
            cur = self._cursor_h[g]
            t = self._ptoks[g][cur:cur + self.chunk]
            rows[j, :len(t)] = t
        ks = np.asarray([g // self.slots_per_shard for g in idx])
        ss = np.asarray([g % self.slots_per_shard for g in idx])
        self.state["tok"] = self.state["tok"].at[ks, ss].set(
            jnp.asarray(rows))

    # ---- self-healing loop ------------------------------------------------
    def _escalate_setpoint(self, k: int) -> bool:
        """Raise shard ``k``'s governor rate setpoint one decade (up to
        the configured cap) -- the graceful-degradation response to
        admission CapacityError once quarantine has eaten into the
        frontier.  Returns False when escalation does not apply (no
        self-healing, no setpoint, nothing quarantined, power-mode
        governor, or already at the cap)."""
        sh = self._shards[k]
        if (self._heal is None or sh.governor is None
                or sh.setpoint is None
                or sh.governor.config.mode not in ("rate", "adaptive",
                                                   "efficiency")
                or not sh.pool.quarantined_pages):
            return False
        cap = float(self._heal.setpoint_cap)
        if sh.setpoint >= cap:
            return False
        old = sh.setpoint
        sh.setpoint = min(sh.setpoint * 10.0, cap)
        sh.setpoint_escalations += 1
        self._emit("escalation", shard=k, setpoint_from=old,
                   setpoint_to=sh.setpoint)
        return True

    def weaken_row(self, k: int, pc: int, row: int) -> np.ndarray:
        """Chaos hook: make DRAM row ``row`` of shard ``k``'s pseudo-
        channel ``pc`` go weak *at runtime* -- every pool page whose
        K/V payload overlaps the row starts reading through weak-rate
        thresholds (read path only; stored data stays clean, so replay
        bit-identity is preserved).  Returns the affected page ids.
        The compiled step is untouched: the mask is a donated state
        leaf, not a trace-time constant."""
        if self._heal is None:
            raise ValueError(
                "weaken_row needs self_heal=SelfHealConfig(...): the "
                "chaos mask and telemetry lanes only exist under the "
                "self-healing loop")
        pids = self._shards[k].pool.pages_on_row(int(pc), int(row))
        if len(pids):
            self.state["chaos"] = self.state["chaos"].at[
                k, jnp.asarray(pids)].set(True)
        return pids

    def _plan_self_heal(self) -> None:
        """Host half, before the step: walk each shard's suspect rows,
        quarantine free victim pages outright, and stage up to
        ``max_migrations`` live-page migrations into the step's
        src/dst lanes (targets freshly allocated off suspect rows)."""
        heal = self._heal
        M = self._mig_slots
        for k, sh in enumerate(self._shards):
            if not sh.suspects or self._pending_mig[k]:
                continue
            p = sh.pool
            suspect_pages: List[int] = []
            seen = set()
            for (pc, row) in sorted(sh.suspects):
                for pid in p.pages_on_row(pc, row):
                    pid = int(pid)
                    if pid not in seen:
                        seen.add(pid)
                        suspect_pages.append(pid)
            free_victims = [pid for pid in suspect_pages
                            if not p.is_owned(pid)
                            and not p.is_quarantined(pid)]
            if free_victims:
                p.quarantine(free_victims)
            victims = [pid for pid in suspect_pages if p.is_owned(pid)]
            pairs: List[Tuple[int, int]] = []
            rejects: List[int] = []
            for src in victims[:M]:
                dst = None
                while dst is None:
                    try:
                        cand = int(p.alloc(1, heal.migrate_tier)[0])
                    except CapacityError:
                        try:
                            cand = int(p.alloc(1, heal.fallback_tier)[0])
                        except CapacityError:
                            # quarantine pressure: keep serving on the
                            # suspect page, retry next step
                            sh.migration_stalls += 1
                            break
                    if cand in seen:
                        rejects.append(cand)    # target itself suspect
                        continue
                    dst = cand
                if dst is None:
                    break
                pairs.append((src, dst))
            if rejects:
                p.quarantine(rejects)
            if not pairs:
                continue
            row_src = np.full(M, p.scratch_id, np.int32)
            row_dst = np.full(M, p.scratch_id, np.int32)
            for i, (s_, d_) in enumerate(pairs):
                row_src[i], row_dst[i] = s_, d_
            self.state["mig_src"] = self.state["mig_src"].at[k].set(
                jnp.asarray(row_src))
            self.state["mig_dst"] = self.state["mig_dst"].at[k].set(
                jnp.asarray(row_dst))
            self._pending_mig[k] = pairs

    def _finalize_self_heal(self) -> None:
        """Host half, after the step: the staged migrations have been
        applied on device (page copy + page-table rewrite), so commit
        the host accounting -- pool ownership and holder transfer,
        every host-side page-id array, each affected request's replay
        placement -- then retire fully-drained quarantined blocks
        through the adopted allocator."""
        for k, sh in enumerate(self._shards):
            p = sh.pool
            pairs = self._pending_mig[k]
            if pairs:
                for src, dst in pairs:
                    p.migrate(src, dst)
                    self._emit("migration", shard=k, src=int(src),
                               dst=int(dst))
                sh.migrations += len(pairs)

                def rewrite(arr):
                    if arr is None or not len(arr):
                        return
                    for src, dst in pairs:
                        arr[arr == src] = dst

                s0 = k * self.slots_per_shard
                for g in range(s0, s0 + self.slots_per_shard):
                    if self._slots[g] is None:
                        continue
                    rewrite(self._slot_priv[g])
                    rewrite(self._slot_shared[g])
                    rewrite(self._slot_plan[g].row)
                    rewrite(self._slot_plan[g].retained)
                # Only LIVE requests move: a retired request's recorded
                # placement is its decode-time history, and its freed
                # pages may since back a different tenant entirely.
                live = {self._slots[g]
                        for g in range(s0, s0 + self.slots_per_shard)
                        if self._slots[g] is not None}
                for rid in live:
                    meta = self._meta[rid]
                    rewrite(meta.page_ids)
                    meta.placement = p.request_placement(meta.page_ids)
                pad = jnp.full((self._mig_slots,), p.scratch_id,
                               jnp.int32)
                self.state["mig_src"] = (
                    self.state["mig_src"].at[k].set(pad))
                self.state["mig_dst"] = (
                    self.state["mig_dst"].at[k].set(pad))
                self._pending_mig[k] = []
            # Block retirement: quarantined-page blocks with no live or
            # free pages left can never serve again -- pull them out of
            # the allocator's recycling for good.
            wpc = p.faultmap.geometry.bytes_per_pc // 4
            segs = [
                s for s in p.retirable_blocks()
                if (s.pc, (s.phys_base_word - s.pc * wpc) // ALIGN_WORDS)
                not in sh.retired_blocks]
            if segs:
                sh.allocator.quarantine(tuple(segs))
                new_blocks = [
                    (s.pc,
                     (s.phys_base_word - s.pc * wpc) // ALIGN_WORDS)
                    for s in segs]
                sh.retired_blocks.update(new_blocks)
                for pc, blk in new_blocks:
                    self._emit("block_retire", shard=k, pc=int(pc),
                               block=int(blk))

    def _fold_telemetry(self) -> None:
        """Diff the donated correction counters (read host-side at the
        existing token-gather sync) and fold each changed page's counts
        into its shard's per-row posterior; refresh the suspect set and
        re-plan adaptive governors when it moves."""
        corr = np.asarray(self.state["telem"], np.int64)
        bad = np.asarray(self.state["telem_u"], np.int64)
        d_corr = corr - self._telem_last
        d_bad = bad - self._telem_u_last
        self._telem_last, self._telem_u_last = corr, bad
        for k, sh in enumerate(self._shards):
            hits = np.flatnonzero((d_corr[k] > 0) | (d_bad[k] > 0))
            if len(hits):
                cw = sh.pool.page_codewords()
                for pid in hits:
                    for (pc, row) in sh.pool.page_rows(int(pid)):
                        sh.posterior.observe(
                            pc, row, corrected=int(d_corr[k, pid]),
                            codewords=cw, voltage=sh.voltage,
                            uncorrectable=int(d_bad[k, pid]))
            new = set(sh.posterior.suspect_rows(
                sh.voltage, self._heal.suspect_threshold))
            if new != sh.suspects:
                sh.suspects = new
                if (sh.governor is not None
                        and sh.governor.config.mode == "adaptive"):
                    sh.governor.replan(sh.posterior)
                    self._emit("replan", shard=k,
                               suspect_rows=len(new))

    # ---- observability hooks ----------------------------------------------
    def _init_spans(self) -> None:
        """Host spans (obs on; :mod:`repro.obs.trace`): the step's
        phases, profiler annotations and span-log entries, and each
        request's ``sched.prefill``, which overlaps other steps and so
        is a log entry only.  The request's own record is its
        ``RequestResult`` (``t_submit``, ``t_admit``, ``t_tokens``)."""
        self._span = (self.metrics.spans if self.metrics is not None
                      else step_spans(None))
        self._t_submit: Dict[Any, int] = {}   # rid -> ns, until admitted
        self._t_arrive = 0          # when the newest tokens reached host

    def _stamp_admit(self, rid, g: int) -> None:
        """The request holds slot ``g``: its queue wait ends."""
        meta = self._meta[rid]
        meta.slot = g
        meta.t_submit = self._t_submit.pop(rid) * 1e-9
        meta.t_admit = time.perf_counter_ns() * 1e-9

    def _stamp_token(self, rid) -> None:
        """A token of ``rid`` reached the host at ``_t_arrive``; token 0
        closes its ``sched.prefill`` span, opened at admission."""
        meta = self._meta[rid]
        if not meta.t_tokens and self.metrics is not None:
            obs_trace.SPANS.add("sched.prefill",
                                round(meta.t_admit * 1e9), self._t_arrive,
                                rid, self.metrics.owner)
        meta.t_tokens.append(self._t_arrive * 1e-9)

    def _emit(self, kind: str, **kw) -> None:
        """Emit one trace event stamped with the current step index and
        the scheduler's cache-layout mix (no-op when tracing is
        disabled)."""
        if self.trace is not None:
            kw.setdefault("layout", "+".join(self.layout_kinds))
            self.trace.emit(kind, step=self.steps, **kw)

    def _pool_event(self, shard: int, kind: str, **data) -> None:
        """Pool-side event hook (quarantine / prefix_evict), bound
        per shard at construction."""
        self._emit(kind, shard=shard, **data)

    @property
    def pricing_voltages(self) -> List[float]:
        """Per-shard voltage the energy accountant prices HBM traffic
        at: the operating rail for placed (undervolted) shards, the
        nominal rail for clean ones."""
        return [sh.voltage if sh.pool.placement is not None else V_NOM
                for sh in self._shards]

    def step_once(self) -> None:
        """One mixed step: every prefilling slot consumes a prompt
        chunk, every decoding slot one token (single compiled call
        across all shards); then transition finished prefills, collect
        tokens, and retire finished requests.

        Spans (obs on): ``sched.step`` holds, in order, ``sched.feed``
        (prompt chunks, self-heal planning), ``sched.dispatch`` (the
        donated step enqueued), ``sched.sync`` (the wait for the device
        and the token read-back) and ``sched.post`` (host bookkeeping:
        self-heal commit, transitions, collection, retirement)."""
        sp = self._span
        with sp["sched.step"]:
            with sp["sched.feed"]:
                self._feed_chunks()
                if self._heal is not None:
                    self._plan_self_heal()
            with sp["sched.dispatch"]:
                self.state, nt = self._step(self.params, self.state,
                                            self._volt_vec())
            with sp["sched.sync"]:
                # (n_shards, S, 1) -> global slot order g = shard * S + slot
                toks = np.asarray(nt).reshape(-1)
                self._t_arrive = time.perf_counter_ns()
            self.steps += 1
            with sp["sched.post"]:
                if self._heal is not None:
                    self._finalize_self_heal()
                    self._fold_telemetry()
                for g, rid in enumerate(self._slots):
                    if rid is None:
                        continue
                    if self._dec_h[g]:
                        self._collect(g, rid, toks[g])
                        continue
                    cur = self._cursor_h[g]
                    fin = self._plen_h[g] - cur <= self.chunk
                    self._cursor_h[g] = min(cur + self.chunk,
                                            self._plen_h[g])
                    if fin:
                        self._transition(g)
                        self._collect(g, rid, toks[g])

    def run(self) -> Dict[Any, RequestResult]:
        """Drain the queue: admit / step / retire until every submitted
        request has finished.  Returns ``results`` (also kept on the
        scheduler)."""
        while self.queue or self.n_active:
            self.admit_pending()
            if not self.n_active:
                if not self.queue:
                    break
                # Nothing running anywhere and the head request still
                # cannot be admitted: it can never fit.  Re-run its
                # admission checks on the best-provisioned shard so the
                # capacity source raises its own error, naming the
                # shard.
                req = self.queue[0]
                prompt = np.asarray(req.tokens, np.int32).reshape(-1)
                n_new = int(req.max_new_tokens
                            if req.max_new_tokens is not None
                            else self.sc.max_new_tokens)
                k = max(range(self.n_shards),
                        key=lambda i: self._shards[i].pool.free_pages)
                sh = self._shards[k]
                plan = self._plan_pages(k, req, prompt, n_new)
                self._rollback(k, plan, req.rid)
                if sh.governor is not None:
                    sh.governor.admit(sh.pool.request_words * 4,
                                      setpoint=sh.setpoint)
                raise CapacityError(
                    "scheduler", sh.pool.request_words * 4,
                    sh.pool.free_pages * sh.pool.page_set_words * 4,
                    "admission stuck with an idle pool",
                    shard=sh.pool.shard)
            self.step_once()
        return self.results

    @property
    def stats(self) -> Dict[str, Any]:
        shards = [{
            "shard": sh.index,
            "active": self.shard_active(sh.index),
            "free_pages": sh.pool.free_pages,
            "weak_pages": sh.pool.num_weak_pages,
            "shared_pages": sh.pool.shared_pages,
            "voltage": sh.voltage,
            "setpoint": sh.setpoint,
            "map_seed": sh.seed,
        } for sh in self._shards]
        if self._heal is not None:
            for row, sh in zip(shards, self._shards):
                ps = sh.posterior.stats()
                row.update({
                    "corrected": ps["corrected"],
                    "uncorrectable": ps["uncorrectable"],
                    "tracked_rows": ps["tracked_rows"],
                    "suspect_rows": len(sh.suspects),
                    "migrations": sh.migrations,
                    "migration_stalls": sh.migration_stalls,
                    "quarantined_pages": len(sh.pool.quarantined_pages),
                    "quarantined_blocks": len(
                        sh.allocator.quarantined_blocks),
                    "setpoint_escalations": sh.setpoint_escalations,
                    "governor_replans": (sh.governor.replans
                                         if sh.governor is not None
                                         else 0),
                })
        out = {
            "route": "paged",
            "cache_layouts": list(self.layout_kinds),
            "steps": self.steps,
            "admitted": self.admitted,
            "peak_active": self.peak_active,
            "decode_traces": len(self.traces),
            "free_pages": sum(s["free_pages"] for s in shards),
            "voltage": self._voltage,
            "prefill_chunk": self.chunk,
            "shared_pages": sum(s["shared_pages"] for s in shards),
            "prefix_entries": sum(sh.pool.prefix_entries
                                  for sh in self._shards),
            "n_shards": self.n_shards,
            "shards": shards,
        }
        if self._heal is not None:
            for key in ("corrected", "uncorrectable", "migrations",
                        "quarantined_pages", "quarantined_blocks",
                        "setpoint_escalations"):
                out[key] = sum(s[key] for s in shards)
        if any(sh.governor is not None for sh in self._shards):
            from repro.training.governor import fleet_report
            out["fleet"] = fleet_report(
                [sh.governor for sh in self._shards],
                [sh.voltage for sh in self._shards],
                [sh.setpoint for sh in self._shards])
        if self.metrics is not None:
            out["obs"] = self.metrics.snapshot(
                self.state, voltages=self.pricing_voltages)
        if self.trace is not None:
            out["events"] = dict(self.trace.counts)
        return out
