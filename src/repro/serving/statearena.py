"""State-arena continuous batching: the scheduler route for families
whose decode state does not page.

The paged scheduler (serving/scheduler.py) serves families that
advertise ``SUPPORTS_PAGED``: uniform/window ring caches scattered into
a shared page pool.  Everything else in the zoo -- MoE with MLA
latents, recurrent-state hybrids (recurrentgemma), xLSTM matrix
memories, whisper's enc-dec decoder, the VLM wrapper -- carries cache
leaves the page pool cannot address (slotless recurrent state, cross-
attention K/V written once at prefill, full rings behind a module that
lacks the paged decode plumbing).  This module serves those families
through the SAME scheduler front door (``ContinuousBatchingScheduler``
dispatches here from ``__new__``) and the same contracts:

  * ONE jitted donated decode step for any mix of tenants -- the whole
    per-slot cache tree is batched along dim 0 (slot = batch row) and
    every step advances all active rows with a per-row position vector
    (``decode_traces == 1``, flat launch budget).
  * Admission runs the request's prefill through the *identical*
    memoized jitted prefill ``generate()`` uses (bucketed where the
    family pads, exact otherwise), samples the first token with the
    same key trajectory, applies the standalone post-prefill
    ``init_inject`` (``inject_group`` on the slot's placement), then
    scatters the (1, max_len) tree into the slot's batch row.
  * Placement is *tiered arena placement per slot*, fixed at
    construction: ``place_groups_tiered`` lays out ``num_slots``
    disjoint copies of the (batch=1, max_len) cache across the plan's
    domains at the state tier (default ``"cheap"`` -- carried state is
    fault-tolerant by default).  Fixed placements keep every per-slot
    threshold table a trace-time constant of the one donated step.
  * Persistent-fault semantics for carried state: the step's per-slot
    write-path injection (``inject_placement_slice``) corrupts ring
    leaves only at the slot just written but slotless ``state`` leaves
    *whole* -- and since recurrent state is rewritten every step, the
    stuck-at masks re-apply to every new value: a fault acquired on
    write persists for the lifetime of the request (corrupt-once-on-
    write), unlike ring rows that are written once and only re-masked
    idempotently.
  * Token equivalence: every request's tokens are bit-identical to a
    standalone ``generate()`` replay with ``kv_placement`` set to the
    slot's placement -- the scheduler performs the standalone engine's
    exact jitted calls (same prefill, same ``inject_group`` init, same
    ``inject_placement_slice`` post-step with the same placement
    constants, same ``sample_tokens`` key trajectory), just batched
    into slot rows.  MoE decode capacity is forced lossless at C=1
    (see ``models.moe.moe_ffn``) so batched routing cannot drop a
    token a solo replay would keep.

Extras (``Request.extras``): modality inputs beyond tokens -- whisper
``frames``, VLM ``patches`` -- passed unbatched and admitted with a
leading batch axis, exactly as ``generate()`` takes them.  VLM query
positions start at ``prompt_len + cfg.enc_len`` (image tokens occupy
the front of the ring), mirroring the engine's ``pos0``.

Whisper encoder sharing: with ``ServeConfig.share_prefix`` the
admission prefill is content-addressed -- identical (tokens, extras)
bytes reuse the previously computed (logits, cache) device buffers, so
repeated audio skips the encoder entirely.  This is a *host-side*
result reuse rather than the paged pool's COW page mapping (cross
leaves live in per-slot arena state, not shared pages); it is
numerically risk-free because the reused values come from the same
compiled prefill the replay runs.

MoE expert criticality tiering (``expert_probe=``): a probe token
batch drives ``module.routing_frequency``; experts are ranked and
placed tiered (hot quarter -> ``safe``, cold quarter ->
``disposable``, rest -> ``cheap``) via ``place_groups_tiered`` over
the plan's domains, and expert weights in unsafe domains are corrupted
ONCE at construction (write-path ``inject_group``).  Weights are never
rewritten, so the corruption is persistent by construction, and solo
replays are bit-exact trivially because they run on ``self.params``.

Not supported on this route (clear errors, not silent fallbacks):
serve meshes, self-healing (both need paged read-mode pools),
admission governors, ``kv_injection='read'``/``'rewrite'`` (no
read-path kernel addresses these layouts; auto resolves to 'write'),
and per-request tier routing (placements are fixed per slot; pass
``state_tier=`` at construction instead).
"""
from __future__ import annotations

import collections
import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as arena
from repro.core.engine import _static_value, resolve_method
from repro.core.domains import place_groups, place_groups_tiered
from repro.core.faultmodel import V_MIN, V_NOM
from repro.core.injection import inject_group
from repro.models import cache as C
from repro.models import dist as dist_lib
from repro.models.base import (ArchBundle, ArchConfig, cache_batch_axes,
                               cache_layouts, cache_slot_axes, spec_avals)
from repro.obs.metrics import (MetricsRegistry, ObsConfig,
                               init_step_counters, N_STEP_COUNTERS)
from repro.obs.trace import EventTrace
from repro.serving import scheduler as _sched
from repro.serving.engine import ServeConfig, bucketed_prefill, sample_tokens


def _batch_bytes(tree) -> bytes:
    """Content address of one admission batch (tokens + extras)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    h = []
    for p, a in flat:
        arr = np.asarray(a)
        h.append(jax.tree_util.keystr(p).encode())
        h.append(str(arr.shape).encode() + str(arr.dtype).encode())
        h.append(arr.tobytes())
    return b"|".join(h)


class StateArenaScheduler(_sched.ContinuousBatchingScheduler):
    """Continuous batching over per-slot arena-placed whole caches.

    Constructed through ``ContinuousBatchingScheduler(...)`` -- its
    ``__new__`` dispatches here when the family lacks
    ``SUPPORTS_PAGED``.  ``num_pages``/``page_slots`` are accepted for
    signature compatibility and ignored (there is no page pool).
    """

    def __init__(self, bundle: ArchBundle, cfg: ArchConfig, params,
                 sc: ServeConfig, *, num_slots: int, num_pages: int = 0,
                 page_slots: int = 0, max_active: Optional[int] = None,
                 dist=None, interpret: Optional[bool] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 mesh_axis: str = "serve",
                 shard_seeds: Optional[Sequence[int]] = None,
                 shard_setpoints: Optional[Sequence[float]] = None,
                 self_heal=None, obs: Optional[ObsConfig] = None,
                 state_tier: Any = "cheap",
                 expert_probe: Optional[Any] = None):
        module = bundle.module
        fam = getattr(cfg, "family", "?")
        if mesh is not None or shard_seeds is not None \
                or shard_setpoints is not None:
            raise _sched.ShardLayoutError(
                f"family {fam!r} serves through the state-arena route "
                "(no SUPPORTS_PAGED), which is single-shard: serve "
                "meshes partition the page pool, and this family's "
                "cache does not page")
        if self_heal is not None:
            raise ValueError(
                "self_heal needs paged read-mode caches (ECC telemetry "
                "and page migration address pool pages); the state-"
                f"arena route serving family {fam!r} has no page pool")
        if sc.governor is not None:
            raise ValueError(
                "ServeConfig.governor walks the paged pool's capacity "
                "frontier; the state-arena route has fixed per-slot "
                "placements decided at construction (re-plan by "
                "rebuilding the scheduler)")
        if sc.kv_injection == "rewrite":
            raise ValueError(
                "kv_injection='rewrite' is the legacy one-shot oracle; "
                "the scheduler's donated step injects incrementally. "
                "Use 'write' (or 'auto')")
        if sc.kv_injection == "read":
            raise ValueError(
                f"kv_injection='read' needs a family with read-path "
                f"support; family {fam!r} serves on the state-arena "
                "route where faults ride the write path ('write' or "
                "'auto')")
        if sc.kv_injection not in ("auto", "write"):
            raise ValueError(f"unknown kv_injection {sc.kv_injection!r}")
        self.mode = "write"

        self.bundle, self.cfg, self.params = bundle, cfg, params
        self.sc, self.dist = sc, dist
        self.mesh = None
        self.n_shards = 1
        self.num_slots = int(num_slots)
        self.slots_per_shard = self.num_slots
        self.max_active = int(num_slots if max_active is None
                              else max_active)
        if self.num_slots < 1 or not 1 <= self.max_active <= self.num_slots:
            raise ValueError(
                f"need 1 <= max_active ({self.max_active}) <= num_slots "
                f"({self.num_slots})")

        # ---- cache geometry / layouts ---------------------------------
        S = self.num_slots
        self._specs1 = module.cache_specs(cfg, 1, sc.max_len)
        self._specsS = module.cache_specs(cfg, S, sc.max_len)
        self.cache_avals1 = spec_avals(self._specs1)
        self.slot_axes1 = cache_slot_axes(self._specs1)
        # The serving-batch axis is located by name per leaf -- period-
        # stacked leaves carry the layer stack at dim 0, so slot
        # scatter/slice must NOT assume the batch lives in front.
        self.batch_axes = cache_batch_axes(self._specs1)
        for ax in jax.tree_util.tree_leaves(self.batch_axes):
            if ax < 0:
                raise ValueError(
                    f"family {fam!r} has a cache leaf without a "
                    "'batch' axis; the state arena slices per-request "
                    "rows by that name")
        self.layouts = cache_layouts(self._specs1, sc.max_len)
        self.layout_kinds = tuple(sorted(
            set(jax.tree_util.tree_leaves(self.layouts))))

        # ---- per-slot tiered arena placement (fixed at construction) --
        plan = (sc.undervolt
                if sc.undervolt is not None and sc.undervolt.enabled
                else None)
        self.plan = plan
        self.state_tier = state_tier
        self.placements: List[Optional[Any]] = [None] * S
        self.fmap = None
        if plan is not None and plan.covers("kv_cache"):
            self.fmap = plan.fault_map()
            groups = {f"kv_cache[{i:04d}]": self.cache_avals1
                      for i in range(S)}
            if plan.tiers is not None:
                # tiered plan: per-slot caches ride the state tier
                # (fault-tolerant by default -- carried state degrades
                # gracefully and solo replay is exact either way)
                placed = place_groups_tiered(
                    groups, {g: state_tier for g in groups},
                    plan.domains, plan.geometry, self.fmap)
            else:
                # policy plan: honor the plan's kv_cache -> domain pin
                # without tier gating, exactly like generate()'s
                # plan.place() on a policy plan
                dname = plan.policy["kv_cache"]
                placed = place_groups(
                    groups, {g: dname for g in groups}, plan.domains,
                    plan.geometry)
            self.placements = [placed[f"kv_cache[{i:04d}]"]
                               for i in range(S)]

        # ---- per-slot voltage / method / liveness (mirrors generate) --
        if (sc.kv_voltage is not None and sc.kv_method == "auto"
                and _static_value(sc.kv_voltage) is None):
            raise ValueError(
                "kv_method='auto' cannot dispatch from a traced "
                "kv_voltage (method selection is static); pass "
                "kv_method='word' or 'bitwise' explicitly")
        self._slot_volt: List[Optional[float]] = [None] * S
        self._slot_method: List[str] = ["word"] * S
        self._slot_live: List[bool] = [False] * S
        for i, plc in enumerate(self.placements):
            if plc is None:
                continue
            eff = (sc.kv_voltage if sc.kv_voltage is not None
                   else plc.domain.voltage)
            sv = _static_value(eff)
            live = not (sv is not None and sv >= V_MIN - 1e-9)
            meth = sc.kv_method
            if live and meth == "auto":
                meth = ("word" if plc.domain.ecc
                        else resolve_method(self.fmap, plc, sv))
            self._slot_volt[i] = eff
            self._slot_method[i] = meth
            self._slot_live[i] = live
        self.active = any(self._slot_live)
        self.governor = None

        # ---- MoE expert criticality tiering ---------------------------
        self.expert_tiers: Optional[Dict[int, str]] = None
        self.expert_freq = None
        self._expert_placements = None
        if expert_probe is not None:
            self._tier_experts(np.asarray(expert_probe, np.int64))

        # ---- prefill (the standalone engine's exact entry) ------------
        self._prefill = bucketed_prefill(module, cfg, sc.max_len, dist)
        if self._prefill is None:
            self._prefill = jax.jit(
                lambda p, bt: module.prefill(p, bt, cfg, sc.max_len,
                                             dist))
        self._prefill_cache: Dict[bytes, Any] = {}
        self.prefill_reuse = 0
        self._admit_jits: Dict[int, Any] = {}

        # ---- donated state / host bookkeeping -------------------------
        self.queue: collections.deque = collections.deque()
        self.results: Dict[Any, _sched.RequestResult] = {}
        self._slots: List[Optional[Any]] = [None] * S
        self._out: Dict[Any, List[int]] = {}
        self._remaining: Dict[Any, int] = {}
        self._meta: Dict[Any, _sched.RequestResult] = {}
        self._admit_step: Dict[Any, int] = {}
        self.steps = 0
        self.admitted = 0
        self.peak_active = 0
        self.traces: List[int] = []

        self.obs = (obs if obs is not None
                    else sc.obs if sc.obs is not None else ObsConfig())
        self.metrics: Optional[MetricsRegistry] = None
        self.trace: Optional[EventTrace] = None
        if self.obs.enabled:
            self.metrics = MetricsRegistry(
                1, None, config=self.obs,
                kv_slot_bytes=self._step_write_bytes(),
                kv_page_bytes=self._slot_read_bytes(),
                layouts=self.layout_kinds)
            self.trace = EventTrace(capacity=self.obs.trace_capacity)
        self._init_spans()

        self.state = self._init_state()
        self._step = jax.jit(self._step_fn, donate_argnums=(1,))

    # ---- static byte geometry (obs) -----------------------------------
    def _payload_leaves(self):
        flat = jax.tree_util.tree_leaves(
            self.cache_avals1,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        axes = jax.tree_util.tree_leaves(self.slot_axes1)
        lays = jax.tree_util.tree_leaves(self.layouts)
        for a, ax, lay in zip(flat, axes, lays):
            if a.dtype == jnp.int32:
                continue               # pos bookkeeping, not payload
            yield a, ax, lay

    def _step_write_bytes(self) -> int:
        """Bytes one active lane writes per decode step: one ring row
        per ring leaf, the WHOLE leaf for carried state (rewritten --
        and re-corrupted -- every step)."""
        total = 0
        for a, ax, lay in self._payload_leaves():
            nb = int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            total += nb // a.shape[ax] if ax >= 0 else nb
        return total

    def _slot_read_bytes(self) -> int:
        """Bytes one active lane reads per decode step (its whole
        per-slot cache payload: rings, cross K/V, carried state)."""
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a, _, _ in self._payload_leaves())

    # ---- MoE expert tiering -------------------------------------------
    def _tier_experts(self, probe: np.ndarray) -> None:
        module, cfg, plan = self.bundle.module, self.cfg, self.plan
        if not hasattr(module, "routing_frequency") or not cfg.n_experts:
            raise ValueError(
                f"expert_probe given but family {cfg.family!r} has no "
                "routing_frequency (expert tiering is MoE-only)")
        if plan is None or self.fmap is None:
            raise ValueError(
                "expert_probe needs an enabled undervolt plan covering "
                "'kv_cache' (expert weights are placed on the same "
                "fault map as the per-slot caches)")
        freq = np.asarray(module.routing_frequency(
            self.params, probe.reshape(1, -1), cfg))
        e = cfg.n_experts
        order = np.argsort(-freq, kind="stable")
        quarter = max(e // 4, 1)
        tiers: Dict[int, str] = {}
        for rank, ex in enumerate(int(x) for x in order):
            tiers[ex] = ("safe" if rank < quarter
                         else "disposable" if rank >= e - quarter
                         else "cheap")
        # per-expert weight slices across every MoE layer group
        trees: Dict[int, Dict[str, Any]] = {ex: {} for ex in range(e)}
        sites = []
        for cname in ("prefix", "periods", "rest"):
            for gkey, grp in self.params["stack"].get(cname, {}).items():
                if "we_g" not in grp:
                    continue
                for w in ("we_g", "we_u", "we_d"):
                    sites.append((cname, gkey, w))
                    for ex in range(e):
                        trees[ex][f"{cname}/{gkey}/{w}"] = \
                            grp[w][..., ex, :, :]
        groups = {f"moe_expert[{ex:03d}]": trees[ex] for ex in range(e)}
        placed = place_groups_tiered(
            groups, {f"moe_expert[{ex:03d}]": tiers[ex]
                     for ex in range(e)},
            plan.domains, plan.geometry, self.fmap)
        # one-time write-path corruption: expert weights are never
        # rewritten, so faults taken here persist for the scheduler's
        # lifetime (the paper's stuck-at-on-write semantics for
        # read-mostly tensors)
        corrupted: Dict[int, Any] = {}
        for ex in range(e):
            plc = placed[f"moe_expert[{ex:03d}]"]
            v = plc.domain.voltage
            if v >= V_MIN - 1e-9:
                continue
            meth = ("word" if plc.domain.ecc
                    else resolve_method(self.fmap, plc, v))
            corrupted[ex], _ = inject_group(
                trees[ex], plc, self.fmap, voltage=jnp.float32(v),
                method=meth)
        if corrupted:
            stack = {cn: dict(gr)
                     for cn, gr in self.params["stack"].items()}
            touched = {(cn, gk) for cn, gk, _ in sites}
            for cn, gk in touched:
                stack[cn][gk] = dict(stack[cn][gk])
            for cn, gk, w in sites:
                arr = jnp.asarray(stack[cn][gk][w])
                for ex, tree in corrupted.items():
                    arr = arr.at[..., ex, :, :].set(
                        tree[f"{cn}/{gk}/{w}"])
                stack[cn][gk][w] = arr
            self.params = {**self.params, "stack": stack}
        self.expert_tiers = tiers
        self.expert_freq = freq
        self._expert_placements = placed

    # ---- compiled pieces ----------------------------------------------
    def _init_state(self):
        S = self.num_slots
        out = {
            "cache": C.init_cache(self._specsS),
            "qpos": jnp.full((S,), -1, jnp.int32),
            "tok": jnp.zeros((S, 1), jnp.int32),
            "keys": jnp.zeros((S, 2), jnp.uint32),
            "active": jnp.zeros((S,), bool),
        }
        if self.obs.enabled:
            out["mtr"] = init_step_counters(1)
        # Replicated on the model's mesh from the start: a step whose
        # layers run shard_map over that mesh returns its state there,
        # and a state that started off it would key a second trace.
        mesh = dist_lib.ensure(self.dist).mesh
        return jax.device_put(out, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))

    def _volt_vec(self):
        return jnp.asarray(
            [v if v is not None else 0.0 for v in self._slot_volt],
            jnp.float32)

    def _post_inject(self, cache, qpos, v):
        """Per-slot write-path injection, unrolled over slots: the
        standalone engine's ``post_inject`` on each slot's own
        placement constants (ring leaves at the slot just written,
        carried state whole -- the persistent-fault semantic)."""
        for s in range(self.num_slots):
            if not self._slot_live[s]:
                continue
            sub = jax.tree_util.tree_map(
                lambda x, ax: jax.lax.slice_in_dim(x, s, s + 1, axis=ax),
                cache, self.batch_axes)
            sub, _ = arena.inject_placement_slice(
                sub, self.placements[s], self.fmap,
                slot_axes=self.slot_axes1, pos=qpos[s], voltage=v[s],
                method=self._slot_method[s])
            cache = jax.tree_util.tree_map(
                lambda full, one, ax: self._set_row(full, one, ax, s),
                cache, sub, self.batch_axes)
        return cache

    @staticmethod
    def _set_row(full, one, ax: int, s: int):
        """Write the (batch=1) tree's single row into batch row ``s``
        of the batched tree, along the leaf's own batch axis."""
        idx = (slice(None),) * ax + (s,)
        return full.at[idx].set(
            jax.lax.index_in_dim(one, 0, axis=ax, keepdims=False))

    def _step_fn(self, params, state, v):
        self.traces.append(1)
        module, cfg = self.bundle.module, self.cfg
        act = state["active"]
        pos = jnp.where(act, state["qpos"], -1)
        logits, cache = module.decode_step(
            params, state["cache"], {"tokens": state["tok"]}, pos, cfg,
            self.dist)
        if self.active:
            cache = self._post_inject(cache, state["qpos"], v)
        ks = jax.vmap(jax.random.split)(state["keys"])
        new_keys, ki = ks[:, 0], ks[:, 1]
        nt = jax.vmap(
            lambda l, kk: sample_tokens(l[None], kk,
                                        self.sc.temperature)[0]
        )(logits, ki)[:, None]
        new_state = {
            "cache": cache,
            "qpos": state["qpos"] + act.astype(jnp.int32),
            "tok": jnp.where(act[:, None], nt, state["tok"]),
            "keys": jnp.where(act[:, None], new_keys, state["keys"]),
            "active": act,
        }
        if self.obs.enabled:
            decoded = act.astype(jnp.int32).sum()
            delta = jnp.zeros((N_STEP_COUNTERS,), jnp.int32)
            delta = delta.at[0].set(decoded)   # tokens_decoded
            delta = delta.at[2].set(decoded)   # kv_slots_written
            delta = delta.at[3].set(decoded)   # cache reads (per lane)
            new_state["mtr"] = state["mtr"] + delta[None]
        return new_state, nt

    def _admit_fn(self, s: int):
        """Per-slot jitted admit: standalone ``init_inject`` on the
        (1, max_len) prefill tree with the slot's placement, then a
        donated scatter into the batched cache's row ``s``."""
        fn = self._admit_jits.get(s)
        if fn is not None:
            return fn
        plc, meth = self.placements[s], self._slot_method[s]
        live, fmap = self._slot_live[s], self.fmap

        def admit(big, one, v):
            if live:
                one, _ = inject_group(one, plc, fmap, voltage=v,
                                      method=meth)
            return jax.tree_util.tree_map(
                lambda full, x, ax: self._set_row(full, x, ax, s),
                big, one, self.batch_axes)

        fn = jax.jit(admit, donate_argnums=(0,))
        self._admit_jits[s] = fn
        return fn

    # ---- host loop ----------------------------------------------------
    def _emit(self, kind: str, **kw) -> None:
        if self.trace is not None:
            self.trace.emit(kind, step=self.steps,
                            layout="+".join(self.layout_kinds), **kw)

    def submit(self, request: _sched.Request) -> None:
        n_new = (request.max_new_tokens
                 if request.max_new_tokens is not None
                 else self.sc.max_new_tokens)
        if int(n_new) < 1:
            raise ValueError(
                f"request {request.rid!r}: max_new_tokens={n_new} must "
                "be >= 1")
        prompt = np.asarray(request.tokens).reshape(-1)
        plen = int(prompt.shape[0])
        if plen < 1:
            raise ValueError(f"request {request.rid!r}: empty prompt")
        enc = (self.cfg.enc_len if self.cfg.family == "vlm" else 0)
        if plen + enc + int(n_new) > self.sc.max_len:
            raise ValueError(
                f"request {request.rid!r}: prompt ({plen}) + "
                f"{'image tokens + ' if enc else ''}new tokens "
                f"({n_new}) exceed max_len={self.sc.max_len}; the "
                "state-arena ring holds the whole request")
        self._t_submit[request.rid] = time.perf_counter_ns()
        self.queue.append(request)

    @property
    def n_active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    def _free_slot(self) -> Optional[int]:
        for s, r in enumerate(self._slots):
            if r is None:
                return s
        return None

    def admit_pending(self) -> int:
        n = 0
        with self._span["sched.admit"]:
            while self.queue and self.n_active < self.max_active:
                s = self._free_slot()
                if s is None:
                    break
                req = self.queue.popleft()
                self._admit(req, s)
                n += 1
        if self.queue and n == 0 and self.n_active >= self.max_active:
            self._emit("backpressure", rid=self.queue[0].rid,
                       queued=len(self.queue), active=self.n_active)
        return n

    def _admit(self, req: _sched.Request, s: int) -> None:
        prompt = np.asarray(req.tokens, np.int32).reshape(-1)
        n_new = int(req.max_new_tokens
                    if req.max_new_tokens is not None
                    else self.sc.max_new_tokens)
        batch = {"tokens": jnp.asarray(prompt)[None]}
        for k_, v_ in (req.extras or {}).items():
            batch[k_] = jnp.asarray(v_)[None]
        reused = False
        if self.sc.share_prefix:
            ck = _batch_bytes(batch)
            hit = self._prefill_cache.get(ck)
            if hit is None:
                hit = self._prefill(self.params, batch)
                self._prefill_cache[ck] = hit
            else:
                self.prefill_reuse += 1
                reused = True
            logits, cache1 = hit
        else:
            logits, cache1 = self._prefill(self.params, batch)
        key = req.key if req.key is not None else jax.random.PRNGKey(0)
        key, k0 = jax.random.split(key)
        tok0 = sample_tokens(logits, k0, self.sc.temperature)

        plen = int(prompt.shape[0])
        qpos0 = plen + (self.cfg.enc_len
                        if self.cfg.family == "vlm" else 0)
        volt = self._slot_volt[s]
        st = self.state
        new_cache = self._admit_fn(s)(
            st["cache"], cache1,
            jnp.float32(volt if volt is not None else 0.0))
        self.state = {
            **st,
            "cache": new_cache,
            "qpos": st["qpos"].at[s].set(qpos0),
            "tok": st["tok"].at[s].set(tok0),
            "keys": st["keys"].at[s].set(key),
            "active": st["active"].at[s].set(True),
        }
        self._slots[s] = req.rid
        self._admit_step[req.rid] = self.steps
        self._out[req.rid] = []
        self._remaining[req.rid] = n_new
        self._meta[req.rid] = _sched.RequestResult(
            rid=req.rid, tokens=None,
            page_ids=np.zeros((0,), np.int32),
            placement=self.placements[s],
            voltage=(volt if self.placements[s] is not None else None),
            pages_shared=int(reused), shard=0)
        self._stamp_admit(req.rid, s)
        self.admitted += 1
        self.peak_active = max(self.peak_active, self.n_active)
        self._emit("admission", rid=req.rid, plen=plen,
                   n_new=int(n_new), voltage=volt,
                   prefill_reused=reused)
        # token 0 is the admission-time prefill sample (standalone tok0)
        tok = int(np.asarray(tok0)[0])
        self._t_arrive = time.perf_counter_ns()
        self._collect(s, req.rid, tok)

    def _collect(self, s: int, rid, token: int) -> None:
        out = self._out[rid]
        if not out:
            self._meta[rid].ttft_steps = (self.steps
                                          - self._admit_step[rid])
        self._stamp_token(rid)
        out.append(int(token))
        self._remaining[rid] -= 1
        if self._remaining[rid] == 0:
            self._retire(s)

    def _retire(self, s: int) -> None:
        rid = self._slots[s]
        res = self._meta.pop(rid)
        res.tokens = np.asarray(self._out.pop(rid), np.int32)[None, :]
        self.results[rid] = res
        self._emit("retirement", rid=rid,
                   tokens=int(res.tokens.shape[1]),
                   ttft_steps=res.ttft_steps)
        del self._remaining[rid]
        del self._admit_step[rid]
        self._slots[s] = None
        st = self.state
        self.state = {
            **st,
            "qpos": st["qpos"].at[s].set(-1),
            "active": st["active"].at[s].set(False),
        }

    def step_once(self) -> None:
        """One decode step of every active row; the paged route's spans
        without ``sched.feed`` (prompts prefill at admission)."""
        sp = self._span
        with sp["sched.step"]:
            with sp["sched.dispatch"]:
                self.state, nt = self._step(self.params, self.state,
                                            self._volt_vec())
            with sp["sched.sync"]:
                toks = np.asarray(nt).reshape(-1)
                self._t_arrive = time.perf_counter_ns()
            self.steps += 1
            with sp["sched.post"]:
                for s, rid in enumerate(self._slots):
                    if rid is not None:
                        self._collect(s, rid, toks[s])

    def run(self) -> Dict[Any, _sched.RequestResult]:
        while self.queue or self.n_active:
            n = self.admit_pending()
            if self.n_active:
                self.step_once()
            elif n == 0:
                raise RuntimeError(
                    f"stuck: {len(self.queue)} queued, none admitted, "
                    "none active")
            # else: every admission retired at its prefill token
            # (max_new_tokens == 1); loop to drain the queue
        return self.results

    @property
    def pricing_voltages(self) -> List[float]:
        vs = [v for v, p in zip(self._slot_volt, self.placements)
              if p is not None and v is not None]
        return [min(vs) if vs else V_NOM]

    @property
    def stats(self) -> Dict[str, Any]:
        vs = [v for v, live in zip(self._slot_volt, self._slot_live)
              if live and v is not None]
        out = {
            "route": "state",
            "cache_layouts": list(self.layout_kinds),
            "steps": self.steps,
            "admitted": self.admitted,
            "peak_active": self.peak_active,
            "decode_traces": len(self.traces),
            "voltage": (min(vs) if vs else None),
            "n_shards": 1,
            "prefill_reuse": self.prefill_reuse,
            "shards": [{
                "shard": 0,
                "active": self.n_active,
                "voltage": (min(vs) if vs else None),
                "setpoint": None,
                "map_seed": (self.plan.map_seed
                             if self.plan is not None else None),
            }],
        }
        if self.expert_tiers is not None:
            tiers = collections.Counter(self.expert_tiers.values())
            out["expert_tiers"] = dict(tiers)
        if self.metrics is not None:
            out["obs"] = self.metrics.snapshot(
                self.state, voltages=self.pricing_voltages)
        if self.trace is not None:
            out["events"] = dict(self.trace.counts)
        return out
