"""In-step metrics: per-shard donated counters + host-side step-latency
percentiles.

Generalizes the self-healing loop's donated-telemetry pattern (the
``telem``/``telem_u`` SECDED counters) into a registry of named
counters that live as ONE extra ``(n_shards, N)`` int32 leaf of the
scheduler's donated state.  Each compiled step accumulates the deltas
with pure ``jnp`` arithmetic on values the step already has in
registers (active/decode masks, cursors, the migration lanes) -- zero
extra pallas launches, zero host syncs per step; the host reads the
cumulative counters only at ``stats()`` / export time.

Counter units are chosen to keep int32 honest over long runs: discrete
events (tokens, cache slots, logical pages), converted to bytes on the
host with the pool's static K/V page geometry.  ``kv_pages_read``
counts *useful* traffic -- every active lane's full page table once per
step (the ring is fixed-shape; inactive lanes' scratch reads are patrol
traffic and excluded on purpose, so joules/token prices the work
tenants bought); ``kv_pages_skipped`` the part of it the paged decode
kernel does not fetch, the blocks past a lane's position.

Host-side, the step-latency report is a view of the scheduler's
``sched.dispatch`` + ``sched.sync`` spans: the count and wall time of
every step from the spans' running totals, the p50/p95/p99 from the
steps the process-wide span log (:data:`repro.obs.trace.SPANS`) still
holds; the registry keeps no timer of its own.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.faulty import live_blocks
from repro.obs import trace
from repro.obs.energy import DEFAULT_ENERGY_MODEL, EnergyModel
from repro.obs.trace import new_owner, step_spans

# Donated-counter layout: one row per shard, one column per name, in
# this order.  Appending is backward-compatible (the state leaf is
# rebuilt per scheduler); reordering is not.
STEP_COUNTERS = (
    "tokens_decoded",     # decode lanes that sampled a token this step
    "prefill_tokens",     # prompt tokens consumed by prefilling lanes
    "kv_slots_written",   # cache slots written (COW write floor applied)
    "kv_pages_read",      # (active lane, logical page) reads via the
                          # page table -- the paged-attention gather
    "pages_migrated",     # self-healing page copies staged this step
    "kv_pages_skipped",   # of kv_pages_read, the full-ring pages the
                          # paged kernel skips (past a lane's position)
)
_IDX = {name: i for i, name in enumerate(STEP_COUNTERS)}
N_STEP_COUNTERS = len(STEP_COUNTERS)


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs of one scheduler.

    ``enabled=False`` removes the donated counter leaf, the event
    trace and the host spans entirely -- the metrics-off baseline the
    launch-budget and overhead tests compare against.
    """

    enabled: bool = True
    trace_capacity: int = 4096        # event ring entries kept
    energy: EnergyModel = DEFAULT_ENERGY_MODEL


def init_step_counters(n_shards: int) -> jnp.ndarray:
    """The donated ``(n_shards, N_STEP_COUNTERS)`` counter leaf."""
    return jnp.zeros((n_shards, N_STEP_COUNTERS), jnp.int32)


def step_counter_delta(*, act, dec, cursor, plen, wstart, qpos,
                       chunk: int, n_logical_pages: int, page_slots: int,
                       block_pages: int, mig_src,
                       scratch_id: int) -> jnp.ndarray:
    """One shard's per-step counter increments (traced, pure jnp).

    All inputs are the *pre-step* values the compiled step body already
    holds; the result is a length-``N_STEP_COUNTERS`` int32 vector.
    Write accounting mirrors the paged write path exactly: decode lanes
    write one slot at ``qpos`` (always at/above the COW floor), prefill
    lanes write their consumed chunk clipped below by ``wstart`` (rows
    of a shared prefix are mapped read-only and never written).  Skips
    mirror the paged decode kernel on a full-length ring of
    ``n_logical_pages`` pages read in blocks of ``block_pages``: its
    query sits at ``qpos`` on decode lanes and at the chunk start on
    prefill lanes, and it reads only the blocks up to that slot.
    """
    pre = act & ~dec
    consumed = jnp.where(pre, jnp.minimum(cursor + chunk, plen) - cursor,
                         0).astype(jnp.int32)
    written = jnp.where(
        pre,
        jnp.maximum(jnp.minimum(cursor + chunk, plen)
                    - jnp.maximum(cursor, wstart), 0),
        0).astype(jnp.int32)
    decoded = (act & dec).astype(jnp.int32)
    blocks = live_blocks(jnp.where(dec, qpos, cursor),
                         n_logical_pages * page_slots,
                         block_pages * page_slots, causal=True)
    skipped = jnp.where(act, n_logical_pages - blocks * block_pages, 0)
    return jnp.stack([
        decoded.sum(),
        consumed.sum(),
        decoded.sum() + written.sum(),
        act.astype(jnp.int32).sum() * jnp.int32(n_logical_pages),
        (mig_src != scratch_id).astype(jnp.int32).sum(),
        skipped.astype(jnp.int32).sum(),
    ]).astype(jnp.int32)


class MetricsRegistry:
    """Host half of the in-step metrics: static byte geometry, the
    cumulative-counter reader, and the step-latency view.

    The device half is :func:`step_counter_delta` inside the compiled
    step; this class never touches the device during serving -- it
    reads the donated leaf once per ``stats()``/export call.
    """

    def __init__(self, n_shards: int, pool, config: ObsConfig, *,
                 kv_page_bytes: Optional[int] = None,
                 kv_slot_bytes: Optional[int] = None,
                 layouts: Optional[Sequence[str]] = None):
        self.n_shards = int(n_shards)
        self.config = config
        # Which cache layouts this scheduler's counters price: the
        # paged route tags "full"/"window", the state-arena route
        # whatever mix its family carries ("full"/"cross"/"state").
        self.layouts = tuple(layouts) if layouts is not None else None
        if pool is not None:
            # Static K/V payload geometry (bytes): what one page-table
            # read and one written cache slot move, over every k/v leaf
            # & layer (``pos`` bookkeeping words excluded -- they are
            # not payload).
            self.kv_page_bytes = 4 * sum(
                leaf.n_layers * leaf.page_words
                for leaf in pool.leaves if leaf.which in ("k", "v"))
            self.kv_slot_bytes = 4 * sum(
                leaf.n_layers * leaf.wps
                for leaf in pool.leaves if leaf.which in ("k", "v"))
        else:
            # Pool-less (state-arena) route: the scheduler supplies its
            # own static geometry -- ``kv_page_bytes`` is one lane's
            # whole per-slot cache payload (read every step),
            # ``kv_slot_bytes`` one lane's per-step write payload.
            self.kv_page_bytes = int(kv_page_bytes or 0)
            self.kv_slot_bytes = int(kv_slot_bytes or 0)
        # This scheduler's spans in the process-wide log: its tag, how
        # many spans the log held before it, and the reusable step
        # spans whose running totals count every step.
        self.owner = new_owner()
        self._since = trace.SPANS.recorded
        self.spans = step_spans(self.owner)

    # ---- latency ---------------------------------------------------------
    def step_seconds(self) -> np.ndarray:
        """Wall time of each donated step, oldest first: its
        ``sched.dispatch`` span plus the ``sched.sync`` span that
        follows it, over the steps the span log still holds."""
        out, dispatch = [], None
        for sp in trace.SPANS.select(("sched.dispatch", "sched.sync"),
                                     owner=self.owner, since=self._since):
            if sp.name == "sched.dispatch":
                dispatch = sp.end_ns - sp.start_ns
            elif dispatch is not None:
                out.append(dispatch + sp.end_ns - sp.start_ns)
                dispatch = None
        return np.asarray(out, np.float64) * 1e-9

    @property
    def steps(self) -> int:
        """Donated steps ever timed (each ends with its sync)."""
        return self.spans["sched.sync"].count

    @property
    def wall_seconds(self) -> float:
        """Dispatch plus sync time of every step ever timed."""
        return (self.spans["sched.dispatch"].total_ns
                + self.spans["sched.sync"].total_ns) * 1e-9

    def latency(self) -> Dict[str, float]:
        """Count and mean over every step; percentiles over the steps
        the span log still holds (left out where it holds none)."""
        n = self.steps
        if n == 0:
            return {"count": 0}
        out = {"count": n, "mean_s": self.wall_seconds / n}
        w = self.step_seconds()
        if w.size:
            p50, p95, p99 = np.percentile(w, [50, 95, 99])
            out.update(p50_s=float(p50), p95_s=float(p95),
                       p99_s=float(p99))
        return out

    # ---- counters --------------------------------------------------------
    def counters_np(self, state) -> np.ndarray:
        """Cumulative ``(n_shards, N)`` counters off the donated leaf
        (one device->host read; no per-step sync)."""
        return np.asarray(state["mtr"], np.int64)

    def shard_bytes_moved(self, counters: np.ndarray) -> np.ndarray:
        """Per-shard K/V bytes moved (read + written) from the
        discrete-unit counters and the static page geometry."""
        return (counters[:, _IDX["kv_pages_read"]] * self.kv_page_bytes
                + counters[:, _IDX["kv_slots_written"]]
                * self.kv_slot_bytes)

    def totals(self, state) -> Dict[str, int]:
        """Fleet-total counters plus derived byte totals."""
        c = self.counters_np(state)
        out = {name: int(c[:, i].sum())
               for i, name in enumerate(STEP_COUNTERS)}
        out["kv_bytes_read"] = int(
            (c[:, _IDX["kv_pages_read"]] * self.kv_page_bytes).sum())
        out["kv_bytes_written"] = int(
            (c[:, _IDX["kv_slots_written"]] * self.kv_slot_bytes).sum())
        out["kv_bytes_moved"] = (out["kv_bytes_read"]
                                 + out["kv_bytes_written"])
        return out

    # ---- energy ----------------------------------------------------------
    def energy(self, state,
               voltages: Sequence[float]) -> Dict[str, Any]:
        """Joules/token and $/1M-tokens per shard and fleet-wide.

        ``voltages`` is each shard's operating rail voltage (shards of
        an unplaced/clean scheduler price at nominal).  Every shard is
        charged the full recorded wall time for its static watts --
        shards step concurrently inside the one compiled call.
        """
        em = self.config.energy
        c = self.counters_np(state)
        bytes_k = self.shard_bytes_moved(c)
        toks_k = c[:, _IDX["tokens_decoded"]]
        wall = self.wall_seconds
        shards = []
        joules_total = 0.0
        for k in range(self.n_shards):
            rep = em.report(seconds=wall,
                            bytes_moved=float(bytes_k[k]),
                            tokens=max(int(toks_k[k]), 1),
                            v=float(voltages[k]))
            rep["shard"] = k
            rep["tokens"] = int(toks_k[k])
            rep["kv_bytes_moved"] = int(bytes_k[k])
            shards.append(rep)
            joules_total += rep["joules"]
        tokens_total = int(toks_k.sum())
        jpt = joules_total / max(tokens_total, 1)
        return {
            "shards": shards,
            "wall_seconds": wall,
            "tokens": tokens_total,
            "kv_bytes_moved": int(bytes_k.sum()),
            "joules": joules_total,
            "joules_per_token": jpt,
            "usd_per_mtok": em.usd_per_mtok(jpt),
            "tokens_per_joule": (tokens_total / joules_total
                                 if joules_total > 0 else 0.0),
        }

    def snapshot(self, state, voltages: Optional[Sequence[float]] = None,
                 ) -> Dict[str, Any]:
        """Counters + latency (+ energy when voltages are supplied)."""
        c = self.counters_np(state)
        out: Dict[str, Any] = {
            "counters": {name: c[:, i].tolist()
                         for i, name in enumerate(STEP_COUNTERS)},
            "totals": self.totals(state),
            "step_latency": self.latency(),
        }
        if self.layouts is not None:
            out["cache_layouts"] = list(self.layouts)
        if voltages is not None:
            out["energy"] = self.energy(state, voltages)
        return out
