"""Serving at scale: a mixed-tier request stream with a shared system
prompt through the continuous-batching scheduler -- on one device or
sharded across a heterogeneous-voltage fleet.

A stream of requests with different prompts, generation lengths and
criticality tiers is pushed through one scheduler: strict-tier requests
get weak-row-free pages, tolerant requests soak up the weak pages first,
the admission governor walks the KV-domain voltage along the
power/reliability frontier as load changes, and every request --
prompt prefill included, chunked through the same program -- rides ONE
compiled step (watch ``decode_traces`` stay 1).

Half the stream opens with the same system prompt: after the first
tenant publishes it, later tenants map the cached prefix pages
read-only (copy-on-write) instead of recomputing and re-storing it --
watch ``pages_shared`` and the flat ``ttft`` of sharing tenants.

With ``--devices N`` the scheduler shards over an N-way serve mesh:
every shard draws its OWN fault map (independent weak-row draws --
real HBM parts differ) and admits against its own governor setpoint,
so the fleet runs heterogeneous voltages: strict shards stay shallow,
tolerant shards undervolt deep, and the fleet report aggregates the
power/reliability mix.  The decode step is still ONE compiled program
with zero cross-shard traffic.

With ``--chaos`` the stream runs on an ECC'd worst-channel domain and
a live DRAM row is flipped weak mid-stream: the fused read path's
SECDED correction counters feed the fault-map posterior, the accused
row's pages migrate inside the decode step, and the row is
quarantined -- watch the printed migration/quarantine counters while
every request still finishes (and ``decode_traces`` still stays 1).

With ``--arch NAME`` the stream runs any registered architecture
through the SAME scheduler front door: paged families take the lane
above, everything else (MoE, recurrent hybrids, xLSTM, whisper, VLM)
transparently dispatches to the state-arena route -- modality extras
(audio frames, image patches) ride the requests, one request is
replayed solo through ``generate()`` on its placement to prove
bit-equivalence, and ``decode_traces`` still stays 1.

  PYTHONPATH=src python examples/serve_many.py
  PYTHONPATH=src python examples/serve_many.py --devices 4
  PYTHONPATH=src python examples/serve_many.py --chaos
  PYTHONPATH=src python examples/serve_many.py --arch whisper-large-v3
"""
import argparse
import os
import sys
from pathlib import Path


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1,
                    help="serve-mesh shard count (forces that many "
                    "host devices; must be set before jax imports)")
    ap.add_argument("--chaos", action="store_true",
                    help="flip a live DRAM row weak mid-stream and "
                    "watch the self-healing loop detect it from the "
                    "SECDED counters, migrate its pages and "
                    "quarantine the row")
    ap.add_argument("--arch", default=None,
                    help="serve this registered architecture instead "
                    "of the default llama3.2-3b stream: the scheduler "
                    "front door dispatches paged vs state-arena by "
                    "family (incompatible with --devices/--chaos)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the observability plane after the "
                    "drain: the Prometheus text exposition (in-step "
                    "counters, step-latency quantiles, joules/token) "
                    "and the structured event trace as JSONL")
    return ap.parse_args()


ARGS = _parse()
if ARGS.devices > 1 and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={ARGS.devices}")

import jax                                            # noqa: E402
import numpy as np                                    # noqa: E402

from repro.core.domains import MemoryDomain           # noqa: E402
from repro.core.hbm import VCU128                     # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serve_mesh         # noqa: E402
from repro.models.base import get_arch, init_params   # noqa: E402
from repro.serving.engine import ServeConfig          # noqa: E402
from repro.serving.scheduler import (                 # noqa: E402
    ContinuousBatchingScheduler, Request, SelfHealConfig)
from repro.training.undervolt import UndervoltPlan    # noqa: E402


def zoo_main(arch):
    """Any-family lane: run ``--arch`` through the one scheduler front
    door, print the route it dispatched to, and prove one request
    bit-identical to its solo ``generate()`` replay."""
    import dataclasses

    from repro.serving.engine import generate

    if ARGS.devices > 1 or ARGS.chaos:
        raise SystemExit("--arch is a single-shard lane; drop "
                         "--devices/--chaos")
    bundle = get_arch(arch)
    cfg = bundle.reduced
    params = init_params(bundle.module.param_specs(cfg),
                         jax.random.PRNGKey(0))
    plan = UndervoltPlan(
        domains={"kv": MemoryDomain("kv", 0.90,
                                    tuple(range(VCU128.num_pcs)))},
        policy={"kv_cache": "kv"}, geometry=VCU128)
    sc = ServeConfig(max_len=32, max_new_tokens=6, undervolt=plan,
                     kv_injection="write", kv_method="bitwise")
    sched = ContinuousBatchingScheduler(
        bundle, cfg, params, sc, num_slots=2, num_pages=16,
        page_slots=8)
    rng = np.random.RandomState(0)
    reqs = []
    for i in range(3):
        extras = None
        if cfg.family == "audio":
            extras = {"frames": rng.standard_normal(
                (cfg.enc_len, cfg.d_model)).astype(np.float32)}
        elif cfg.family == "vlm":
            extras = {"patches": rng.standard_normal(
                (cfg.enc_len, cfg.frontend_dim)).astype(np.float32)}
        reqs.append((rng.randint(0, cfg.vocab, (4 + 2 * i,)), 3 + i,
                     extras))
        sched.submit(Request(
            rid=f"req{i}", tokens=reqs[-1][0], max_new_tokens=3 + i,
            key=jax.random.PRNGKey(7 * i), extras=extras))
    results = sched.run()
    st = sched.stats
    print(f"{arch} [{cfg.family}] route={st['route']} "
          f"layouts={sorted(set(st['cache_layouts']))} "
          f"steps={st['steps']} decode_traces={st['decode_traces']}")
    for i in range(3):
        r = results[f"req{i}"]
        print(f"req{i} v={r.voltage:.2f} tokens={r.tokens[0].tolist()}")
    assert st["decode_traces"] == 1, st

    # solo replay of req1 on its placement: the bit-equivalence
    # contract, same as tests/test_zoo_serving.py's matrix
    toks, n_new, extras = reqs[1]
    batch = {"tokens": toks[None]}
    for k, v in (extras or {}).items():
        batch[k] = v[None]
    solo = generate(bundle, cfg, params, batch,
                    dataclasses.replace(sc, max_new_tokens=n_new),
                    key=jax.random.PRNGKey(7),
                    kv_placement=results["req1"].placement)
    np.testing.assert_array_equal(np.asarray(solo),
                                  results["req1"].tokens)
    print("solo replay: bit-identical")


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    if ARGS.arch is not None:
        zoo_main(ARGS.arch)
        return
    n_shards = ARGS.devices
    bundle = get_arch("llama3.2-3b")
    cfg = bundle.reduced
    params = init_params(bundle.module.param_specs(cfg),
                         jax.random.PRNGKey(0))

    kw = {}
    if ARGS.chaos:
        # Self-healing demo: an ECC'd domain on the four least-
        # reliable pseudo-channels, where a weak row at 0.91 V throws
        # correctable SECDED events on every read -- the telemetry the
        # healing loop feeds on.  (SelfHealConfig needs the fused ECC
        # read path: kv_injection='read', kv_method='word'.)
        plan = UndervoltPlan(
            domains={"kv": MemoryDomain("kv", 0.91, (8, 15, 18, 29),
                                        ecc=True)},
            policy={"kv_cache": "kv"}, geometry=VCU128)
        sc = ServeConfig(max_len=64, max_new_tokens=8, undervolt=plan,
                         kv_injection="read", kv_method="word",
                         prefill_chunk=8, share_prefix=True)
        kw["self_heal"] = SelfHealConfig()
        if n_shards > 1:
            kw["mesh"] = make_serve_mesh(n_shards)
    else:
        plan = UndervoltPlan(
            domains={"kv": MemoryDomain("kv", 0.90,
                                        tuple(range(VCU128.num_pcs)))},
            policy={"kv_cache": "kv"}, geometry=VCU128)
        governor = plan.make_governor("kv", mode="rate",
                                      tolerable_rate=1e-3, v_lo=0.87)
        sc = ServeConfig(max_len=64, max_new_tokens=8, undervolt=plan,
                         governor=governor, kv_injection="read",
                         kv_method="bitwise", prefill_chunk=8,
                         share_prefix=True)
        if n_shards > 1:
            # heterogeneous rate setpoints: shard 0 is the strict end
            # of the fleet (tight stuck-cell cap -> shallow
            # undervolt), the last shard the tolerant end (deep
            # undervolt, max savings)
            setpoints = list(np.geomspace(1e-9, 1e-4, n_shards))
            kw = dict(mesh=make_serve_mesh(n_shards),
                      shard_setpoints=setpoints)
    sched = ContinuousBatchingScheduler(
        bundle, cfg, params, sc, num_slots=4 * n_shards,
        num_pages=40 * n_shards, page_slots=8, **kw)

    rng = np.random.RandomState(0)
    system = rng.randint(0, cfg.vocab, (19,))   # shared system prompt
    tiers = ["cheap", "critical", "cheap", "hedged", "cheap", "cheap",
             "critical", "cheap"] * n_shards
    print(f"fleet: {sched.stats['n_shards']} shard(s), "
          f"{sched.stats['free_pages']} pages total, "
          f"{sched.pool.n_logical_pages} pages/request")
    for i, tier in enumerate(tiers):
        user = rng.randint(0, cfg.vocab, (4 + i % 8,))
        toks = np.concatenate([system, user]) if i % 2 else user
        sched.submit(Request(
            rid=f"req{i}", tokens=toks,
            max_new_tokens=4 + 2 * (i % 3), tier=tier,
            key=jax.random.PRNGKey(i)))

    if ARGS.chaos:
        # drain manually so the chaos hook fires mid-stream: after two
        # steps, flip the DRAM row under the oldest live page weak
        weakened = None
        step_i = 0
        while sched.queue or sched.n_active:
            sched.admit_pending()
            if not sched.n_active:
                break
            if weakened is None and step_i == 2:
                owned = sorted(sched.pool._owned)
                pc, row = sched.pool.page_rows(owned[0])[0]
                pids = sched.weaken_row(0, pc, row)
                weakened = (pc, row)
                print(f"CHAOS @step {step_i}: pc{pc} row {row} went "
                      f"weak ({len(pids)} live pages affected)")
            sched.step_once()
            step_i += 1
        results = sched.results
    else:
        results = sched.run()
    for i, tier in enumerate(tiers):
        r = results[f"req{i}"]
        pool_k = sched._shards[r.shard].pool
        weak = sum(1 for p in r.page_ids
                   if int(p) in pool_k._weak_set)
        print(f"req{i:<2d} [{tier:8s}] shard={r.shard} "
              f"v={r.voltage:.2f} ({weak} weak, "
              f"{r.pages_shared} shared) ttft={r.ttft_steps} "
              f"tokens={r.tokens[0].tolist()}")
    st = sched.stats
    for sh in st["shards"]:
        sp = ("-" if sh["setpoint"] is None
              else f"{sh['setpoint']:.1e}")
        print(f"shard {sh['shard']}: seed={sh['map_seed']} "
              f"setpoint={sp} v={sh['voltage']:.2f} "
              f"weak_pages={sh['weak_pages']} "
              f"free_pages={sh['free_pages']}")
    if "fleet" in st:
        fl = st["fleet"]
        print(f"fleet: power_factor mean={fl['power_factor_mean']:.3f} "
              f"max={fl['power_factor_max']:.3f} "
              f"worst_rate={fl.get('worst_rate', 0):.2e}")
    if ARGS.chaos:
        sh0 = st["shards"][0]
        print(f"self-heal: corrected={st['corrected']} "
              f"uncorrectable={st['uncorrectable']} "
              f"suspect_rows={sh0['suspect_rows']} "
              f"migrations={st['migrations']} "
              f"quarantined_pages={st['quarantined_pages']} "
              f"quarantined_blocks={st['quarantined_blocks']}")
        assert st["corrected"] > 0, "chaos row never produced telemetry"
        assert st["uncorrectable"] == 0, st
        assert st["migrations"] >= 1 and st["quarantined_pages"] >= 1, st
    assert st["decode_traces"] == 1
    shared = [results[f"req{i}"].pages_shared
              for i in range(len(tiers)) if i % 2]
    if not ARGS.chaos:
        # (under --chaos the reliability pin keeps the prefix cache
        # from publishing on the deep worst-PC domain, so sharing is
        # legitimately absent)
        assert any(s > 0 for s in shared[1:]), shared
    if n_shards > 1 and not ARGS.chaos:
        # (--chaos runs the fleet at one deep voltage, no governor)
        vs = [sh["voltage"] for sh in st["shards"]]
        assert len(set(f"{v:.3f}" for v in vs)) > 1, (
            f"expected heterogeneous shard voltages, got {vs}")
        assert vs[0] >= vs[-1], vs   # strict shard runs shallower

    if ARGS.metrics:
        from repro.obs import export
        print("\n---- prometheus exposition " + "-" * 38)
        print(export.prometheus_text(sched), end="")
        print("---- event trace (JSONL tail) " + "-" * 35)
        tail = sched.trace.events()[-8:]
        for ev in tail:
            import json
            print(json.dumps(ev.to_dict()))
        # Cross-check the donated counters against what the drain
        # provably did: every request spends n_new-1 decode steps (the
        # first token samples at the prefill transition) and consumes
        # its whole prompt through chunked prefill.
        tot = st["obs"]["totals"]
        want_dec = sum(r.tokens.shape[1] - 1 for r in results.values())
        assert tot["tokens_decoded"] == want_dec, (tot, want_dec)
        assert tot["kv_bytes_moved"] > 0
        assert st["obs"]["step_latency"]["count"] == st["steps"]
        en = st["obs"]["energy"]
        assert en["tokens"] == tot["tokens_decoded"]
        assert en["joules_per_token"] > 0
        assert st["events"]["admission"] == len(results)
        assert st["events"]["retirement"] == len(results)
        print(f"metrics OK: {tot['tokens_decoded']} tokens, "
              f"{tot['kv_bytes_moved']} KV bytes, "
              f"{en['joules_per_token']:.3f} J/token "
              f"(${en['usd_per_mtok']:.2f}/Mtok)")


if __name__ == "__main__":
    main()
