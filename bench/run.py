#!/usr/bin/env python3
"""Chip benchmark of the paged serving path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process on the machine that holds the cell's chips.  It
reads the cell from ``BENCHMARK.json`` and, by name, its configuration
(``bench/configs/``), traffic mix (``bench/traffic/``), cell file
(``bench/cells/``) and metric readers (``bench/metrics/``).  Set-up
draws the weights on the device from the seed, builds the scheduler and
serves warm-up requests from the mix until every program the window
uses is compiled; then the window drives the scheduler's public loop
(``submit``, ``admit_pending``, ``step_once``) for ``--seconds``.  After
the window the program is freed and a sample of the requests it
finished, from every slot, is compared with the plain float32 reference
(``bench/reference.py``); ``correct`` also holds the scheduler to the
configuration's KV domain (read-path injection live or not, as the cell
file states).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers compared with their limits under ``check``.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no
result.

Options the driver does not pass: ``--rate`` offers an open-loop cell
another rate (the knee sweep), and ``--control`` also puts the float8
reference in the program's place on the compared requests and scores it
through the same comparison (``control_*`` entries under ``check``).
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import flops, traffic  # noqa: E402
from bench import trace as tracemod  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# A traced run records the first seconds of its window: the profile of a
# whole minute takes the chip host minutes to write.
TRACE_S = 8.0
CACHE_MISS = "/jax/compilation_cache/cache_misses"
# Compared numbers held from below, and those that must equal their limit.
MINIMA = ("tokens",)
EXACT = ("kv_injection",)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One ``workloads`` entry of BENCHMARK.json and the files it names."""

    def __init__(self, root: Path, name: str):
        self.root = root
        self.spec = load_json(root / "BENCHMARK.json")
        found = [w for w in self.spec["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
        self.workload = found[0]
        conf = [c for c in self.spec["configs"]
                if c["name"] == self.workload["config"]][0]
        self.conf = dict(load_json(root / conf["file"]), name=conf["name"])
        self.mix = load_json(root / "bench" / "traffic"
                             / f"{self.workload['traffic']}.json")
        self.cell = load_json(root / "bench" / "cells" / f"{name}.json")
        self.chips = int(self.workload["chips"])

    def metrics(self, section: str):
        """Metric entries of ``section`` that this cell reports."""
        name = self.workload["name"]
        return [m for m in self.spec[section]
                if "workloads" not in m or name in m["workloads"]]

    def reader(self, metric: str):
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class Ledger:
    """Host-side record of every request: when it was due, submitted,
    admitted, and when each of its tokens reached the host; and, while
    ``lanes`` is on, what each step's lanes computed."""

    def __init__(self, chunk: int):
        self.chunk = chunk
        self.req = {}
        self.waiting = collections.deque()
        self.active = []
        self.lanes = None            # list of per-step lane records

    def submit(self, sched, r, due_abs: float) -> None:
        """``sched.submit`` the request ``r`` that was due at ``due_abs``."""
        from repro.serving.scheduler import Request
        sched.submit(Request(rid=r.rid, tokens=r.prompt,
                             max_new_tokens=r.max_new))
        self.req[r.rid] = dict(plen=len(r.prompt), max_new=r.max_new,
                               prompt=r.prompt, due=due_abs,
                               submit=time.perf_counter(), admit=None,
                               slot=None, cursor=0, times=[], done=False)
        self.waiting.append(r.rid)

    def admit(self, sched) -> None:
        """``sched.admit_pending``; admission is first in, first out.
        Records the serving slot each request was given."""
        n = sched.admit_pending()
        now = time.perf_counter()
        for _ in range(n):
            rid = self.waiting.popleft()
            self.req[rid].update(admit=now, slot=sched._slots.index(rid))
            self.active.append(rid)

    def step(self, sched) -> float:
        """``sched.step_once``; returns the time its tokens reached the
        host."""
        self.before_step()
        sched.step_once()
        now = time.perf_counter()
        self.after_step(sched, now)
        return now

    def before_step(self) -> None:
        """What the coming step computes for each active request: a decode
        lane at its context, or a prompt chunk (the scheduler feeds
        ``prefill_chunk`` prompt tokens a step)."""
        dec, pre, sampled = [], [], 0
        for rid in self.active:
            q = self.req[rid]
            if q["times"]:
                dec.append(q["plen"] + len(q["times"]))
                sampled += 1
            else:
                a = q["cursor"]
                b = min(a + self.chunk, q["plen"])
                pre.append((a, b))
                q["cursor"] = b
                sampled += b == q["plen"]
        if self.lanes is not None:
            self.lanes.append((dec, pre, sampled))

    def after_step(self, sched, now: float) -> None:
        keep = []
        for rid in self.active:
            q = self.req[rid]
            res = sched.results.get(rid)
            n = (res.tokens.shape[1] if res is not None
                 else len(sched._out.get(rid, ())))
            q["times"].extend([now] * (n - len(q["times"])))
            if res is not None:
                q["done"] = True
                q["tokens"] = np.asarray(res.tokens[0])
            else:
                keep.append(rid)
        self.active = keep


def drive(sched, led: Ledger, until: float, *, pending=None, origin=0.0,
          clients=None) -> float:
    """Run the scheduler's public loop until the clock passes ``until``.

    Open loop: ``pending`` holds requests due at ``origin + due``.
    Closed loop: ``clients`` maps a client to the iterator of its
    requests; a client sends its next request once the last finished.
    Returns the time the last step ended."""
    import jax
    ta = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    now = clock()
    while now < until:
        with ta("bench.submit"):
            due = []
            if pending is not None:
                while pending and origin + pending[0].due <= now:
                    r = pending.popleft()
                    due.append((r, origin + r.due))
            if clients is not None:
                for c, it in clients.items():
                    cur = it["current"]
                    if cur is None or led.req[cur]["done"]:
                        r = next(it["reqs"], None)
                        it["current"] = None if r is None else r.rid
                        if r is not None:
                            due.append((r, now))
            for r, at in due:
                led.submit(sched, r, at)
        with ta("bench.admit"):
            led.admit(sched)
        if sched.n_active:
            with ta("bench.step"):
                now = led.step(sched)
        else:
            nxt = (origin + pending[0].due) if pending else until
            with ta("bench.idle"):
                time.sleep(max(0.0, min(nxt, until) - clock()))
            now = clock()
    return now


def warm_chunk_feeds(sched) -> None:
    """Compile the prompt-chunk refresh for every count of prefilling
    slots: the scheduler writes the chunks of the n slots that prefill
    in a step with one scatter whose shape depends on n."""
    import jax
    import jax.numpy as jnp
    tok = sched.state["tok"]
    s = tok.shape[1]
    for n in range(1, tok.shape[0] * s + 1):
        g = list(range(n))
        ks = np.asarray([i // s for i in g])
        ss = np.asarray([i % s for i in g])
        rows = np.zeros((n, tok.shape[2]), np.int32)
        jax.block_until_ready(tok.at[ks, ss].set(jnp.asarray(rows)))


def mem(device, key: str) -> int:
    return int((device.memory_stats() or {}).get(key, 0))


def pctl(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


class Run:
    """What the metric readers read: the host-clock record of the
    window and, in a traced run, the reduced trace."""

    def __init__(self, cell: Cell, led: Ledger, t0: float, t1: float,
                 setup_s: float, peaks: dict):
        self.cell, self.led, self.t0, self.t1 = cell, led, t0, t1
        self.setup_s = setup_s
        self.peaks = peaks
        self.trace = None
        self.lanes = None
        self.open_loop = cell.mix["loop"] == "open"

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def _in(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def tokens_in_window(self) -> int:
        return sum(1 for q in self.led.req.values() for t in q["times"]
                   if self._in(t))

    def ttfts(self):
        return [q["times"][0] - q["due"] for q in self.led.req.values()
                if q["times"] and self._in(q["times"][0])]

    def itls(self):
        out = []
        for q in self.led.req.values():
            ts = q["times"]
            out.extend(b - a for a, b in zip(ts, ts[1:]) if self._in(b))
        return out

    @property
    def queue_waits(self):
        if not self.open_loop:
            return []
        return [q["admit"] - q["due"] for q in self.led.req.values()
                if q["admit"] is not None and self._in(q["admit"])]

    def peak(self, key: str) -> float:
        return float(self.peaks[key])

    # ---- traced run ------------------------------------------------------
    def _dev(self):
        tr = self.trace
        return tr, tracemod.device(tr), tracemod.window(tr)

    def step_device(self):
        tr, dev, (lo, hi) = self._dev()
        return tracemod.program(tr.modules.get(dev, []), "_step", lo, hi)

    def kernel_device(self, match: str = 'custom_call_target="tpu_custom_call"'):
        tr, dev, (lo, hi) = self._dev()
        return tracemod.program(tr.ops.get(dev, []), match, lo, hi)

    def busy(self):
        tr, dev, (lo, hi) = self._dev()
        return tracemod.busy_ns(tr.ops[dev], lo, hi) * 1e-9, (hi - lo) * 1e-9

    def useful_flops(self) -> float:
        conf = self.cell.conf
        mm = flops.matmul_flops_per_token(conf)
        total = 0
        for dec, pre, sampled in self.lanes:
            total += sampled * flops.head_flops(conf)
            for ctx in dec:
                total += mm + flops.attention_flops(conf, ctx)
            for a, b in pre:
                n = b - a
                ctx_sum = (a + 1 + b) * n // 2
                total += n * mm + flops.attention_flops(conf, ctx_sum)
        return float(total)

    def decode_attention_bytes(self) -> float:
        conf = self.cell.conf
        return float(sum(flops.decode_attention_bytes(conf, ctx)
                         for dec, _, _ in self.lanes for ctx in dec))


def sample(chk: dict, seed: int, finished: dict) -> list:
    """The finished requests compared: the longest, then one drawn from
    the seed in every other slot that finished one, then more drawn from
    the seed until they hold ``min_tokens`` served tokens."""
    rng = np.random.default_rng(seed)
    order = sorted(finished, key=lambda r: (-finished[r]["tokens"].size, r))
    pick = order[:1]
    rest = [order[i] for i in rng.permutation(len(order))]
    rest = [r for r in rest if r not in pick]
    slots = {finished[r]["slot"] for r in pick}
    for r in list(rest):
        if finished[r]["slot"] not in slots:
            slots.add(finished[r]["slot"])
            pick.append(r)
            rest.remove(r)
    while rest and sum(finished[r]["tokens"].size for r in pick) < int(
            chk["min_tokens"]):
        pick.append(rest.pop(0))
    return pick


def widest_gaps(ref, picks) -> list:
    """For each request: the widest distance by which a picked token's
    reference logit lies below the reference's best at that step, in
    standard deviations of that step's logits (inf where a compared
    step's logits are not finite)."""
    import jax.numpy as jnp
    out = []
    for lg, tok in zip(ref, picks):
        got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], 1)[:, 0]
        gap = np.asarray((lg.max(-1) - got) / lg.std(-1), np.float64)
        out.append(float(gap.max()) if np.isfinite(gap).all()
                   else float("inf"))
    return out


def compare(cell: Cell, seed: int, finished: dict, control: bool):
    """The served tokens of a seeded sample of finished requests (see
    :func:`sample`) against the float32 reference's logits.

    Returns ``(numbers, failed, control_numbers)``.  ``numbers`` maps a
    compared quantity to ``(value, limit)``: ``gap_sigma``, the widest
    gap (:func:`widest_gaps`) over the sample, and ``tokens``, the
    served tokens compared (a minimum); ``failed`` counts the requests
    whose gap passes the limit.  With ``control`` the float8 reference
    is put in the program's place on the same prompts and tokens: the
    token it ranks first at each step is scored the same way, giving
    its own ``(numbers, failed)``; otherwise ``control_numbers`` is
    None."""
    from bench.reference import logits_rows
    chk = cell.cell["check"]
    limit, least = float(chk["gap_sigma"]), int(chk["min_tokens"])
    pick = sample(chk, seed, finished)
    served = [finished[r]["tokens"] for r in pick]
    seqs = []
    for rid, tok in zip(pick, served):
        q = finished[rid]
        toks = np.concatenate([q["prompt"], tok[:-1]]).astype(np.int32)
        rows = np.arange(q["plen"] - 1, q["plen"] - 1 + tok.size)
        seqs.append((toks, rows))
    n_tok = int(sum(t.size for t in served))
    t0 = time.perf_counter()
    ref = logits_rows(cell.conf, seed, seqs)
    gaps = widest_gaps(ref, served)
    log(f"reference over {len(pick)} requests in "
        f"{len({finished[r]['slot'] for r in pick})} slots, {n_tok} served "
        f"tokens: {time.perf_counter() - t0:.2f} s")

    def scored(gaps):
        return ({"gap_sigma": (max(gaps, default=0.0), limit),
                 "tokens": (n_tok, least)},
                sum(1 for g in gaps if not g <= limit))

    ctl = None
    if control:
        t0 = time.perf_counter()
        low = logits_rows(cell.conf, seed, seqs, precision="fp8")
        ctl = scored(widest_gaps(ref, [lo.argmax(-1) for lo in low]))
        log(f"control (float8 reference): {time.perf_counter() - t0:.2f} s")
    return (*scored(gaps), ctl)


def judge(numbers: dict, failed: int) -> bool:
    """Every compared number within its limit and no request failed."""
    return failed == 0 and all(
        v >= lim if k in MINIMA else v == lim if k in EXACT else v <= lim
        for k, (v, lim) in numbers.items())


def check_lines(numbers: dict, prefix: str = "") -> dict:
    """Log each compared number beside its limit; the result line's
    ``check`` entries."""
    out = {}
    for k, (v, lim) in numbers.items():
        rel = "min" if k in MINIMA else "equals" if k in EXACT else "limit"
        log(f"check {prefix}{k} {v!r} {rel} {lim!r}")
        out[prefix + k] = {"value": v, rel: lim}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop rate override (knee sweep)")
    ap.add_argument("--control", action="store_true",
                    help="also read the float8 control's gap")
    args = ap.parse_args(argv)
    cell = Cell(ROOT, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform!r} device(s)")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    return run(cell, args, devices)


def run(cell: Cell, args, devices) -> int:
    """One run of ``cell`` on ``devices`` (``main`` has checked them)."""
    import jax
    from bench import model

    peaks_all = load_json(cell.root / "bench" / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks_all:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    events = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: events.update([name]))
    jax.monitoring.register_event_listener(
        lambda name, *a, **k: events.update([name]))

    conf, mix, serving = cell.conf, cell.mix, cell.conf["serving"]
    chunk = int(serving["prefill_chunk"])
    cfg = model.arch_config(conf)
    bndl = model.bundle(cfg)
    t = time.perf_counter()
    params = model.draw_params(bndl, cfg, args.seed)
    log(f"weights drawn in {time.perf_counter() - t:.2f} s")
    sched = model.scheduler(bndl, cfg, params, serving, mix["kv_domain"])
    # The configuration's KV domain as the scheduler runs it: 1 where the
    # read path injects (and, with ECC, corrects) on every decode read.
    injection = int(bool(sched.active) and sched.mode == "read")
    log(f"kv domain {mix['kv_domain']}: injection live {sched.active}, "
        f"mode {sched.mode}, method {sched.method}")
    led = Ledger(chunk)
    vocab = cfg.vocab
    clock = time.perf_counter

    compile_request(sched, led, cell, vocab, args.seed)
    warm_chunk_feeds(sched)
    sched.metrics.totals(sched.state)
    if mix["loop"] == "open":
        rate = args.rate if args.rate is not None else float(
            cell.cell["rate_per_s"])
        pending = collections.deque(traffic.open_loop(
            mix, rate=rate, seconds=args.seconds, vocab=vocab,
            seed=args.seed))
        origin = clock()
        drive(sched, led, origin + float(mix["warmup_s"]),
              pending=pending, origin=origin)
        clients = None
    else:
        rate, pending, origin = None, None, 0.0
        seqs = traffic.closed_loop(
            mix, clients=sched.num_slots,
            per_client=int(mix["requests_per_client"]), vocab=vocab,
            seed=args.seed)
        clients = first_wave(sched, led, seqs, chunk, args.seed)

    jax.block_until_ready(sched.state)
    c0 = sched.metrics.totals(sched.state)
    trace_dir = None
    if args.trace:
        trace_dir = cell.root / "bench_out" / "trace" / (
            f"{cell.workload['name']}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        led.lanes = []
    compiles0 = events[BACKEND_COMPILE]
    steps0 = sched.steps
    t0 = clock()
    setup_s = t0 - T_START
    if pending is not None:
        origin += t0 - (origin + float(mix["warmup_s"]))
    loop = dict(pending=pending, origin=origin, clients=clients)
    traced = min(args.seconds, TRACE_S) if args.trace else args.seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        t1 = drive(sched, led, t0 + traced, **loop)
    lanes, led.lanes = led.lanes, None
    read_to = t1       # host-clock readers read what the trace covers
    if args.trace:
        jax.profiler.stop_trace()
        t1 = drive(sched, led, t0 + args.seconds, **loop)
    compiles = events[BACKEND_COMPILE] - compiles0
    steps = sched.steps - steps0
    c1 = sched.metrics.totals(sched.state)
    counters = {k: c1[k] - c0[k] for k in c1}
    mem_peak = max(mem(d, "peak_bytes_in_use") for d in devices[:cell.chips])
    limit = mem(devices[0], "bytes_limit")
    log(f"set-up {setup_s:.3f} s (compile cache misses in set-up: "
        f"{events[CACHE_MISS]}); window {t1 - t0:.3f} s, {steps} steps, "
        f"{(t1 - t0) / max(steps, 1) * 1e3:.2f} ms a step")
    log(f"compiles inside the window: {compiles}")
    log(f"step counters over the window: {counters}")
    log(f"memory: peak {mem_peak} B of {limit} B")
    lateness = [q["submit"] - q["due"] for q in led.req.values()
                if t0 <= q["submit"] <= t1]
    if mix["loop"] == "open":
        log(f"generator lateness: p50 {pctl(lateness, 50)} s, max "
            f"{max(lateness, default=None)} s over {len(lateness)} "
            f"requests; rate {rate}/s; queued at the window's end "
            f"{len(sched.queue)}, active {sched.n_active}")
    attempted = sum(1 for q in led.req.values() if t0 <= q["submit"] <= t1)
    drain(sched, led, int(cell.cell["check"]["min_tokens"]))
    finished = {rid: q for rid, q in led.req.items() if q["done"]}

    del sched, params
    gc.collect()
    jax.clear_caches()
    log(f"after freeing the program: {mem(devices[0], 'bytes_in_use')} B "
        "in use")

    rd = Run(cell, led, t0, read_to, setup_s, peaks_all[kind])
    rd.lanes = lanes
    breakdown = None
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": mem_peak}
    if args.trace:
        t = time.perf_counter()
        rd.trace = tracemod.load(str(trace_dir))
        log(f"trace read in {time.perf_counter() - t:.1f} s")
        busy_s, window_s = rd.busy()
        dev.update(busy_s=busy_s, window_s=window_s)
        d = tracemod.device(rd.trace)
        lo, hi = tracemod.window(rd.trace)
        idle = tracemod.attribute(tracemod.gaps(rd.trace.ops[d], lo, hi),
                                  rd.trace.host)
        breakdown = {
            "device_ops": tracemod.top(rd.trace.ops[d], lo, hi),
            "idle_gaps": [[k, v * 1e-9] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:10]]}
        log(f"device idle share {100 * (1 - busy_s / window_s)!r} %")
        shutil.rmtree(trace_dir, ignore_errors=True)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(section):
        v = cell.reader(m["name"])(rd)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"{m['name']}: {v!r} {m['unit']}")

    numbers, n_bad, ctl = compare(cell, args.seed, finished, args.control)
    numbers["kv_injection"] = (injection, int(cell.cell["check"]
                                              ["kv_injection"]))
    correct = judge(numbers, n_bad)
    check = check_lines(numbers)
    if ctl is not None:
        log(f"control: correct {judge(*ctl)}, {ctl[1]} requests failed")
        check.update(check_lines(ctl[0], "control_"))
        check["control"] = {"correct": judge(*ctl), "failed": ctl[1]}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": n_bad, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    log(f"run took {time.perf_counter() - T_START:.1f} s from the "
        "start of the process")
    print(json.dumps(out), flush=True)
    return 0


def drain(sched, led: Ledger, tokens: int, limit_s: float = 120.0) -> None:
    """After the window, untimed: step the requests already admitted
    (nothing new is admitted) until the finished ones hold ``tokens``
    served tokens and every slot that served a request has finished one,
    so the check has some hundreds to compare from every slot."""
    def short() -> bool:
        done = [q for q in led.req.values() if q["done"]]
        used = {q["slot"] for q in led.req.values() if q["slot"] is not None}
        return (sum(q["tokens"].size for q in done) < tokens
                or bool(used - {q["slot"] for q in done}))

    t0 = time.perf_counter()
    while short() and led.active and time.perf_counter() - t0 < limit_s:
        led.step(sched)
    log(f"drained {time.perf_counter() - t0:.2f} s after the window")


def compile_request(sched, led: Ledger, cell: Cell, vocab: int,
                    seed: int) -> None:
    """Serve one short request to the end before anything is timed: its
    admission, prefill chunk, transition, decode step, sampling and
    retirement compile (or load from the cache) every program the window
    runs, at the window's shapes."""
    chunk = int(cell.conf["serving"]["prefill_chunk"])
    prompt = np.random.default_rng(seed).integers(
        0, vocab, chunk + 1).astype(np.int32)
    led.submit(sched, traffic.Req("warm", prompt, 3), time.perf_counter())
    while led.waiting or led.active:
        led.admit(sched)
        led.step(sched)


def first_wave(sched, led: Ledger, seqs, chunk: int, seed: int) -> dict:
    """Serve each client's first request so that, when the window opens,
    client ``c`` stands a stratified share of the way through its
    request's life (prefill steps, then one token a step): the closed
    loop starts near its steady mix instead of with every slot
    prefilling at once.  A client deep in decode has its first request
    cut short by the steps it would have served before warm-up began."""
    phase = traffic.phases(len(seqs), seed)
    pre = [-(-len(s[0].prompt) // chunk) for s in seqs]
    w = max(pre)
    start, first = {}, {}
    for c, s in enumerate(seqs):
        r = s[0]
        life = pre[c] + r.max_new - 1
        at = int(phase[c] * life)
        if at <= w:
            start[c], first[c] = w - at, r
        else:
            keep = (w - pre[c] + 1) + (life - at)
            start[c] = 0
            first[c] = dataclasses.replace(
                r, max_new=max(keep, w - pre[c] + 2))
    clients = {c: {"reqs": iter(s[1:]), "current": first[c].rid}
               for c, s in enumerate(seqs)}
    for step in range(w + 1):
        for c in [c for c in start if start[c] == step]:
            led.submit(sched, first[c], time.perf_counter())
        led.admit(sched)
        if step < w and sched.n_active:
            led.step(sched)
    return clients


if __name__ == "__main__":
    sys.exit(main())
