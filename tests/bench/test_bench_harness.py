"""CPU tests of the chip benchmark's harness (``bench/``).

They cover what a CPU can show: the trace reduction on synthetic
traces, the operation and byte counts against hand counts at both
configurations' shapes, the seeded traffic, the names and units of
BENCHMARK.json, the reference against the program's logits, and whole
runs of the harness on miniature cells, sound and with the timed path
broken.  No number here is a device metric.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).resolve().parent)]

from bench import flops, traffic  # noqa: E402
from bench import trace as tr  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def conf(name):
    c = [c for c in SPEC["configs"] if c["name"] == name][0]
    return json.loads((ROOT / c["file"]).read_text())


# ---- trace reduction --------------------------------------------------------

def synthetic():
    ms = 1_000_000
    return tr.Trace(
        ops={"/device:TPU:0": [
            ("fusion.1", 0, 10 * ms), ("paged_kernel", 5 * ms, 10 * ms),
            ("fusion.2", 30 * ms, 10 * ms), ("paged_kernel", 60 * ms,
                                             5 * ms),
            ("%while.1 = (s32[])", 0, 40 * ms)]},
        modules={"/device:TPU:0": [("jit__step_fn(1)", 0, 15 * ms),
                                   ("jit__step_fn(1)", 30 * ms, 35 * ms),
                                   ("jit_other(2)", 90 * ms, 1 * ms)]},
        host=[("bench.window", 0, 100 * ms), ("bench.step", 0, 16 * ms),
              ("bench.admit", 16 * ms, 10 * ms),
              ("bench.step", 28 * ms, 40 * ms),
              ("other.span", 0, 100 * ms)])


def test_trace_busy_union_gaps_and_attribution():
    t = synthetic()
    ops = t.ops["/device:TPU:0"]
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 100_000_000)
    # [0, 40) (the loop op spans its body) + [60, 65) ms, counted once
    assert tr.busy_ns(ops, lo, hi) == 45_000_000
    assert tr.gaps(ops, lo, hi) == [(40_000_000, 60_000_000),
                                    (65_000_000, 100_000_000)]
    idle = tr.attribute(tr.gaps(ops, lo, hi), t.host)
    # 40..60 ms under the step span 28..68; 65..100: step to 68, then none
    assert idle == {"bench.step": 20_000_000 + 3_000_000,
                    "other": 32_000_000}
    no_loop = [e for e in ops if not e[0].startswith("%while")]
    idle = tr.attribute(tr.gaps(no_loop, lo, hi), t.host)
    # 15..30 ms: step 15..16, admit 16..26, nothing 26..28, step 28..30
    assert idle["bench.admit"] == 10_000_000
    assert idle["bench.step"] == 1_000_000 + 2_000_000 + 20_000_000 + \
        3_000_000
    assert idle["other"] == 2_000_000 + 32_000_000
    # clipping to a narrower window
    assert tr.busy_ns(no_loop, 8_000_000, 12_000_000) == 4_000_000


def test_trace_program_and_kernel_time():
    t = synthetic()
    lo, hi = tr.window(t)
    assert tr.program(t.modules["/device:TPU:0"], "_step", lo, hi) == (
        50_000_000, 2)
    assert tr.program(t.ops["/device:TPU:0"], "paged", lo, hi) == (
        15_000_000, 2)
    top = tr.top(t.ops["/device:TPU:0"], lo, hi, n=2)
    # the loop op (40 ms) holds its body's operations: left out
    assert top[0][0] == "paged_kernel" and abs(top[0][1] - 0.015) < 1e-12
    assert len(top) == 2 and top[1][0].startswith("fusion")
    assert tr.device(t) == "/device:TPU:0"


def test_trace_window_missing_is_an_error():
    with pytest.raises(ValueError):
        tr.window(tr.Trace(ops={}, modules={}, host=[]))


# ---- operation and byte counts ----------------------------------------------

def test_flops_and_bytes_hand_counts():
    il, yi = conf("internlm2-1.8b"), conf("yi-34b-d8")
    # per layer: q, k, v, o projections and three MLP matrices
    il_layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192
    assert flops.matmul_flops_per_token(il) == 2 * il_layer * 24
    yi_layer = 7168 * 7168 + 2 * 7168 * 1024 + 7168 * 7168 \
        + 3 * 7168 * 20480
    assert flops.matmul_flops_per_token(yi) == 2 * yi_layer * 8
    assert flops.head_flops(il) == 2 * 2048 * 92553
    # 16 heads x 128 x (QK + PV) x 2 per multiply-add x 24 layers
    assert flops.attention_flops(il, 3000) == 4 * 16 * 128 * 3000 * 24
    assert flops.kv_bytes_per_position(il) == 98304
    assert flops.kv_bytes_per_position(yi) == 32768
    # K/V of the live positions plus query and output, every layer
    assert flops.decode_attention_bytes(yi, 500) == (
        32768 * 500 + 2 * 7168 * 2 * 8)


# ---- traffic ----------------------------------------------------------------

def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def test_open_loop_traffic_is_seeded_exact_and_clipped():
    m = mix("chat.ecc095")
    a = traffic.open_loop(m, rate=3.0, seconds=10, vocab=1000, seed=7)
    b = traffic.open_loop(m, rate=3.0, seconds=10, vocab=1000, seed=7)
    c = traffic.open_loop(m, rate=3.0, seconds=10, vocab=1000,
                          seed=2 ** 31 + 9)
    assert len(a) == round(3.0 * (m["warmup_s"] + 10)) == len(c)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new)
                                               for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: the same schedule and sizes, other token ids
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == [
        (r.due, len(r.prompt), r.max_new) for r in c]
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    assert all(64 <= len(r.prompt) <= 1024 and 16 <= r.max_new <= 256
               for r in a)
    dues = [r.due for r in a]
    assert dues == sorted(dues) and dues[0] == 0.0
    assert dues[-1] < m["warmup_s"] + 10
    assert all(0 <= t < 1000 for r in a for t in r.prompt)


def test_closed_loop_traffic_is_seeded_and_clipped():
    m = mix("longctx.ecc095")
    a = traffic.closed_loop(m, clients=24, per_client=6, vocab=500, seed=3)
    b = traffic.closed_loop(m, clients=24, per_client=6, vocab=500, seed=4)
    flat = [r for s in a for r in s]
    assert len(a) == 24 and all(len(s) == 6 for s in a)
    assert all(1024 <= len(r.prompt) <= 1536 and 256 <= r.max_new <= 512
               for r in flat)
    assert sorted(r.max_new for r in flat) == sorted(
        r.max_new for s in b for r in s)
    p = traffic.phases(24, 3)
    assert sorted(p) == sorted((np.arange(24) + 0.5) / 24)


def test_gamma_gaps_have_the_asked_spread():
    g = traffic.gamma_gaps(20000, 2.0, 1000.0, set_seed=0)
    assert abs(g.sum() - 1000.0) < 1e-6
    assert abs(g.std() / g.mean() - 2.0) < 0.1


# ---- BENCHMARK.json -----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_units_and_files():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    names = [c["name"] for c in SPEC["configs"]] + list(cells) + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).exists() and len(c["why"]) <= 200
        for k in c["reduced"]:
            assert NAME.match(k)
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").exists()
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["source"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    w = SPEC["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", w,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


# ---- weights and the reference ------------------------------------------------

def test_reference_draws_the_bits_the_program_serves():
    import jax.numpy as jnp
    from bench import model, reference
    import tiny
    c = dict(tiny.TINY, name="tiny")
    cfg = model.arch_config(c)
    seed = 2 ** 31 + 5
    p = model.draw_params(model.bundle(cfg), cfg, seed)
    key = model.seed_key(seed)
    st = p["stack"]["periods"]["s0_global"]
    for name, layer, got in [("wq", 1, st["wq"][1]), ("ln2", 0, st["ln2"][0]),
                             ("wd", 1, st["wd"][1]), ("embed", 0, p["embed"]),
                             ("unembed", 0, p["unembed"])]:
        want = reference._draw(key, jnp.uint32(layer), name=name,
                               shape=tuple(got.shape), d_model=cfg.d_model)
        assert bool(jnp.all(got.astype(jnp.float32) == want)), name


def test_reference_agrees_with_the_program_logits():
    """The program's own forward pass (bf16) and the float32 reference
    give the same next-token logits to bf16 rounding."""
    import jax.numpy as jnp
    from bench import model, reference
    import tiny
    c = dict(tiny.TINY, name="tiny")
    cfg = model.arch_config(c)
    b = model.bundle(cfg)
    p = model.draw_params(b, cfg, 11)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, 40)
    from repro.models import dense
    logits, _ = dense.prefill(p, {"tokens": jnp.asarray(toks)[None]}, cfg,
                              max_len=64)
    ref = reference.logits_rows(c, 11, [(toks, np.array([39]))])[0][0]
    got = np.asarray(logits[0], np.float64)
    ref = np.asarray(ref, np.float64)
    assert np.abs(got - ref).max() < 0.05 * ref.std()
    # a wrong weight draw is far off
    bad = reference.logits_rows(c, 12, [(toks, np.array([39]))])[0][0]
    assert np.abs(got - np.asarray(bad)).max() > 0.5 * ref.std()


# ---- whole runs on miniature cells ------------------------------------------

def harness_run(tmp_path, capsys, cell, *, seconds=1.5, seed=3, **kw):
    import jax
    import tiny
    from bench import run as br
    root = tiny.make_root(tmp_path, **kw)
    c = br.Cell(root, cell)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0, rate=None, control=False)
    assert br.run(c, args, jax.devices()) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


@pytest.mark.parametrize("cell", ["tiny.closed.ecc", "tiny.open.ecc"])
def test_harness_run_is_correct_and_reports_its_metrics(tmp_path, capsys,
                                                        cell):
    res = harness_run(tmp_path, capsys, cell, seed=2 ** 31 + 3)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "check"
    m = res["metrics"]
    assert {"tokens_per_s", "itl_p95_s", "setup_s"} <= set(m)
    assert m["tokens_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["check"]["tokens"]["value"] >= res["check"]["tokens"]["min"]
    assert res["check"]["kv_injection"] == {"value": 1, "equals": 1}


def _tokens_altered(monkeypatch):
    from repro.serving.scheduler import ContinuousBatchingScheduler as S
    collect = S._collect
    monkeypatch.setattr(S, "_collect", lambda self, g, rid, t: collect(
        self, g, rid, (int(t) + 1) % self.cfg.vocab))


def _state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from repro.serving.scheduler import ContinuousBatchingScheduler as S
    monkeypatch.setattr(S, "_step_fn", lambda self, params, state, v: (
        state, jnp.zeros(state["tok"].shape[:2] + (1,), jnp.int32)))


def _half_batch(monkeypatch):
    from repro.serving.scheduler import ContinuousBatchingScheduler as S
    step = S._step_fn

    def half(self, params, state, v):
        new, nt = step(self, params, state, v)
        h = nt.shape[1] // 2
        return new, nt.at[:, h:].set(nt[:, :h])
    monkeypatch.setattr(S, "_step_fn", half)


def _injection_off(monkeypatch):
    """The KV domain served at the bottom of the guardband, where the
    scheduler compiles the read-path injection out."""
    from bench import model
    build = model.scheduler
    monkeypatch.setattr(model, "scheduler", lambda b, c, p, s, d: build(
        b, c, p, s, dict(d, voltage=0.98)))


@pytest.mark.parametrize("fault", [_tokens_altered, _state_unchanged,
                                   _half_batch, _injection_off])
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch,
                                            fault):
    fault(monkeypatch)
    res = harness_run(tmp_path, capsys, "tiny.closed.ecc")
    assert res["correct"] is False


def test_the_float8_control_fails_the_comparison(tmp_path):
    """The control through the harness's own comparison: the reference
    put in the program's place one precision step down (float8 e4m3)
    reads not correct at the least limit of any cell, while the
    program's greedy bf16 tokens on the same requests read correct
    (16x narrower widths than the cells, same architecture)."""
    import jax
    import jax.numpy as jnp
    from bench import model
    from bench import run as br
    from repro.models import dense
    from repro.models import layers as L
    import tiny
    wide = dict(tiny.TINY, hidden_size=512, intermediate_size=1024,
                num_hidden_layers=8, num_attention_heads=4,
                num_key_value_heads=2, head_dim=128, vocab_size=8192)
    limit = min(json.loads((ROOT / "bench" / "cells" / f"{w['name']}.json")
                           .read_text())["check"]["gap_sigma"]
                for w in SPEC["workloads"])
    root = tiny.make_root(tmp_path, config=wide, gap_sigma=limit,
                          min_tokens=128)
    cell = br.Cell(root, "tiny.closed.ecc")
    cfg = model.arch_config(cell.conf)
    seed, ring, plen, new = 5, 256, 96, 64
    p = model.draw_params(model.bundle(cfg), cfg, seed)

    @jax.jit
    def logits(p, toks):
        x = L.embed(toks[None], p["embed"])
        x, _ = dense._run_stack(cfg, p, x, jnp.arange(ring)[None], None,
                                "train")
        return L.unembed(x, p["unembed"])[0]

    rng = np.random.default_rng(1)
    finished = {}
    for slot in range(2):
        toks = np.zeros(ring, np.int32)
        toks[:plen] = rng.integers(0, cfg.vocab, plen)
        for i in range(plen, plen + new):
            toks[i] = int(jnp.argmax(logits(p, jnp.asarray(toks))[i - 1]))
        finished[f"r{slot}"] = dict(prompt=toks[:plen], plen=plen,
                                    tokens=toks[plen:plen + new],
                                    slot=slot, done=True)
    numbers, failed, ctl = br.compare(cell, seed, finished, control=True)
    assert numbers["tokens"] == (2 * new, 128)
    assert br.judge(numbers, failed), numbers
    assert not br.judge(*ctl), ctl
    assert ctl[0]["gap_sigma"][0] > 2 * numbers["gap_sigma"][0]
