"""Seeded traffic from a mix file under ``bench/traffic/``.

One generator reads every mix.  A mix fixes the sizes and inter-arrival
gaps (drawn once from ``set_seed``), so every seed offers the same work.
The run's ``--seed`` draws the token ids and, in a closed loop, the order
in which the clients take the sizes.  An open loop replays one schedule
in the same order for every seed: which bursts and which long requests
fall inside a window of a minute would otherwise swing its tail and its
rate by a fifth from seed to seed (my chip runs, PR 13).

Length distributions (``prompt``, ``output``):
  ``{"dist": "uniform", "min": a, "max": b}``
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
Open loop: ``{"loop": "open", "cv": c, "warmup_s": w}``, gamma gaps of
coefficient of variation ``c`` at the rate the cell file gives.
Closed loop: ``{"loop": "closed"}``, one client per serving slot, each
sending its next request when the last one finishes.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One generated request: its prompt, output budget and due time
    (seconds after the start of warm-up; None in a closed loop)."""

    rid: str
    prompt: np.ndarray
    max_new: int
    due: float | None = None


def draw_lengths(dist: dict, n: int, set_seed: int) -> np.ndarray:
    """``n`` integer lengths of ``dist``, clipped to [min, max]."""
    rng = np.random.default_rng(set_seed)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, n)
    elif dist["dist"] == "lognormal":
        x = np.exp(np.log(float(dist["median"]))
                   + float(dist["sigma"]) * rng.standard_normal(n))
        x = np.rint(x)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def gamma_gaps(n: int, cv: float, total: float, set_seed: int) -> np.ndarray:
    """``n`` gamma inter-arrival gaps of coefficient of variation ``cv``,
    scaled so that they sum to ``total`` seconds (an exact rate)."""
    rng = np.random.default_rng(set_seed)
    shape = 1.0 / (cv * cv)
    g = rng.gamma(shape, 1.0, n)
    return g * (total / g.sum())


def _ordered(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    return values[rng.permutation(values.size)]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n).astype(np.int32)


def open_loop(mix: dict, *, rate: float, seconds: float, vocab: int,
              seed: int) -> List[Req]:
    """Requests due over warm-up plus the window, at ``rate`` per second.

    The window starts at ``mix["warmup_s"]``.  The count is the rate
    times the whole span, so the offered load is exact in every run; the
    schedule and the sizes are the mix's, the token ids the seed's."""
    span = float(mix["warmup_s"]) + float(seconds)
    n = max(1, int(round(rate * span)))
    base = int(mix["set_seed"])
    gaps = gamma_gaps(n, float(mix["cv"]), span, base)
    plen = draw_lengths(mix["prompt"], n, base + 1)
    nout = draw_lengths(mix["output"], n, base + 2)
    rng = np.random.default_rng(seed)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Req(f"q{i}", _tokens(rng, int(plen[i]), vocab), int(nout[i]),
                float(due[i])) for i in range(n)]


def closed_loop(mix: dict, *, clients: int, per_client: int, vocab: int,
                seed: int) -> List[List[Req]]:
    """``per_client`` requests for each of ``clients`` clients, sent one
    after another: the set of sizes is fixed, the seed orders it."""
    n = clients * per_client
    base = int(mix["set_seed"])
    plen = draw_lengths(mix["prompt"], n, base + 1)
    nout = draw_lengths(mix["output"], n, base + 2)
    rng = np.random.default_rng(seed)
    plen, nout = _ordered(plen, rng), _ordered(nout, rng)
    return [[Req(f"c{c}r{j}",
                 _tokens(rng, int(plen[c * per_client + j]), vocab),
                 int(nout[c * per_client + j]))
             for j in range(per_client)] for c in range(clients)]


def phases(n: int, seed: int) -> np.ndarray:
    """Stratified points in [0, 1), one per client, ordered by the seed:
    where in its life each client's first request stands when the
    window opens (a closed loop then starts near its steady mix)."""
    rng = np.random.default_rng(seed)
    return (rng.permutation(n) + 0.5) / n
