"""EDEN-style resilient inference: serve a model with its KV cache in an
undervolted HBM domain and measure output degradation vs. power saved.

The paper's three-factor trade-off, application-level: at each voltage
the trade-off solver picks the most reliable PCs for the cache, faults
are injected through the real kernel every decode step, and we compare
greedy generations against the V_nom reference.

  PYTHONPATH=src python examples/resilient_inference.py
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hbm import VCU128
from repro.launch.compile_cache import enable_compile_cache
from repro.models.base import get_arch, init_params
from repro.serving.engine import ServeConfig, generate
from repro.training.undervolt import UndervoltPlan
from repro.core.domains import MemoryDomain
from repro.core.faultmap import PAPER_MAP_SEED, FaultMap
from repro.core.voltage import DEFAULT_POWER_MODEL


def plan_at(v: float) -> UndervoltPlan:
    fmap = FaultMap.from_seed(VCU128, seed=PAPER_MAP_SEED)
    pcs = tuple(int(p) for p in fmap.usable_pcs(v, 1.0))[:16] or tuple(
        range(16))
    return UndervoltPlan(
        domains={"kv": MemoryDomain("kv", v, pcs)},
        policy={"kv_cache": "kv"}, geometry=VCU128,
        map_seed=PAPER_MAP_SEED)


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    bundle = get_arch("gemma3-4b")
    cfg = bundle.reduced
    params = init_params(bundle.module.param_specs(cfg),
                         jax.random.PRNGKey(0))
    prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                            (4, 12), 0, cfg.vocab)}

    ref = None
    for v in (1.20, 0.98, 0.93, 0.89, 0.86):
        sc = ServeConfig(max_len=64, max_new_tokens=16,
                         undervolt=plan_at(v) if v < 1.2 else None)
        toks = np.asarray(generate(bundle, cfg, params, prompts, sc))
        if ref is None:
            ref = toks
        agreement = float((toks == ref).mean())
        savings = float(DEFAULT_POWER_MODEL.savings(v, 0.5))
        print(f"V={v:.2f}  power_savings={savings:4.2f}x  "
              f"token_agreement_vs_nominal={agreement:5.1%}")

    print("\nguardband serving is bit-identical; deeper voltages trade "
          "fidelity for power -- the paper's capacity/fault-rate/power "
          "triangle at the application level.")


if __name__ == "__main__":
    main()
