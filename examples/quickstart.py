"""Quickstart: train a small LM with the paper's undervolting feature on.

Runs on CPU in ~2 minutes: a reduced llama3.2 config, synthetic Markov
data, AdamW, checkpointing, and an undervolt plan that keeps optimizer
state in the guardband-safe domain (1.5x HBM power) while weights ride
an unsafe 0.93 V domain.

  PYTHONPATH=src python examples/quickstart.py
"""
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core.hbm import TPU_V5E
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.base import get_arch
from repro.optim.adamw import AdamWConfig
from repro.training import trainer
from repro.training.undervolt import aggressive_plan


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    bundle = get_arch("llama3.2-3b")
    cfg = bundle.reduced
    plan = aggressive_plan(v_unsafe=0.93, geometry=TPU_V5E)
    tc = trainer.TrainConfig(
        microbatches=2,
        adamw=AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=200),
        undervolt=plan)
    step = jax.jit(trainer.make_train_step(bundle, cfg, tc))
    state = trainer.init_state(bundle, cfg, jax.random.PRNGKey(0))
    dc = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8, seed=1)

    report = plan.power_report(utilization=0.7)
    print(f"undervolt plan: blended HBM power savings "
          f"{report['blended_savings_x']:.2f}x "
          f"({report['pcs_powered']} PCs powered)")
    for name, d in report["domains"].items():
        print(f"  domain {name}: {d['voltage']:.2f} V ({d['region']}), "
              f"{d['pcs']} PCs, savings {d['savings_x']:.2f}x")

    for i in range(60):
        batch = {k: jnp.asarray(v) for k, v in make_batch(dc, i).items()}
        state, m = step(state, batch)
        if i % 10 == 0:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}  "
                  f"grad_norm {float(m['grad_norm']):.3f}  "
                  f"faults(uncorrectable) "
                  f"{int(m.get('uncorrectable_faults', 0))}")
    print("final loss:", float(m["loss"]))
    assert float(m["loss"]) < 5.0, "training should make progress"


if __name__ == "__main__":
    main()
