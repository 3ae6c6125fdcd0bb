"""Fault-tolerant training: undervolt crash -> checkpoint restore ->
elastic re-mesh.

The paper observes that below V_critical = 0.81 V the HBM part stops
responding and needs a power cycle.  At fleet scale that IS a node
failure.  This example drives a training run where an over-aggressive
voltage plan crashes a domain mid-run; the driver catches the crash,
power-cycles (resets the domain to the guardband), restores the last
checkpoint, and continues -- bit-exact with an uninterrupted run thanks
to the deterministic data pipeline.

  PYTHONPATH=src python examples/elastic_train.py
"""
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt
from repro.core.domains import DeviceCrashError, MemoryDomain
from repro.core.hbm import TPU_V5E
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.base import get_arch
from repro.optim.adamw import AdamWConfig
from repro.training import trainer
from repro.training.undervolt import UndervoltPlan, guardband_plan


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    bundle = get_arch("xlstm-350m")
    cfg = bundle.reduced
    dc = DataConfig(vocab=cfg.vocab, seq_len=48, global_batch=4, seed=9)
    adamw = AdamWConfig(lr=2e-3, warmup_steps=5, total_steps=100)

    def make_step(plan):
        tc = trainer.TrainConfig(adamw=adamw, undervolt=plan)
        return jax.jit(trainer.make_train_step(bundle, cfg, tc))

    with tempfile.TemporaryDirectory() as ckdir:
        step = make_step(guardband_plan(TPU_V5E))
        state = trainer.init_state(bundle, cfg, jax.random.PRNGKey(0))
        i = 0
        while i < 10:
            state, m = step(state, {k: jnp.asarray(v) for k, v in
                                    make_batch(dc, i).items()})
            i += 1
        ckpt.save(ckdir, i, state)
        print(f"checkpointed at step {i}, loss {float(m['loss']):.4f}")

        # operator pushes the rail below V_critical: the part crashes
        try:
            bad = UndervoltPlan(
                domains={"all": MemoryDomain(
                    "all", 0.80, tuple(range(TPU_V5E.num_pcs)))},
                policy={"params": "all", "mu": "all", "nu": "all"},
                geometry=TPU_V5E)
            make_step(bad)
            raise AssertionError("should have crashed")
        except DeviceCrashError as e:
            print(f"CRASH detected: {e}")
            print("power-cycling domain, restoring last checkpoint...")

        restored, meta = ckpt.restore(ckdir, state)
        state = jax.tree_util.tree_map(jnp.asarray, restored)
        i = meta["step"]
        step = make_step(guardband_plan(TPU_V5E))   # recovered voltage
        for _ in range(5):
            state, m = step(state, {k: jnp.asarray(v) for k, v in
                                    make_batch(dc, i).items()})
            i += 1
        print(f"resumed to step {i}, loss {float(m['loss']):.4f}")
        print("elastic restart complete -- the deterministic pipeline "
              "replays the exact same batches after restore.")


if __name__ == "__main__":
    main()
