"""Fault-tolerance example: train with checkpoints, inject a node failure
mid-run, restart from the latest checkpoint, and verify the final weights
are bit-identical to an uninterrupted run (exactly-once semantics).

Run:  PYTHONPATH=src python examples/fault_tolerant_train.py
"""
import shutil
import tempfile

import jax
import numpy as np

from repro.data.pipeline import DataConfig
from repro.models.registry import build_model
from repro.optim.optimizer import OptConfig
from repro.train.fault import FailurePlan, run_with_restarts
from repro.train.loop import LoopConfig, TrainLoop

STEPS = 20


def build(tmp, fail_at=()):
    model = build_model("fpnew-case-study", policy="tp_bf16", reduced=True)
    opt = OptConfig(lr=3e-3, warmup_steps=5, total_steps=STEPS,
                    weight_decay=0.0)
    data = DataConfig(vocab=model.cfg.vocab, seq_len=64, global_batch=8,
                      noise=0.0)
    lc = LoopConfig(total_steps=STEPS, log_every=5, ckpt_every=6,
                    ckpt_dir=tmp)
    loop = TrainLoop(model, opt, data, lc,
                     failure_plan=FailurePlan(fail_at=fail_at)
                     if fail_at else None)
    return loop


def main():
    tmp_a = tempfile.mkdtemp()
    tmp_b = tempfile.mkdtemp()
    try:
        print("--- reference run (no failures) ---")
        ref = build(tmp_a)
        ref.run()

        print("\n--- faulty run: node failure injected at step 10 ---")
        plan = FailurePlan(fail_at=(10,))

        def make():
            loop = build(tmp_b)
            loop.failure_plan = plan
            return loop

        loop, restarts = run_with_restarts(make, max_restarts=2)
        print(f"\nrecovered with {restarts} restart(s); resumed from step "
              f"{loop.metrics_log[0]['step']} (latest checkpoint)")

        for x, y in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(loop.params)):
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))
        print("final weights BIT-IDENTICAL to the uninterrupted run  [OK]")
        if loop.monitor.flagged:
            print("stragglers flagged:", list(loop.monitor.flagged))
    finally:
        shutil.rmtree(tmp_a, ignore_errors=True)
        shutil.rmtree(tmp_b, ignore_errors=True)


if __name__ == "__main__":
    main()
