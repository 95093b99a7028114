"""Substrate tests: data determinism, optimizers, transprecision optimizer
state, gradient compression, checkpointing (atomic/keep-N/mesh-elastic),
the train loop end-to-end, and fault injection + restart."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager, restore_pytree, \
    save_pytree
from repro.core.policy import PRESETS
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.models.registry import build_model
from repro.optim.optimizer import OptConfig, apply_update, init_opt_state, \
    lr_at
from repro.train.fault import FailurePlan, SimulatedFailure, \
    StragglerMonitor, run_with_restarts
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.train_step import make_train_step


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_and_restartable():
    cfg = DataConfig(vocab=512, seq_len=64, global_batch=8)
    d1 = SyntheticLMData(cfg)
    batches = [next(d1) for _ in range(3)]
    d2 = SyntheticLMData(cfg)
    d2.load_state_dict({"step": 2})
    b2 = next(d2)
    np.testing.assert_array_equal(np.asarray(batches[2]["tokens"]),
                                  np.asarray(b2["tokens"]))


def test_data_host_sharding_partitions_batch():
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=8)
    h0 = SyntheticLMData(cfg, host_index=0, host_count=2).batch_at(0)
    h1 = SyntheticLMData(cfg, host_index=1, host_count=2).batch_at(0)
    assert h0["tokens"].shape == (4, 32)
    assert not np.array_equal(np.asarray(h0["tokens"]),
                              np.asarray(h1["tokens"]))


def test_data_is_learnable_structure():
    """Tokens follow the arithmetic progression except noise positions."""
    cfg = DataConfig(vocab=512, seq_len=128, global_batch=4, noise=0.0)
    b = SyntheticLMData(cfg).batch_at(0)
    t = np.asarray(b["tokens"])
    d = np.diff(t, axis=1) % cfg.vocab
    assert (d == d[:, :1]).all()        # constant stride per row


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_descends_quadratic(name):
    pol = PRESETS["fp32"]
    cfg = OptConfig(name=name, lr=0.1, warmup_steps=0, total_steps=100,
                    weight_decay=0.0, clip_norm=None)
    params = {"w": jnp.ones((8, 8)) * 3.0}
    state = init_opt_state(params, cfg, pol)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}          # d/dw ||w||^2
        params, state, _ = apply_update(params, grads, state, cfg, pol)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


def test_lr_schedule():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=110,
                    min_lr_frac=0.1)
    assert float(lr_at(cfg, 0)) == 0.0
    assert abs(float(lr_at(cfg, 10)) - 1.0) < 1e-6
    assert abs(float(lr_at(cfg, 110)) - 0.1) < 1e-6
    assert float(lr_at(cfg, 60)) == pytest.approx(0.55, abs=0.01)


def test_transprecision_moments_stored_narrow():
    pol = PRESETS["prod_tp"]    # bf16 moments
    cfg = OptConfig(lr=1e-2, warmup_steps=0, total_steps=10)
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = init_opt_state(params, cfg, pol)
    assert state["m"]["w"].dtype == jnp.bfloat16
    assert state["v"]["w"].dtype == jnp.bfloat16
    assert state["master"]["w"].dtype == jnp.float32
    grads = {"w": jnp.full((4, 4), 0.5)}
    params2, state2, _ = apply_update(params, grads, state, cfg, pol,
                                      sr_key=jax.random.key(0))
    assert state2["m"]["w"].dtype == jnp.bfloat16
    assert params2["w"].dtype == jnp.bfloat16
    assert float(state2["master"]["w"][0, 0]) != 1.0


# ---------------------------------------------------------------------------
# gradient compression (semantics on a trivial mesh; the 512-device lowering
# is exercised by the dry-run)
# ---------------------------------------------------------------------------
def test_compress_sync_error_feedback_converges():
    from repro.optim.grad_compress import compress_sync_local
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    g = jnp.asarray(np.random.RandomState(0).randn(16, 16).astype(np.float32))
    ef = jnp.zeros_like(g)
    total_synced = jnp.zeros_like(g)

    def one(g, ef, i):
        def body(g, ef):
            return compress_sync_local(g, ef, axes=("data",), fmt="fp8",
                                       key=jax.random.key(i), n_replicas=1)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),) * 2,
            out_specs=(jax.sharding.PartitionSpec(),) * 2,
            axis_names={"data"}, check_vma=False))(g, ef)

    # with a CONSTANT gradient, error feedback must make the cumulative
    # synced sum converge to the cumulative true sum
    for i in range(20):
        s, ef = one(g, ef, i)
        total_synced = total_synced + s
    err = float(jnp.max(jnp.abs(total_synced - 20 * g)))
    # EF bounds the cumulative error by one quantization step (fp8-scaled)
    assert err < float(jnp.max(jnp.abs(g))) * 0.25, err


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_bf16(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": (jnp.float32(3.5), jnp.arange(4, dtype=jnp.int32)),
            "k": jnp.zeros((2,), jnp.float16)}
    save_pytree(str(tmp_path / "c"), tree, {"step": 7})
    got, extra = restore_pytree(str(tmp_path / "c"), tree)
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_manager_keep_n_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.ones((2,))}
    for s in (10, 20, 30):
        mgr.save(s, tree, sync=True)
    assert mgr.latest_step() == 30
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [20, 30]
    step, got, extra = mgr.restore_latest(tree)
    assert step == 30 and extra["step"] == 30


def test_checkpoint_atomic_no_partial_state(tmp_path):
    """A tmp dir left by a 'crashed' save must not shadow the real one."""
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.ones((2,))}
    mgr.save(5, tree, sync=True)
    os.makedirs(str(tmp_path / "step_00000009.tmp"))  # simulated crash debris
    assert mgr.latest_step() == 5


# ---------------------------------------------------------------------------
# train loop end-to-end + fault tolerance
# ---------------------------------------------------------------------------
def _mk_loop(tmp_path, fail_at=(), total=24):
    model = build_model("fpnew-case-study", policy="tp_bf16", reduced=True)
    opt = OptConfig(lr=3e-3, warmup_steps=5, total_steps=total,
                    weight_decay=0.0)
    data = DataConfig(vocab=model.cfg.vocab, seq_len=64, global_batch=8,
                      noise=0.0)
    lc = LoopConfig(total_steps=total, log_every=0, ckpt_every=8,
                    ckpt_dir=str(tmp_path / "ckpt"))
    return TrainLoop(model, opt, data, lc,
                     failure_plan=FailurePlan(fail_at=fail_at)
                     if fail_at else None)


def test_loop_loss_decreases(tmp_path):
    loop = _mk_loop(tmp_path, total=30)
    log = loop.run()
    first = np.mean([m["loss"] for m in log[:5]])
    last = np.mean([m["loss"] for m in log[-5:]])
    assert last < first - 0.5, (first, last)


def test_loop_restart_after_failure_resumes_not_restarts(tmp_path):
    plan = FailurePlan(fail_at=(13,))

    def make():
        loop = _mk_loop(tmp_path, total=24)
        loop.failure_plan = plan
        return loop

    loop, restarts = run_with_restarts(make, max_restarts=2)
    assert restarts == 1
    assert loop.step == 24
    # resumed from the step-8 checkpoint, not from scratch
    assert loop.metrics_log[0]["step"] == 8


def test_restart_reproduces_uninterrupted_run(tmp_path):
    """Fault tolerance must be *exact*: crash+restore = never-crashed."""
    a = _mk_loop(tmp_path / "a", total=16)
    a.run()
    plan = FailurePlan(fail_at=(12,))

    def make():
        loop = _mk_loop(tmp_path / "b", total=16)
        loop.failure_plan = plan
        return loop

    b, restarts = run_with_restarts(make, max_restarts=1)
    assert restarts == 1
    la = jax.tree.leaves(a.params)
    lb = jax.tree.leaves(b.params)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_straggler_monitor():
    mon = StragglerMonitor(alpha=0.5, threshold=2.0, warmup=3)
    for i in range(8):
        assert not mon.record(i, 1.0)
    assert mon.record(8, 5.0)           # 5x the EWMA -> straggler
    assert mon.flagged[0][0] == 8
    assert not mon.record(9, 1.0)       # baseline not poisoned by outlier


def test_checkpoint_mesh_elastic_restore(tmp_path):
    """A checkpoint written from unsharded state must restore under a
    different (mesh) sharding layout — the pod-loss recovery path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.ones((8,), jnp.bfloat16)}
    save_pytree(str(tmp_path / "c"), tree, {"step": 3})
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shardings = {"w": NamedSharding(mesh, P("data", "model")),
                 "b": NamedSharding(mesh, P(None))}
    got, extra = restore_pytree(str(tmp_path / "c"), tree, shardings)
    assert extra["step"] == 3
    assert got["w"].sharding.spec == P("data", "model")
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
