"""Mesh-sharded serving: tensor-parallel attention parity + satellites.

The 8-device tests need ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(scripts/ci.sh runs this file under that flag as its own gate) and skip
cleanly under the plain tier-1 run, where jax sees one CPU device.  The
parity claims they pin:

  * head-sharded attention (dense + Pallas, prefill + decode, contiguous
    + paged) is BIT-identical per head to the single-device path — head
    slices are independent, concat is data movement;
  * the row-parallel output projection psums per-shard partials in fp32
    and snaps the policy format ONCE after the reduce, so full outputs
    are allclose at fp32 tolerance (and bitwise under tp_bf16, whose
    output snap absorbs the fp32 reduction-order noise);
  * the continuous engine and its data-parallel replication emit
    token-identical streams with and without a mesh.

The 1-device satellite tests (mesh axis types, divisibility fallback, per-replica allocator isolation, paged cache specs, queue
partitioning) always run, and so does
``test_engine_tp_token_parity_in_an_eight_device_child``, which runs the
engine's token-parity test in a child process that sees eight devices.
"""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as meshmod
from repro.launch.engine import ReplicatedEngine, Request
from repro.models.attention import KVCache, gqa_attention, gqa_params
from repro.models.paged import (PageAllocator, PagedKVCache, aggregate_stats,
                                init_paged_kv_cache)
from repro.models.sharding import cache_specs, param_specs
from repro.models.transformer import Caches

need8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

B, S, DM, H, HKV, HD = 2, 16, 32, 8, 8, 16
PAGE, MAXLEN = 8, 32


def tp_mesh(tp=8):
    return meshmod.replica_meshes(meshmod.make_serving_mesh(1, tp))[0]


def setup():
    params = gqa_params(jax.random.key(0), DM, H, HKV, HD, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (B, S, DM), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    return params, x, pos


def attend(mesh, x, params, pos, *, policy="tp_bf16", return_attend=True,
           **kw):
    return gqa_attention(x, params, policy, n_heads=H, n_kv_heads=HKV,
                         head_dim=HD, positions=pos, mesh=mesh,
                         return_attend=return_attend, **kw)


# ---------------------------------------------------------------------------
# per-head bit-exactness: every attend route, mesh vs single-device
# ---------------------------------------------------------------------------
@need8
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_contiguous_prefill_attend_bitexact(backend):
    params, x, pos = setup()
    kw = dict(prefill_backend=backend)
    a, _ = jax.jit(lambda m=None: attend(m, x, params, pos, **kw))()
    b, _ = jax.jit(lambda: attend(tp_mesh(), x, params, pos, **kw))()
    assert np.array_equal(np.asarray(a), np.asarray(b))


@need8
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_contiguous_decode_attend_bitexact(backend):
    params, x, pos = setup()
    zeros = jnp.zeros((B, HKV, MAXLEN, HD), jnp.float32)
    _, cache = attend(None, x, params, pos, cache=KVCache(zeros, zeros),
                      cache_pos=0)
    x1 = jax.random.normal(jax.random.key(2), (B, 1, DM), jnp.float32)
    p1 = jnp.full((B, 1, 1), S, jnp.int32)
    kw = dict(cache=cache, cache_pos=jnp.full((B,), S, jnp.int32),
              kv_len=jnp.full((B,), S + 1, jnp.int32),
              decode_backend=backend)
    a, _ = jax.jit(lambda: attend(None, x1, params, p1, **kw))()
    b, _ = jax.jit(lambda: attend(tp_mesh(), x1, params, p1, **kw))()
    assert np.array_equal(np.asarray(a), np.asarray(b))


@need8
@pytest.mark.parametrize("backend", ["dense", "pallas"])
@pytest.mark.parametrize("q_offset", [0, 4])
def test_paged_prefill_attend_bitexact(backend, q_offset):
    params, x, pos = setup()
    kw = dict(cache=init_paged_kv_cache(B, HKV, MAXLEN, PAGE, HD,
                                        jnp.float32),
              cache_pos=q_offset,
              kv_len=jnp.full((B,), q_offset + S, jnp.int32),
              prefill_backend=backend)
    a, _ = jax.jit(lambda: attend(None, x, params, pos + q_offset, **kw))()
    b, _ = jax.jit(lambda: attend(tp_mesh(), x, params, pos + q_offset,
                                  **kw))()
    assert np.array_equal(np.asarray(a), np.asarray(b))


@need8
@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_paged_decode_attend_bitexact(backend):
    params, x, pos = setup()
    _, cache = attend(None, x, params, pos,
                      cache=init_paged_kv_cache(B, HKV, MAXLEN, PAGE, HD,
                                                jnp.float32),
                      cache_pos=0, kv_len=jnp.full((B,), S, jnp.int32))
    x1 = jax.random.normal(jax.random.key(2), (B, 1, DM), jnp.float32)
    p1 = jnp.full((B, 1, 1), S, jnp.int32)
    kw = dict(cache=cache, cache_pos=jnp.full((B,), S, jnp.int32),
              kv_len=jnp.full((B,), S + 1, jnp.int32),
              decode_backend=backend)
    a, _ = jax.jit(lambda: attend(None, x1, params, p1, **kw))()
    b, _ = jax.jit(lambda: attend(tp_mesh(), x1, params, p1, **kw))()
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# projected outputs: psum boundary
# ---------------------------------------------------------------------------
@need8
def test_projection_bitexact_under_bf16_snap():
    # tp_bf16 snaps the psum'd fp32 partial sums to bf16 AFTER the reduce;
    # the snap absorbs the reduction-order noise, so full outputs are
    # bitwise here (the fp32 policy below shows the underlying tolerance)
    params, x, pos = setup()
    a, _ = jax.jit(lambda: attend(None, x, params, pos,
                                  return_attend=False))()
    b, _ = jax.jit(lambda: attend(tp_mesh(), x, params, pos,
                                  return_attend=False))()
    assert np.array_equal(np.asarray(a), np.asarray(b))


@need8
def test_projection_allclose_fp32():
    params, x, pos = setup()
    a, _ = jax.jit(lambda: attend(None, x, params, pos, policy="fp32",
                                  return_attend=False))()
    b, _ = jax.jit(lambda: attend(tp_mesh(), x, params, pos, policy="fp32",
                                  return_attend=False))()
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=0, atol=1e-5)


@need8
def test_full_model_logits_allclose():
    from conftest import cached_model
    model, params = cached_model("gemma2-9b")
    toks = jax.random.randint(jax.random.key(1), (2, 12), 0,
                              model.cfg.vocab)
    lg0, _ = jax.jit(lambda p, t: model.prefill(p, t, max_len=24))(
        params, toks)
    mesh = tp_mesh(2)        # reduced arch: 4 heads / 2 kv heads
    lg1, _ = jax.jit(lambda p, t: model.prefill(p, t, max_len=24,
                                                mesh=mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# engine: tensor-parallel + data-parallel token parity
# ---------------------------------------------------------------------------
def _engine_fixture():
    from conftest import cached_model
    from repro.launch.engine import ContinuousEngine, synthetic_trace
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    reqs = synthetic_trace(6, 3, 16, 16, model.cfg.vocab)
    max_len = max(r.prompt_len + r.max_new for r in reqs)
    mk = lambda mesh: ContinuousEngine(model, params, slots=3,
                                       max_len=max_len, chunk=8, mesh=mesh)
    return mk, reqs


@need8
def test_engine_tp_token_parity():
    mk, reqs = _engine_fixture()
    base, _ = mk(None).run(reqs)
    tp, _ = mk(tp_mesh(2)).run(reqs)
    assert all(a.tokens == b.tokens for a, b in zip(base, tp))


def test_engine_tp_token_parity_in_an_eight_device_child():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         f"{__file__}::test_engine_tp_token_parity"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "1 passed" in r.stdout, r.stdout[-3000:]


@need8
def test_replicated_engine_token_parity_and_stats():
    from conftest import cached_model
    mk, reqs = _engine_fixture()
    base, _ = mk(None).run(reqs)
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    max_len = max(r.prompt_len + r.max_new for r in reqs)
    rep = ReplicatedEngine(model, params,
                           mesh=meshmod.make_serving_mesh(2, 2),
                           slots=3, max_len=max_len, chunk=8)
    fin, st = rep.run(reqs)
    assert all(a.tokens == b.tokens for a, b in zip(base, fin))
    assert [f.rid for f in fin] == [r.rid for r in reqs]
    assert st["replicas_n"] == 2 and len(st["replicas"]) == 2
    assert st["pool"]["n_pages"] == sum(
        s["n_pages"] for s in st["pool"]["replicas"])
    assert st["decode_rounds"] == sum(
        s["decode_rounds"] for s in st["replicas"])


@need8
def test_moe_ep_on_model_only_mesh():
    # regression: a serving replica's ("model",) sub-mesh has no "data"
    # axis — the MoE EP specs must only name axes the mesh actually has
    from repro.core.policy import PRESETS
    from repro.models.layers import set_batch_axes
    from repro.models.moe import MoEConfig, moe_block, moe_params
    set_batch_axes(("data",))
    try:
        cfg = MoEConfig(n_experts=8, top_k=2, d_expert=16, n_shared=1)
        pol = PRESETS["fp32"]
        params = moe_params(jax.random.key(0), 32, cfg, jnp.float32)
        x = jax.random.normal(jax.random.key(1), (2, 16, 32))
        y0, aux0 = moe_block(x, params, cfg, pol, mesh=None)
        y1, aux1 = jax.jit(lambda x, p: moe_block(
            x, p, cfg, pol, mesh=tp_mesh(2)))(x, params)
    finally:
        set_batch_axes(())
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux0), float(aux1), rtol=1e-5)


# ---------------------------------------------------------------------------
# satellite 1: meshes are built with Auto axes
# ---------------------------------------------------------------------------
def test_production_mesh_axis_type_probe(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax, "make_mesh",
                        lambda shape, axes, **kw: seen.update(kw) or "m")
    assert meshmod.make_production_mesh() == "m"
    assert seen == {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
    assert meshmod.make_serving_mesh(1, 1) == "m"
    assert seen == {"axis_types": (jax.sharding.AxisType.Auto,) * 2}


def test_serving_mesh_validation():
    with pytest.raises(ValueError, match=">= 1"):
        meshmod.make_serving_mesh(0, 1)
    m = meshmod.make_serving_mesh(1, 1)
    subs = meshmod.replica_meshes(m)
    assert len(subs) == 1 and subs[0].axis_names == ("model",)
    with pytest.raises(ValueError, match="serving mesh"):
        meshmod.replica_meshes(
            jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("pod",)))


# ---------------------------------------------------------------------------
# satellite 2: divisibility fallback warns and replicates
# ---------------------------------------------------------------------------
def test_param_divisibility_fallback_warns():
    params = {"wq": jax.ShapeDtypeStruct((32, 13), jnp.float32),
              "g": jax.ShapeDtypeStruct((32,), jnp.float32)}
    with pytest.warns(UserWarning,
                      match=r"'wq' \(32, 13\).*16-way 'model'.*replicated"):
        specs = param_specs(params, model_size=16)
    assert specs["wq"] == P()            # pinned: fallback is replication
    assert specs["g"] == P()             # 'rep' role: no warning expected


def test_param_specs_divisible_no_warning(recwarn):
    params = {"wq": jax.ShapeDtypeStruct((32, 64), jnp.float32)}
    specs = param_specs(params, model_size=16)
    assert specs["wq"] == P(None, "model")
    assert not [w for w in recwarn.list
                if "replicated instead" in str(w.message)]


def _fake_mesh(model=2, data=1):
    return types.SimpleNamespace(shape={"model": model, "data": data},
                                 axis_names=("data", "model"))


def test_cache_specs_paged_leaves():
    from repro.configs.base import ModelConfig
    paged = PagedKVCache(
        jax.ShapeDtypeStruct((12, 4, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((12, 4, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((3, 4), jnp.int32))
    caches = Caches(prefix=(paged,), pattern=None, suffix=None)
    specs = cache_specs(None, caches, batch=3, mesh=_fake_mesh(model=2),
                        batch_axes=())
    got = specs.prefix[0]
    assert got.k_pool == P(None, "model", None, None)
    assert got.v_pool == P(None, "model", None, None)
    assert got.block_table == P(None, None)
    # indivisible head count: pool replicates, table spec unchanged
    bad = PagedKVCache(
        jax.ShapeDtypeStruct((12, 3, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((12, 3, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((3, 4), jnp.int32))
    specs = cache_specs(None, Caches(prefix=(bad,), pattern=None,
                                     suffix=None),
                        batch=3, mesh=_fake_mesh(model=2), batch_axes=())
    assert specs.prefix[0].k_pool == P(None, None, None, None)


# ---------------------------------------------------------------------------
# satellite 4: per-replica allocator isolation + aggregation
# ---------------------------------------------------------------------------
def test_allocator_isolation():
    a, b = PageAllocator(8), PageAllocator(8)
    got_a = a.alloc(8)                   # drain A completely
    assert a.try_alloc(1) is None
    assert b.n_free == 8                 # B untouched: disjoint pools
    got_b = b.alloc(3)
    a.free(got_a[:4])
    assert b.n_live == 3 and b.n_free == 5   # A's churn invisible to B
    assert a.n_free == 4
    b.free(got_b)
    assert a.peak_live == 8 and b.peak_live == 3


def test_aggregate_stats():
    allocs = [PageAllocator(8), PageAllocator(4)]
    allocs[0].alloc(5)
    allocs[1].alloc(2)
    allocs[1].free(allocs[1].alloc(2))   # push replica-1 peak to 4
    agg = aggregate_stats(allocs)
    assert agg["n_pages"] == 12 and agg["n_live"] == 7
    assert agg["n_free"] == 5
    assert agg["peak_live"] == 5 + 4     # sums of independent pool peaks
    assert [s["n_pages"] for s in agg["replicas"]] == [8, 4]


def test_replicated_partition_round_robin():
    eng = ReplicatedEngine.__new__(ReplicatedEngine)
    eng.engines = [object(), object()]
    reqs = [Request(rid=i, tokens=[1], max_new=1, arrival=a)
            for i, a in ((0, 5), (1, 0), (2, 0), (3, 2))]
    parts = ReplicatedEngine.partition(eng, reqs)
    # (arrival, rid) order = 1, 2, 3, 0 -> round-robin over 2 replicas
    assert [r.rid for r in parts[0]] == [1, 3]
    assert [r.rid for r in parts[1]] == [2, 0]
    for part in parts:                   # per-replica arrival order intact
        assert [r.arrival for r in part] == sorted(r.arrival for r in part)
