"""Compile-only tests: the serving-path Pallas kernels at real widths,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret-mode tests prove the kernels' numerics on the CPU; they cannot
show what Mosaic refuses (unaligned block shapes, VMEM over-use) or that a
program fits the device.  These tests lower and compile each kernel for
one chip of a ``v5e:2x2`` topology and check that the compiled program
really contains the kernel (``tpu_custom_call``).  Nothing runs: there is
no result and no timing here.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports every test file.  The fixture skips where no topology can
be described.  JAX's persistent compilation cache is switched off for the
duration — a program compiled for a described chip is written to the cache
but cannot be read back without one.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.policy import get_policy
from repro.kernels import ops as kops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.attention import kv_store_dtype


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *specs):
    """Compile ``fn`` for the described chip; returns the compiled text
    after checking the kernel made it into the program."""
    compiled = jax.jit(fn).lower(*specs).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    return txt


# (name, H, Hkv, Dh, policy, window, softcap): qwen3-moe-30b-a3b and
# gemma2-9b attention widths (configs/*.py)
DECODE_CASES = [
    ("qwen3-bf16", 32, 4, 128, "tp_bf16", None, None),
    ("qwen3-fp8", 32, 4, 128, "tp_bf16_kv8", None, None),
    ("gemma2-window-softcap", 16, 8, 256, "tp_bf16", 4096, 50.0),
]

SLOTS, PAGE, MAX_PAGES = 4, 128, 9       # 4 serving slots, 1152-token rows


@pytest.mark.parametrize("case", DECODE_CASES, ids=[c[0] for c in DECODE_CASES])
def test_paged_decode_compiles(one_chip, case):
    _, h, hkv, dh, pol, window, cap = case
    policy = get_policy(pol)
    n_pages = SLOTS * MAX_PAGES + 1
    pool_dt = kv_store_dtype(policy)
    fn = lambda q, kp, vp, bt, kvl: kops.decode_attention(
        q, kp, vp, kv_len=kvl, block_table=bt, policy=policy,
        window=window, softcap=cap, interpret=False)
    _compile(fn, _spec(one_chip, (SLOTS, h, 1, dh), jnp.bfloat16),
             _spec(one_chip, (n_pages, hkv, PAGE, dh), pool_dt),
             _spec(one_chip, (n_pages, hkv, PAGE, dh), pool_dt),
             _spec(one_chip, (SLOTS, MAX_PAGES), jnp.int32),
             _spec(one_chip, (SLOTS,), jnp.int32))


def test_paged_flash_prefill_chunk_compiles(one_chip):
    # one 128-token prefill chunk of one slot at offset 256, reading the
    # row's earlier chunks back through the page pool (qwen3 widths)
    n_pages = SLOTS * MAX_PAGES + 1
    fn = lambda q, kp, vp, bt, kvl: kops.flash_attention(
        q, kp, vp, kv_len=kvl, block_table=bt, policy="tp_bf16",
        q_offset=256, interpret=False)
    _compile(fn, _spec(one_chip, (1, 32, 128, 128), jnp.bfloat16),
             _spec(one_chip, (n_pages, 4, PAGE, 128), jnp.bfloat16),
             _spec(one_chip, (n_pages, 4, PAGE, 128), jnp.bfloat16),
             _spec(one_chip, (1, MAX_PAGES), jnp.int32),
             _spec(one_chip, (1,), jnp.int32))


def test_paged_flash_prefill_long_chunk_compiles(one_chip):
    # a wave of 4 slots' 1,024-token chunks at offset 7,168, reading 56
    # pages of 128 back through the pool (internlm2-20b widths: 48/8 heads
    # of 128, 8,192-position rows)
    slots, pages = 4, 64
    n_pages = slots * pages + 1
    fn = lambda q, kp, vp, bt, kvl: kops.flash_attention(
        q, kp, vp, kv_len=kvl, block_table=bt, policy="tp_bf16",
        q_offset=7168, interpret=False)
    _compile(fn, _spec(one_chip, (slots, 48, 1024, 128), jnp.bfloat16),
             _spec(one_chip, (n_pages, 8, PAGE, 128), jnp.bfloat16),
             _spec(one_chip, (n_pages, 8, PAGE, 128), jnp.bfloat16),
             _spec(one_chip, (slots, pages), jnp.int32),
             _spec(one_chip, (slots,), jnp.int32))


def test_contiguous_flash_prefill_compiles(one_chip):
    fn = lambda q, k, v, kvl: kops.flash_attention(
        q, k, v, kv_len=kvl, policy="tp_bf16", interpret=False)
    _compile(fn, _spec(one_chip, (1, 32, 1024, 128), jnp.bfloat16),
             _spec(one_chip, (1, 4, 1024, 128), jnp.bfloat16),
             _spec(one_chip, (1, 4, 1024, 128), jnp.bfloat16),
             _spec(one_chip, (1,), jnp.int32))


@pytest.mark.parametrize("debug", ["visits", "flags"])
def test_decode_debug_outputs_compile(one_chip, debug):
    # paged qwen3 decode with the telemetry outputs on
    bh, nk = SLOTS * 4, MAX_PAGES
    n_pages = (SLOTS * MAX_PAGES + 1) * 4
    fn = lambda q, kp, vp, kvl, bt: decode_attention_pallas(
        q, kp, vp, kvl, bt, bk=PAGE, scale=128 ** -0.5,
        src_dtype=jnp.bfloat16, interpret=False,
        debug_visits=debug == "visits", debug_flags=debug == "flags")
    txt = _compile(fn, _spec(one_chip, (bh, 8, 128), jnp.bfloat16),
                   _spec(one_chip, (n_pages, PAGE, 128), jnp.bfloat16),
                   _spec(one_chip, (n_pages, PAGE, 128), jnp.bfloat16),
                   _spec(one_chip, (bh, 1), jnp.int32),
                   _spec(one_chip, (bh, nk), jnp.int32))
    assert txt


@pytest.mark.parametrize("debug", ["visits", "flags"])
def test_flash_debug_outputs_compile(one_chip, debug):
    fn = lambda q, k, v, kvl: flash_attention_pallas(
        q, k, v, kvl, group=8, bq=128, bk=128, scale=128 ** -0.5,
        src_dtype=jnp.bfloat16, interpret=False,
        debug_visits=debug == "visits", debug_flags=debug == "flags")
    _compile(fn, _spec(one_chip, (32, 512, 128), jnp.bfloat16),
             _spec(one_chip, (4, 512, 128), jnp.bfloat16),
             _spec(one_chip, (4, 512, 128), jnp.bfloat16),
             _spec(one_chip, (32,), jnp.int32))


def test_tp_matmul_compiles(one_chip):
    fn = lambda a, b: kops.tp_matmul(a, b, policy="tp_bf16",
                                     interpret=False)
    _compile(fn, _spec(one_chip, (256, 2048), jnp.bfloat16),
             _spec(one_chip, (2048, 768), jnp.bfloat16))


def test_tp_quantize_fp8_compiles(one_chip):
    fn = lambda x: kops.tp_quantize(x, fmt="fp8", interpret=False)
    _compile(fn, _spec(one_chip, (512, 1024), jnp.float32))


def test_interpret_resolves_from_platform():
    # None follows the platform; an explicit bool pins the mode
    assert kops.resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert kops.resolve_interpret(False) is False
    assert kops.resolve_interpret(True) is True
    assert np.isfinite(float(kops.dotp_ex(jnp.ones(4), jnp.ones(4))))


# the serving programs at qwen3-moe-30b-a3b widths (128 experts of 2048 x
# 768, top-8), 10 layers in the scan: the grouped expert kernel takes the
# stacked [L, E, D, F] weights whole, so no op may copy or slice a tensor
# whose trailing dims are one expert's matrix
_EXPERT_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]*)\]\S* "
                        r"([\w-]+)\(")


def _expert_weight_copies(txt, d, f):
    bad = []
    for line in txt.splitlines():
        m = _EXPERT_OP.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        dims = tuple(int(s) for s in dims.split(",") if s)
        if dims[-2:] in ((d, f), (f, d)) and (
                op in ("copy", "dynamic-slice", "slice")
                or "slice" in name or "copy" in name):
            bad.append(line.strip()[:160])
    return bad


# (program, policy): the bf16 serving programs, and the float32 prefill
# chunk that chip_smoke.py's reference runs over the same bf16 weights
MOE_PROGRAMS = [("decode_burst", "tp_bf16"), ("prefill_chunk", "tp_bf16"),
                ("prefill_chunk", "fp32")]


@pytest.mark.parametrize("program,policy", MOE_PROGRAMS,
                         ids=["-".join(c) for c in MOE_PROGRAMS])
def test_moe_serving_reads_stacked_expert_weights(one_chip, monkeypatch,
                                                  program, policy):
    from repro.models.registry import build_model
    from repro.models.transformer import init_caches
    # trace as on the chip: the platform picks the Pallas kernels and the
    # grouped expert path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model("qwen3-moe-30b-a3b", policy="tp_bf16").with_cfg(
        n_layers=10, paged_kv=True, page_size=PAGE)
    cfg, slots, max_len = model.cfg, 8, 1024
    on_chip = lambda tree: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: model.init(jax.random.key(0))))
    model = dataclasses.replace(model, policy=get_policy(policy))
    caches = on_chip(jax.eval_shape(
        lambda: init_caches(cfg, slots, max_len, model.policy)))
    if program == "decode_burst":
        fn = lambda p, c, pos: model.decode_burst(
            p, pos[:, None], c, pos, pos, pos < 0, pos + 100,
            max_len=max_len, out_width=64, n_max=64, exit_on_finish=0,
            guard=True)[-1]
    else:
        fn = lambda p, c, pos: model.prefill_chunk(
            p, jnp.zeros((1, 128), jnp.int32), c, q_offset=0,
            row=pos[:1], chunk_lens=pos[:1])[0]
    txt = _compile(fn, params, caches, _spec(one_chip, (slots,), jnp.int32))
    assert "grouped_ffn_pallas" in txt
    assert _expert_weight_copies(txt, cfg.d_model, cfg.moe.d_expert) == []
