"""Public jit'd wrappers around the Pallas kernels.

These handle shape padding/alignment, policy plumbing, and head flattening
so the model code can call them like ordinary jnp ops.  Every wrapper takes
``interpret=None``, resolved from the platform (``resolve_interpret``): the
Pallas interpreter on CPU, compiled Mosaic kernels on a TPU.  Passing a bool
pins the mode (tests, autotuning).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.formats import get_format
from ..core.ops import storage_dtype
from ..core.policy import PrecisionPolicy, get_policy
from . import autotune
from .tp_matmul import tp_matmul_pallas, DEFAULT_BLOCK
from .tp_quant import tp_quantize_pallas, cast_and_pack_pallas
from .flash_attention import flash_attention_pallas
from .decode_attention import decode_attention_pallas
from .dotp_ex import dotp_ex_pallas
from .grouped_ffn import grouped_ffn_pallas


def _pad_to(x, mults, axes):
    pads = [(0, 0)] * x.ndim
    padded = False
    for ax, m in zip(axes, mults):
        r = (-x.shape[ax]) % m
        if r:
            pads[ax] = (0, r)
            padded = True
    return (jnp.pad(x, pads), True) if padded else (x, False)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret on CPU, compiled kernels elsewhere."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def tp_matmul(a, b, *, policy=None, out_fmt=None, block=None,
              interpret: Optional[bool] = None):
    """Policy-aware Pallas matmul: a [.., M, K] @ b [K, N]."""
    policy = get_policy(policy) if policy is not None else get_policy("tp_bf16")
    mp = policy.matmul
    out = get_format(out_fmt) if out_fmt is not None else mp.resolved_out()
    lead = a.shape[:-2]
    a2 = a.reshape(-1, a.shape[-1]) if lead else a
    m, k = a2.shape
    _, n = b.shape
    if block is None:  # memoized autotuner winner, else static heuristic
        block = autotune.best_block("matmul", (m, k, n), a.dtype)
    bm, bk, bn = block
    bm, bk, bn = (max(8, min(bm, m)), max(128, bk), max(128, bn))
    a2, _ = _pad_to(a2, (bm, bk), (0, 1))
    b2, _ = _pad_to(b, (bk, bn), (0, 1))

    if policy.mode == "native":
        a2 = a2.astype(mp.src_fmt.native_dtype)
        b2 = b2.astype(mp.src_fmt.native_dtype)
        qname = None
        out_dtype = out.native_dtype
    else:
        qname = mp.src_fmt.name
        out_dtype = jnp.float32
    r = tp_matmul_pallas(a2, b2, block=(bm, bk, bn), out_dtype=out_dtype,
                         quant_fmt_name=qname,
                         interpret=resolve_interpret(interpret))
    r = r[:m, :n]
    return r.reshape(*lead, a.shape[-2], n) if lead else r


def tp_quantize(x, *, fmt, stochastic: bool = False, key=None,
                out_dtype=None, interpret: Optional[bool] = None):
    """Pallas-fused quantization of a 2D array (CONV block)."""
    fmt = get_format(fmt)
    rows, cols = x.shape
    x2, _ = _pad_to(x, (256, 128), (0, 1))
    rbits = None
    if stochastic:
        assert key is not None
        rbits = jax.random.bits(key, x2.shape, jnp.uint32)
    r = tp_quantize_pallas(x2, rbits, fmt_name=fmt.name, stochastic=stochastic,
                           out_dtype=out_dtype or jnp.float32,
                           interpret=resolve_interpret(interpret))
    return r[:rows, :cols]


def cast_and_pack(a, b, *, fmt, stochastic: bool = False, key=None,
                  interpret: Optional[bool] = None):
    fmt = get_format(fmt)
    rows, cols = a.shape
    a2, _ = _pad_to(a, (256, 128), (0, 1))
    b2, _ = _pad_to(b, (256, 128), (0, 1))
    rbits = None
    if stochastic:
        assert key is not None
        rbits = jax.random.bits(key, a2.shape, jnp.uint32)
    r = cast_and_pack_pallas(a2, b2, rbits, fmt_name=fmt.name,
                             stochastic=stochastic,
                             interpret=resolve_interpret(interpret))
    return r[:rows, :2 * cols]


def expand_kv_lens(kv_len, batch: int, heads: int, default):
    """Normalize a scalar-or-vector sequence length to one int32 entry per
    flattened head row ([batch * heads]) — the SMEM layout both attention
    kernels consume.  A scalar (python int, 0-d, or traced) is shared by
    every row; a [batch] vector is a ragged batch's per-sequence lengths,
    repeated across that sequence's heads.  ``None`` means ``default``."""
    kvl = jnp.reshape(jnp.asarray(default if kv_len is None else kv_len,
                                  jnp.int32), (-1,))
    if kvl.shape[0] == 1:
        return jnp.broadcast_to(kvl, (batch * heads,))
    assert kvl.shape[0] == batch, (kvl.shape, batch)
    return jnp.repeat(kvl, heads)


def expand_block_table(table, heads: int):
    """Expand a per-sequence page table [B, max_pages] to flat per-head
    page ids [B * heads, max_pages] — the table twin of ``expand_kv_lens``.
    The model-level pool [n_pages, Hkv, page, D] reshapes (zero-copy) to
    the kernels' flat pool [n_pages * Hkv, page, D], where page ``p`` of
    head ``hk`` sits at flat slot ``p * Hkv + hk``."""
    b, mp = table.shape
    flat = (jnp.asarray(table, jnp.int32)[:, None, :] * heads
            + jnp.arange(heads, dtype=jnp.int32)[None, :, None])
    return flat.reshape(b * heads, mp)


def resolve_backend(backend: str) -> str:
    """Shared decode/prefill attention-backend resolution.

    ``"auto"`` picks the Pallas kernels only off-CPU: on CPU the kernels run
    in interpret mode, which is ~20x slower than the dense jnp path on the
    serving hot loop (BENCH_serve.json, gemma2-9b: ``scan_pallas_kv8_tok_s``
    716 vs ``scan_tok_s`` 14043) — ``auto`` must never silently interpret
    there.  Explicit ``"pallas"`` is honored anywhere (tests/benchmarks).
    """
    if backend == "auto":
        return "dense" if jax.default_backend() == "cpu" else "pallas"
    if backend not in ("dense", "pallas"):
        raise ValueError(f"backend must be dense|pallas|auto, got {backend!r}")
    return backend


def _reduce_flag_cells(cells, b: int, h: int):
    """Reduce a kernel's per-(head-row, cell) flag counters [B*H, n, 4] to
    per-SEQUENCE counts [B, 4] (summed over cells and heads).  In-kernel
    liveness masking already zeroed dead/padded slots, so this is a plain
    sum."""
    return jnp.sum(cells.reshape(b, h, -1, cells.shape[-1]),
                   axis=(1, 2)).astype(jnp.int32)


def flash_attention(q, k, v, *, kv_len=None, policy=None,
                    block_table=None,
                    scale: Optional[float] = None,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    bq: Optional[int] = None, bk: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    return_flags: bool = False):
    """q [B, H, S, D], k/v [B, Hkv, Skv, Dk/Dv] -> [B, H, S, Dv] (f32).

    The prefill/train attention entry point (behind ``cfg.prefill_backend``):
    heads are flattened, ``(bq, bk)`` comes from the autotuner unless pinned,
    and the kernel runs the pruned block schedule — causal future blocks and
    blocks left of a sliding window are never visited.  ``kv_len`` is a
    dynamic kernel input (padding/ragged masking without retrace): a scalar
    shared by the batch, or a per-sequence [B] vector for ragged batches,
    where each sequence's KV walk then early-outs at its own length inside
    the kernel (work proportional to the row's length, not the batch max);
    ``q_offset`` shifts query positions (prefill at a nonzero cache write
    index).  V may have a different head dim than Q/K (MLA expanded form).

    Paged cache (``block_table`` [B, max_pages] int32, traced): k/v are
    the shared page pools [n_pages, Hkv, page, D(v)] of
    ``models.paged.PagedKVCache`` — continued/chunked prefill attending
    against an already-paged cache.  As in ``decode_attention`` the pool
    reshapes zero-copy to the kernels' flat layout, the table expands per
    head, and ``bk`` is pinned to the page size (autotuned ``bq`` still
    applies).

    ``interpret=None`` auto-resolves: interpret on CPU, compiled on real
    accelerators — same hot-path contract as ``decode_attention``.

    ``return_flags=True`` additionally returns per-SEQUENCE int32 [B, 4]
    IEEE flag counts (OF, UF, NX, NV summed over heads and scheduled
    steps; per-visit semantics — docs/KERNELS.md) from the kernel's
    ``debug_flags`` counters.
    """
    interpret = resolve_interpret(interpret)
    policy = get_policy(policy) if policy is not None else get_policy("tp_bf16")
    mp = policy.matmul
    if policy.mode == "native":
        src_dt, src_fmt_name = mp.src_fmt.native_dtype, None
    else:
        # f32 containers: RNE-snap operands onto the src grid in-kernel
        src_dt = jnp.float32
        src_fmt_name = mp.src_fmt.name if mp.src_fmt.name != "fp32" else None
    b, h, sq, d = q.shape
    if block_table is not None:
        n_pages, hkv, page, _ = k.shape
        skv = block_table.shape[1] * page
        dv = v.shape[-1]
    else:
        _, hkv, skv, _ = k.shape
        dv = v.shape[-1]
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5
    if bq is None or bk is None:
        tq, tk = autotune.best_block("attn", (sq, skv, d), q.dtype)
        bq, bk = (bq or tq), (bk or tk)
    qf = q.reshape(b * h, sq, d)
    bq_ = min(bq, max(8, sq))
    qf, _ = _pad_to(qf, (bq_,), (1,))
    if block_table is not None:
        o = flash_attention_pallas(
            qf, k.reshape(n_pages * hkv, page, d),
            v.reshape(n_pages * hkv, page, dv),
            expand_kv_lens(kv_len, b, h, skv),
            expand_block_table(block_table, hkv), group=group,
            bq=bq_, bk=page, scale=scale, causal=causal, window=window,
            softcap=softcap, q_offset=q_offset, src_fmt_name=src_fmt_name,
            src_dtype=src_dt, out_dtype=jnp.float32, interpret=interpret,
            debug_flags=return_flags)
        if return_flags:
            o, fl = o
            return (o[:, :sq].reshape(b, h, sq, dv),
                    _reduce_flag_cells(fl, b, h))
        return o[:, :sq].reshape(b, h, sq, dv)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, dv)
    bk_ = min(bk, max(128, skv))
    kf, _ = _pad_to(kf, (bk_,), (1,))
    vf, _ = _pad_to(vf, (bk_,), (1,))
    o = flash_attention_pallas(
        qf, kf, vf, expand_kv_lens(kv_len, b, h, skv), group=group,
        bq=bq_, bk=bk_, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, src_fmt_name=src_fmt_name,
        src_dtype=src_dt, out_dtype=jnp.float32, interpret=interpret,
        debug_flags=return_flags)
    if return_flags:
        o, fl = o
        return o[:, :sq].reshape(b, h, sq, dv), _reduce_flag_cells(fl, b, h)
    return o[:, :sq].reshape(b, h, sq, dv)


def decode_attention(q, k, v, *, kv_len, policy=None,
                     block_table=None,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     bk: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     return_flags: bool = False):
    """Fused single-query decode attention over the (quantized) KV cache.

    q [B, H, 1, D]; k/v [B, Hkv, Smax, D] *in their storage dtype* (native
    narrow dtype, or f32 container on the ``policy.kv_fmt`` grid);
    ``kv_len`` the live cache length: a python int or traced scalar shared
    by the batch, or a per-sequence [B] vector (ragged batches — each row's
    KV-block loop early-exits at its own length in-kernel).  Either way it
    is a dynamic kernel input, so per-step calls under ``lax.scan`` never
    retrace.  Returns [B, H, 1, D] f32.

    Paged cache (``block_table`` [B, max_pages] int32, traced): k/v are
    instead the shared page pools [n_pages, Hkv, page, D] of
    ``models.paged.PagedKVCache``.  The pool reshapes zero-copy to the
    kernel's flat [n_pages * Hkv, page, D] layout, the table expands to
    flat per-head page ids (``expand_block_table``), and the kernel's
    BlockSpec index maps dereference them — no gather ever materializes
    the contiguous view.  The kernel block size is pinned to the page size
    (the page IS the block), so the autotuned ``bk`` is bypassed; choose
    ``cfg.page_size`` accordingly (>= 128 for TPU lane alignment).

    ``interpret=None`` auto-resolves: interpret on CPU, compiled on real
    accelerators — this wrapper sits on the serving hot path (behind
    ``cfg.decode_backend``), so it must never run the interpreter on TPU.

    ``return_flags=True`` additionally returns per-SEQUENCE int32 [B, 4]
    IEEE flag counts (OF, UF, NX, NV summed over heads and KV blocks;
    each live K/V element once, Q once per head row — docs/KERNELS.md)
    from the kernel's ``debug_flags`` counters.
    """
    interpret = resolve_interpret(interpret)
    policy = get_policy(policy) if policy is not None else get_policy("tp_bf16")
    mp = policy.matmul
    if policy.mode == "native":
        # cache already carries the narrow dtype — widening is exact
        src_dt, kv_fmt_name, q_fmt_name = mp.src_fmt.native_dtype, None, None
    else:
        # f32 containers: snap q / KV onto their grids inside the kernel
        src_dt = jnp.float32
        kv_fmt_name = policy.kv_fmt.name if policy.kv_fmt is not None else None
        q_fmt_name = mp.src_fmt.name if mp.src_fmt.name != "fp32" else None
    b, h, sq, d = q.shape
    if block_table is not None:
        n_pages, hkv, page, _ = k.shape
        smax = block_table.shape[1] * page
    else:
        _, hkv, smax, _ = k.shape
    assert sq == 1, q.shape
    group = h // hkv
    scale = scale if scale is not None else d ** -0.5

    qf = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    g_pad = max(8, group)                    # sublane-align the query strip
    if g_pad != group:
        qf = jnp.pad(qf, ((0, 0), (0, g_pad - group), (0, 0)))
    kvl = expand_kv_lens(kv_len, b, hkv, smax).reshape(b * hkv, 1)
    if block_table is not None:
        kf = k.reshape(n_pages * hkv, page, d)
        vf = v.reshape(n_pages * hkv, page, d)
        btf = expand_block_table(block_table, hkv)
        o = decode_attention_pallas(
            qf, kf, vf, kvl, btf, bk=page, scale=scale, window=window,
            softcap=softcap, kv_fmt_name=kv_fmt_name, q_fmt_name=q_fmt_name,
            src_dtype=src_dt, out_dtype=jnp.float32, interpret=interpret,
            debug_flags=return_flags)
        if return_flags:
            o, fl = o
            return (o[:, :group].reshape(b, hkv, group, d
                                         ).reshape(b, h, 1, d),
                    _reduce_flag_cells(fl, b, hkv))
        return o[:, :group].reshape(b, hkv, group, d).reshape(b, h, 1, d)
    kf = k.reshape(b * hkv, smax, d)
    vf = v.reshape(b * hkv, smax, d)
    if bk is None:
        bk = autotune.best_block("decode_attn", (g_pad, smax, d), src_dt)[0]
    bk = min(bk, max(128, smax))
    kf, _ = _pad_to(kf, (bk,), (1,))
    vf, _ = _pad_to(vf, (bk,), (1,))
    o = decode_attention_pallas(
        qf, kf, vf, kvl, bk=bk, scale=scale, window=window, softcap=softcap,
        kv_fmt_name=kv_fmt_name, q_fmt_name=q_fmt_name, src_dtype=src_dt,
        out_dtype=jnp.float32, interpret=interpret, debug_flags=return_flags)
    if return_flags:
        o, fl = o
        return (o[:, :group].reshape(b, hkv, group, d).reshape(b, h, 1, d),
                _reduce_flag_cells(fl, b, hkv))
    return o[:, :group].reshape(b, hkv, group, d).reshape(b, h, 1, d)


def grouped_ffn(x, expert_ids, w_gate, w_up, w_down, layer, *, policy,
                interpret: Optional[bool] = None,
                debug_fetches: bool = False):
    """Grouped expert SwiGLU over expert-sorted rows (native-mode policy):
    x [N, D], expert_ids [N] non-decreasing, stacked weights
    [L, E, D, F] / [L, E, F, D], ``layer`` the traced index into them.
    Dtypes follow ``core.ops.tp_einsum``/``tp_elementwise`` in native mode.
    Returns ``(y [N, D], n_active)`` (``grouped_ffn_pallas``)."""
    policy = get_policy(policy)
    assert policy.mode == "native", policy.mode
    mp = policy.matmul
    out = mp.resolved_out()
    acc = storage_dtype(mp.acc_fmt, "native")
    if policy.narrow_partials and out.width < mp.acc_fmt.width:
        acc = out.native_dtype
    return grouped_ffn_pallas(
        x, expert_ids, w_gate, w_up, w_down, layer,
        src_dtype=mp.src_fmt.native_dtype,
        acc_dtype=acc, out_dtype=out.native_dtype,
        elem_dtype=storage_dtype(policy.elem_fmt, "native"),
        interpret=resolve_interpret(interpret), debug_fetches=debug_fetches)


def dotp_ex(a, b, *, policy=None, interpret: Optional[bool] = None):
    """Expanding dot product of two 1D streams (paper Fig 11e)."""
    policy = get_policy(policy) if policy is not None else get_policy("tp_fp16")
    src_dt = (policy.matmul.src_fmt.native_dtype
              if policy.mode == "native" else jnp.float32)
    n = a.shape[0]
    c = 128
    rows = -(-n // c)
    pad = rows * c - n
    a2 = jnp.pad(a, (0, pad)).reshape(rows, c)
    b2 = jnp.pad(b, (0, pad)).reshape(rows, c)
    br = min(256, rows)
    a2, _ = _pad_to(a2, (br,), (0,))
    b2, _ = _pad_to(b2, (br,), (0,))
    lanes = dotp_ex_pallas(a2, b2, block_rows=br, src_dtype=src_dt,
                           interpret=resolve_interpret(interpret))
    return jnp.sum(lanes)
