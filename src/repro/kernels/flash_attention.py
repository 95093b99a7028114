"""Pallas TPU kernel: pruned-grid transprecision flash attention (prefill).

Attention is the framework's dominant non-GEMM compute hot-spot; this kernel
applies FPnew's multi-format FMA contract to both attention contractions:
QK^T and PV multiply in ``src_fmt`` (bf16/fp16/fp8), while the online-softmax
statistics (running max / denominator) and the output accumulator stay in
f32 — the expanding-FMA pattern of paper §II.B.4 at the kernel level.

Energy proportionality at the schedule level (§II.B.4): the grid visits ONLY
the KV blocks a query block can actually see.  ``block_schedule`` computes
the active ``(iq, ik)`` pairs host-side — causal future blocks and blocks
left of a sliding window never appear in the grid at all — and the flattened
schedule is fed to the kernel as scalar-prefetch tables that drive the block
index maps (splash-attention style).  Causal ``sq == skv`` prefill thus runs
~half the dense grid's block visits, and a window layer O(window / skv) of
them.  ``kv_len`` is a *dynamic* kernel input (SMEM scalar-prefetch, like
the decode kernel): distinct prompt lengths reuse one compiled kernel, and
blocks entirely past ``kv_len`` early-out via ``pl.when`` at run time.

Ragged batches: ``kv_len`` generalizes to a per-row *vector* — one int32
SMEM entry per flattened head row (ops.py expands a [B] sequence-length
vector by the head count).  The early-out and the in-block mask both read
``kvl_ref[program_id(0)]``, so each sequence's KV walk stops at its OWN
length: a short row in a ragged batch does work proportional to its own
``kv_len``, not the batch max (``debug_visits`` is per-row, [BH, n_steps],
and proves it).  The length vector is a traced value — differing ragged
batches share one compiled kernel, exactly like the scalar case.

Paged KV (``block_table``): K/V may arrive as shared page pools instead of
per-row contiguous strips — a per-row table in the scalar-prefetch set maps
each logical KV block to a physical page, and ONLY the K/V BlockSpec index
maps change (``(h // g, ki[s], 0)`` becomes ``(bt[h // g, ki[s]], 0, 0)``).
The kernel body is untouched, so paged output is bit-exact against the
contiguous kernel on the same values; tables are traced (page churn and
prefix re-sharing never retrace).  This is the continued-prefill read path
against a paged cache (decoding's twin lives in decode_attention.py).

Features: GQA head mapping, causal masking, sliding-window (local) masking,
attention-logit soft-capping (gemma-2/3), V head dim != QK head dim (MLA
expanded prefill), optional in-kernel RNE operand snap for emulate-mode
policies, per-block VMEM tiling, optional block-visit instrumentation.

Layout: q [BH, Sq, D], k [BKV, Skv, D], v [BKV, Skv, Dv] (heads
pre-flattened by ops.py).  Grid (BH, n_steps) over the pruned schedule;
scratch: acc (bq, Dv) f32, running max m and denominator l as (bq, 128)
replicated lanes (TPU-friendly 2D).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import get_format
from .decode_attention import (N_FLAGS, _flag_counts, _put_debug_row,
                               softcap_scores)
from .quant_common import widen as _widen

NEG_INF = -1e30


def block_schedule(sq: int, skv: int, bq: int, bk: int, *, causal: bool,
                   window: Optional[int], q_offset: int = 0
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The pruned grid: active ``(iq, ik)`` block pairs, host-side.

    Returns int32 arrays ``(qi, ki, first, last)`` of equal length — for
    each grid step, the query-block index, the KV-block index, and flags
    marking the first / last KV block of that query block's run (scratch
    init / output store points).  A KV block is scheduled iff some query row
    in the block can attend to some key in it under the *static* masks:

      causal  — key blocks past the last query row of the block are dropped
                (``ik * bk > q_offset + (iq+1)*bq - 1``),
      window  — key blocks entirely left of the earliest reachable key
                (``q_offset + iq*bq - window + 1``) are dropped.

    The dynamic ``kv_len`` bound cannot shrink the grid (it is a traced
    value) — the kernel ``pl.when``-skips those blocks at run time instead.
    Every query block keeps >= 1 step so its output is always stored.
    """
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    nq, nk = sq // bq, skv // bk
    qi, ki, first, last = [], [], [], []
    for iq in range(nq):
        k_hi = nk - 1
        if causal:
            k_hi = min(k_hi, (q_offset + (iq + 1) * bq - 1) // bk)
        k_lo = 0
        if window is not None:
            k_lo = max(0, (q_offset + iq * bq - window + 1) // bk)
        k_lo = min(k_lo, k_hi)   # degenerate: keep one step for the store
        for ik in range(k_lo, k_hi + 1):
            qi.append(iq)
            ki.append(ik)
            first.append(1 if ik == k_lo else 0)
            last.append(1 if ik == k_hi else 0)
    mk = lambda a: np.asarray(a, np.int32)
    return mk(qi), mk(ki), mk(first), mk(last)


def _attn_kernel(kvl_ref, qi_ref, ki_ref, ff_ref, lf_ref, *args, bq: int,
                 bk: int, paged: bool, scale: float, causal: bool,
                 window: Optional[int], softcap: Optional[float],
                 q_offset: int, src_fmt, src_dtype, out_dtype,
                 debug_visits: bool, debug_flags: bool):
    if paged:
        args = args[1:]            # bt_ref: consumed by the index maps only
    q_ref, k_ref, v_ref, o_ref, *rest = args
    visits_ref = flags_ref = None
    if debug_visits:
        visits_ref, rest = rest[0], rest[1:]
    if debug_flags:
        flags_ref, rest = rest[0], rest[1:]
    acc_ref, m_ref, l_ref = rest
    step = pl.program_id(1)
    iq = qi_ref[step]
    ik = ki_ref[step]
    kvl = kvl_ref[pl.program_id(0)]      # this row's own live length

    @pl.when(ff_ref[step] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # dynamic early-out: the whole KV block lies past the live length.
    # Skipping is exact — a fully-masked block contributes p = 0 and
    # alpha = exp(0) = 1, so the online state would be bit-identical.
    active = ik * bk < kvl

    @pl.when(active)
    def _work():
        q = _widen(q_ref[0], src_fmt, src_dtype)     # (bq, D)
        k = _widen(k_ref[0], src_fmt, src_dtype)     # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap is not None:
            s = softcap_scores(s, softcap)

        q_idx = (q_offset + iq * bq
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        k_idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_idx < kvl
        if causal:
            mask &= q_idx >= k_idx
        if window is not None:
            mask &= (q_idx - k_idx) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                         # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (m_new == NEG_INF): keep exp arg finite
        p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(jnp.where(m_new <= NEG_INF / 2, 0.0, m_prev - m_new))

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = _widen(v_ref[0], src_fmt, src_dtype)
        pv = jax.lax.dot_general(_widen(p, src_fmt, src_dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(lf_ref[step] == 1)
    def _store():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(out_dtype)

    if debug_visits:
        _put_debug_row(visits_ref, step, (active.astype(jnp.int32),))
    if debug_flags:
        # Per-VISIT flag counts (like debug_visits): each scheduled step's
        # K/V tiles are charged to its own (h, step) cell, masked to the
        # row's live length; the Q tile is charged once per query block, at
        # its first scheduled step.  Early-out steps write zeros.  The
        # derived p-snap at the PV input is NOT counted — telemetry tracks
        # stored-data CONV sites (q/k/v), not recomputed probabilities.
        live = (ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                ) < kvl
        kc = _flag_counts(k_ref[0], src_fmt, src_dtype, live)
        vc = _flag_counts(v_ref[0], src_fmt, src_dtype, live)
        qc = _flag_counts(q_ref[0], src_fmt, src_dtype,
                          jnp.ones((1, 1), jnp.bool_))
        first = ff_ref[step] == 1
        _put_debug_row(flags_ref, step, tuple(
            jnp.where(active, a + b + jnp.where(first, c, 0), 0)
            for a, b, c in zip(kc, vc, qc)))


@functools.partial(jax.jit, static_argnames=(
    "group", "bq", "bk", "scale", "causal", "window", "softcap", "q_offset",
    "src_fmt_name", "src_dtype", "out_dtype", "interpret", "debug_visits",
    "debug_flags"))
def flash_attention_pallas(q, k, v, kv_len=None, block_table=None, *,
                           group: int = 1,
                           bq: int = 128, bk: int = 128, scale: float = 1.0,
                           causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           q_offset: int = 0,
                           src_fmt_name: Optional[str] = None,
                           src_dtype=jnp.bfloat16,
                           out_dtype=jnp.float32,
                           interpret: bool = True,
                           debug_visits: bool = False,
                           debug_flags: bool = False):
    """q: [BH, Sq, D]; k: [BKV, Skv, D]; v: [BKV, Skv, Dv]; BH = BKV * group.

    Paged layout (``block_table`` [BKV, nk] int32, a traced value): k/v are
    instead shared page POOLS ([n_pages, bk, D] / [n_pages, bk, Dv]) and kv
    row ``hk``'s logical KV block ``ik`` is physical page
    ``block_table[hk, ik]``.  Only the K/V BlockSpec index maps change
    (``(h // g, ki[s], 0)`` -> ``(bt[h // g, ki[s]], 0, 0)``), so numerics
    are identical to the contiguous layout; the logical KV length is
    ``nk * bk`` (chunked / continued prefill against an already-paged
    cache, e.g. extending a shared prompt prefix).

    Sq % bq == 0 and Skv % bk == 0 (ops.py pads).  ``kv_len`` masks keys at
    or past the live length — it is a DYNAMIC input (python int, 0-d array,
    traced scalar, or a per-row [BH] vector; None means Skv), so distinct
    prompt lengths — and distinct ragged length *vectors* — sharing a padded
    shape reuse one compiled kernel.  A scalar is broadcast to every row; a
    vector gives each flattened head row its own live length (ragged
    batches; ops.py expands per-sequence [B] lengths by the head count).
    ``src_fmt_name`` requests the in-kernel RNE operand snap for
    emulate-mode policies (f32 containers); native narrow ``src_dtype``
    casts need none.  With ``debug_visits`` the kernel also returns an int32
    [BH, n_steps] array flagging, per row, which scheduled grid steps did
    QK/PV work (the dynamic per-row ``kv_len`` early-outs write 0 — the
    per-sequence energy-proportionality proof).

    With ``debug_flags`` the kernel additionally returns an int32
    [BH, n_steps, 4] array of per-(row, scheduled step) IEEE flag counts
    (OF, UF, NX, NV — docs/KERNELS.md): K/V tiles are counted per VISIT
    (a KV block seen by several query blocks is charged at each), the Q
    tile once per query block at its first scheduled step; slots at or
    past ``kv_len`` and early-out steps contribute zero.  Extra outputs
    are appended in (visits, flags) order when both are requested.
    """
    bh, sq, d = q.shape
    paged = block_table is not None
    if paged:
        n_pages, page, dk = k.shape
        assert page == bk and v.shape[:2] == (n_pages, page), \
            (k.shape, v.shape, bk)
        assert block_table.shape[0] * group == bh, (block_table.shape, bh,
                                                    group)
        skv = block_table.shape[1] * bk       # logical KV length
        dv = v.shape[-1]
    else:
        bkv, skv, dk = k.shape
        _, skv_v, dv = v.shape
        assert skv == skv_v and bh == bkv * group, \
            (q.shape, k.shape, v.shape, group)
    assert d == dk, (q.shape, k.shape)
    assert sq % bq == 0 and skv % bk == 0, (q.shape, k.shape, bq, bk)
    kvl = jnp.reshape(jnp.asarray(skv if kv_len is None else kv_len,
                                  jnp.int32), (-1,))
    assert kvl.shape[0] in (1, bh), (kvl.shape, bh)
    kvl = jnp.broadcast_to(kvl, (bh,))
    qi, ki, ff, lf = block_schedule(sq, skv, bq, bk, causal=causal,
                                    window=window, q_offset=q_offset)
    n_steps = len(qi)

    kern = functools.partial(
        _attn_kernel, bq=bq, bk=bk, paged=paged, scale=scale, causal=causal,
        window=window, softcap=softcap, q_offset=q_offset,
        src_fmt=get_format(src_fmt_name) if src_fmt_name else None,
        src_dtype=src_dtype, out_dtype=out_dtype, debug_visits=debug_visits,
        debug_flags=debug_flags)
    # index maps see (grid ids..., *scalar-prefetch refs); the paged form
    # appends the page table and dereferences it for the K/V block index
    if paged:
        scalars = (kvl, jnp.asarray(qi), jnp.asarray(ki), jnp.asarray(ff),
                   jnp.asarray(lf), jnp.asarray(block_table, jnp.int32))
        q_map = lambda h, s, kvl, qi, ki, ff, lf, bt: (h, qi[s], 0)
        kv_map = lambda h, s, kvl, qi, ki, ff, lf, bt, g=group: \
            (bt[h // g, ki[s]], 0, 0)
        row_map = lambda h, s, kvl, qi, ki, ff, lf, bt: (h, 0, 0)
    else:
        scalars = (kvl, jnp.asarray(qi), jnp.asarray(ki), jnp.asarray(ff),
                   jnp.asarray(lf))
        q_map = lambda h, s, kvl, qi, ki, ff, lf: (h, qi[s], 0)
        kv_map = lambda h, s, kvl, qi, ki, ff, lf, g=group: \
            (h // g, ki[s], 0)
        row_map = lambda h, s, kvl, qi, ki, ff, lf: (h, 0, 0)
    out_shape = [jax.ShapeDtypeStruct((bh, sq, dv), out_dtype)]
    out_specs = [pl.BlockSpec((1, bq, dv), q_map)]
    if debug_visits:
        # whole-row debug blocks, resident across the row's steps (see
        # decode_attention._put_debug_row)
        out_shape.append(jax.ShapeDtypeStruct((bh, n_steps, 1), jnp.int32))
        out_specs.append(pl.BlockSpec((1, n_steps, 1), row_map))
    if debug_flags:
        out_shape.append(jax.ShapeDtypeStruct((bh, n_steps, N_FLAGS),
                                              jnp.int32))
        out_specs.append(pl.BlockSpec((1, n_steps, N_FLAGS), row_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(bh, n_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ])
    out = pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )(*scalars, q, k, v)
    out = list(out)
    if debug_visits:
        out[1] = out[1][..., 0]
    return tuple(out) if (debug_visits or debug_flags) else out[0]
