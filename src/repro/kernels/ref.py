"""Pure-jnp oracles for every Pallas kernel (the paper-semantics references).

Each function mirrors one kernel's contract exactly — same formats, same
masking, same accumulation dtype — but written as straight jnp so tests can
assert_allclose kernels against them over shape/dtype sweeps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import softfloat
from ..core.formats import get_format

NEG_INF = -1e30

N_FLAG_CH = 4   # flag-count channel order: OF, UF, NX, NV


def _per_row_lens(kv_len, bh, default):
    """Normalize a scalar-or-vector ``kv_len`` to a length-``bh`` numpy int
    vector (one live length per flattened head row) — the oracle twin of the
    kernels' SMEM length normalization.  ``None`` means ``default``."""
    import numpy as np
    if kv_len is None:
        kv_len = default
    lens = np.asarray(kv_len, np.int64).reshape(-1)
    assert lens.shape[0] in (1, bh), (lens.shape, bh)
    return np.broadcast_to(lens, (bh,))


def tp_matmul_ref(a, b, *, out_dtype=jnp.float32, quant_fmt_name=None,
                  bk=None):
    """Expanding-FMA matmul oracle: optional fp-grid operand snap (FTZ like
    the kernel), f32 accumulation, out_dtype store.

    ``bk`` fixes the K-blocking schedule: partial products are summed per
    K-block in order, exactly like the kernel's VMEM accumulator.  The
    summation schedule is part of the op's numerical contract (the paper's
    FMA units likewise specify their accumulation order); with matching
    ``bk`` the oracle is bit-exact against the kernel."""
    if quant_fmt_name is not None:
        fmt = get_format(quant_fmt_name)
        a = _ftz(softfloat.quantize(a.astype(jnp.float32), fmt), fmt)
        b = _ftz(softfloat.quantize(b.astype(jnp.float32), fmt), fmt)
    # operands stay in their source dtype (the MXU contract); only the
    # accumulator is f32 — identical to the kernel's dot_general.
    k = a.shape[-1]
    dot = lambda x, y: jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    if bk is None or bk >= k:
        r = dot(a, b)
    else:
        assert k % bk == 0, (k, bk)
        r = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
        for kk in range(0, k, bk):  # sequential K-block accumulation
            r = r + dot(a[:, kk:kk + bk], b[kk:kk + bk, :])
    return r.astype(out_dtype)


def _ftz(x, fmt):
    return jnp.where(jnp.abs(x) < fmt.min_normal, jnp.sign(x) * 0.0, x)


def _snap(x, fmt, src_dtype):
    """Oracle twin of quant_common.widen: emulate-mode f32 containers are
    RNE-snapped (with FTZ) onto the storage grid, then cast to the compute
    dtype.  Shared by every attention oracle in this module."""
    if fmt is not None and x.dtype == jnp.float32:
        x = _ftz(softfloat.quantize(x, fmt), fmt)
    return x.astype(src_dtype)


def tp_quantize_ref(x, *, fmt_name, out_dtype=jnp.float32):
    fmt = get_format(fmt_name)
    q = _ftz(softfloat.quantize(x.astype(jnp.float32), fmt), fmt)
    return q.astype(out_dtype)


def cast_and_pack_ref(a, b, *, fmt_name, out_dtype=jnp.float32):
    qa = tp_quantize_ref(a, fmt_name=fmt_name, out_dtype=out_dtype)
    qb = tp_quantize_ref(b, fmt_name=fmt_name, out_dtype=out_dtype)
    r, c = qa.shape
    return jnp.stack([qa, qb], axis=-1).reshape(r, 2 * c)


def flash_attention_ref(q, k, v, *, group: int = 1, scale: float = 1.0,
                        causal: bool = True, window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        kv_len: Optional[int] = None, q_offset: int = 0,
                        src_fmt_name: Optional[str] = None,
                        src_dtype=jnp.bfloat16, out_dtype=jnp.float32,
                        bq: Optional[int] = None, bk: Optional[int] = None):
    """Flash-attention oracle with identical format contract to the kernel.

    ``bq``/``bk`` fix the online-softmax blocking schedule: the oracle then
    walks the SAME pruned block schedule as the kernel
    (``flash_attention.block_schedule``) with the same per-block rescaling
    ops, making it bit-exact against ``flash_attention_pallas`` in interpret
    mode — the prefill analogue of ``decode_attention_ref``'s ``bk``.  With
    ``bq=bk=None`` it is the plain dense-softmax reference (one global max,
    one sum — tolerance comparisons only).

    ``src_fmt_name`` mirrors the kernel's emulate-mode RNE operand snap
    (f32 containers); ``q_offset`` shifts query positions for the causal /
    window masks.  q: [BH, Sq, D]; k: [BKV, Skv, D]; v: [BKV, Skv, Dv].

    ``kv_len`` may be a scalar (every row shares one length) or a per-row
    length-BH vector (ragged batches — the per-sequence oracle; expand a
    [B] sequence-length vector by the head count like ops.py does).
    """
    bh, sq, d = q.shape
    bkv, skv, _ = k.shape
    kv_len = _per_row_lens(kv_len, bh, skv)
    if bq is not None or bk is not None:
        assert bq is not None and bk is not None, (bq, bk)
        return _flash_blocked_ref(
            q, k, v, group=group, scale=scale, causal=causal, window=window,
            softcap=softcap, kv_len=kv_len, q_offset=q_offset,
            src_fmt_name=src_fmt_name, src_dtype=src_dtype,
            out_dtype=out_dtype, bq=bq, bk=bk)

    fmt = get_format(src_fmt_name) if src_fmt_name else None
    snap = lambda x: _snap(x, fmt, src_dtype)

    kk = jnp.repeat(k, group, axis=0)
    vv = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", snap(q), snap(kk),
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_idx = q_offset + jnp.arange(sq)[:, None]
    k_idx = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= q_idx >= k_idx
    if window is not None:
        mask &= (q_idx - k_idx) < window
    # per-row live length: [BH, 1, Skv] against the static [Sq, Skv] masks
    mask = mask[None] & (k_idx[None] < jnp.asarray(kv_len)[:, None, None])
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("hqk,hkd->hqd", snap(p).astype(jnp.float32),
                   vv.astype(jnp.float32), preferred_element_type=jnp.float32)
    return (o / jnp.where(l == 0.0, 1.0, l)).astype(out_dtype)


def _flash_block_update(qb, kb, vb, acc, m, l, q_base, k_base, kvl, *,
                        scale, causal, window, softcap, src_fmt_name,
                        src_dtype):
    """One online-softmax block step — the exact op sequence of
    ``flash_attention._attn_kernel``'s work block.  MUST run jitted: the
    rescale updates are mul+add chains that XLA:CPU contracts into FMAs
    (single rounding) inside any compiled computation — eager op-by-op
    dispatch rounds twice and is one ulp off.  The jitted form matches the
    kernel (whose body is always compiled, interpret mode included)."""
    from .decode_attention import softcap_scores

    fmt = get_format(src_fmt_name) if src_fmt_name else None
    snap = lambda x: _snap(x, fmt, src_dtype)
    bq, bk = qb.shape[0], kb.shape[0]
    s = jax.lax.dot_general(snap(qb), snap(kb), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap_scores(s, softcap)
    q_idx = q_base + jnp.arange(bq)[:, None]
    k_idx = k_base + jnp.arange(bk)[None, :]
    mask = k_idx < kvl
    if causal:
        mask = mask & (q_idx >= k_idx)
    if window is not None:
        mask = mask & ((q_idx - k_idx) < window)
    s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(jnp.where(m_new <= NEG_INF / 2, 0.0, m - m_new))
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(snap(p), snap(vb), (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc = acc * alpha + pv
    return acc, m_new, l


def _flash_blocked_ref(q, k, v, *, group, scale, causal, window, softcap,
                       kv_len, q_offset, src_fmt_name, src_dtype, out_dtype,
                       bq, bk):
    """Blocked online-softmax walk over the kernel's pruned schedule —
    elementary-op-for-op the same updates as ``_attn_kernel``, so the
    result is bitwise identical in interpret mode.  ``kv_len`` is the
    per-row length vector from ``_per_row_lens``: each row early-outs at
    its OWN length, the oracle twin of the kernel's per-row ``pl.when``."""
    from .flash_attention import block_schedule

    bh, sq, d = q.shape
    dv = v.shape[-1]
    qi, ki, ff, lf = block_schedule(sq, k.shape[1], bq, bk, causal=causal,
                                    window=window, q_offset=q_offset)
    upd = jax.jit(functools.partial(
        _flash_block_update, scale=scale, causal=causal, window=window,
        softcap=softcap, src_fmt_name=src_fmt_name, src_dtype=src_dtype))
    out = []
    for h in range(bh):
        hk = h // group
        kvl = int(kv_len[h])
        rows = {}
        for step in range(len(qi)):
            iq, ik = int(qi[step]), int(ki[step])
            if ff[step]:
                acc = jnp.zeros((bq, dv), jnp.float32)
                m = jnp.full((bq, 1), NEG_INF, jnp.float32)
                l = jnp.zeros((bq, 1), jnp.float32)
            if ik * bk < kvl:   # the kernel's dynamic pl.when early-out
                acc, m, l = upd(q[h, iq * bq:(iq + 1) * bq],
                                k[hk, ik * bk:(ik + 1) * bk],
                                v[hk, ik * bk:(ik + 1) * bk],
                                acc, m, l,
                                jnp.int32(q_offset + iq * bq),
                                jnp.int32(ik * bk), jnp.int32(kvl))
            if lf[step]:
                rows[iq] = (acc /
                            jnp.where(l == 0.0, 1.0, l)).astype(out_dtype)
        out.append(jnp.concatenate([rows[iq] for iq in sorted(rows)], axis=0))
    return jnp.stack(out)


def decode_attention_ref(q, k, v, *, kv_len, scale: float = 1.0,
                         window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         kv_fmt_name: Optional[str] = None,
                         q_fmt_name: Optional[str] = None,
                         src_dtype=jnp.float32, out_dtype=jnp.float32,
                         bk: Optional[int] = None):
    """Dense single-query decode-attention oracle with the decode kernel's
    exact format contract: in-container RNE snap of KV (and optionally q)
    onto the storage grid, src-format multiplies, f32 accumulation, exact
    global softmax max, single store cast.

    ``bk`` fixes the KV-blocking schedule of the numerator/denominator
    accumulation (and the score dot shapes), exactly like tp_matmul_ref's
    K-blocking — with matching ``bk`` the oracle is bit-exact against
    decode_attention_pallas in interpret mode; with ``bk=None`` it is the
    plain dense path (one block).

    q: [BHkv, G, D]; k, v: [BHkv, Smax, D]; kv_len: int (or 0-d array)
    shared by every row, or a per-row length-BHkv vector (ragged batches —
    each row's KV blocks past its own length are skipped, mirroring the
    kernel's per-row early-exit).
    """
    bh, g, d = q.shape
    _, smax, _ = k.shape
    bk = smax if bk is None else bk
    assert smax % bk == 0, (smax, bk)
    kv_len = _per_row_lens(kv_len, bh, smax)

    snap = lambda x, fmt_name: _snap(
        x, get_format(fmt_name) if fmt_name else None, src_dtype)
    qs = snap(q, q_fmt_name)
    ks = snap(k, kv_fmt_name)
    vs = snap(v, kv_fmt_name)
    dot_qk = lambda qi, ki: jax.lax.dot_general(
        qi, ki, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    dot_pv = lambda pi, vi: jax.lax.dot_general(
        pi, vi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    out = []
    for h in range(bh):
        kvl = int(kv_len[h])
        if kvl <= 0:           # empty row: the kernel's l == 0 store guard
            out.append(jnp.zeros((g, d), out_dtype))
            continue
        blocks = []
        for kk in range(0, smax, bk):
            if kk >= kvl:      # the kernel's per-row early-exit (exact)
                continue
            s = dot_qk(qs[h], ks[h, kk:kk + bk]) * scale
            if softcap is not None:
                from .decode_attention import softcap_scores
                s = softcap_scores(s, softcap)
            k_idx = kk + jnp.arange(bk)[None, :]
            mask = k_idx < kvl
            if window is not None:
                mask = mask & (k_idx > kvl - 1 - window)
            blocks.append((kk, jnp.where(mask, s, NEG_INF), mask))
        m = jnp.max(jnp.concatenate([s for _, s, _ in blocks], axis=-1),
                    axis=-1, keepdims=True)
        m = jnp.where(m <= NEG_INF / 2, 0.0, m)
        acc = jnp.zeros((g, d), jnp.float32)
        l = jnp.zeros((g, 1), jnp.float32)
        for kk, s, mask in blocks:
            p = jnp.where(mask, jnp.exp(s - m), 0.0)
            l = l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc + dot_pv(p.astype(src_dtype), vs[h, kk:kk + bk])
        out.append((acc / jnp.where(l == 0.0, 1.0, l)).astype(out_dtype))
    return jnp.stack(out)


def paged_gather(pool, table):
    """Materialize a paged KV layout back into per-row contiguous strips:
    ``pool`` [n_pages, page, D] gathered through ``table`` [rows, nk] ->
    [rows, nk * page, D].  Pure data movement (no arithmetic), so oracles
    built on it are exact references for the paged kernels: the kernel
    dereferences the table at DMA time, the oracle dereferences it up
    front, and both then run the identical blocked walk."""
    rows, nk = table.shape
    n_pages, page, d = pool.shape
    g = jnp.take(pool, jnp.asarray(table).reshape(-1), axis=0)
    return g.reshape(rows, nk * page, d)


def decode_attention_paged_ref(q, k_pool, v_pool, block_table, *, kv_len,
                               **kw):
    """Paged decode-attention oracle: gather pages to the contiguous view,
    then run ``decode_attention_ref`` with ``bk`` pinned to the page size
    (the paged kernel's block IS the page, so the blocked accumulation
    schedule — part of the numerical contract — matches and the result is
    bit-exact against ``decode_attention_pallas(..., block_table=)``,
    partial tail pages included via the usual ``kv_len`` masking).

    q: [BHkv, G, D]; k_pool/v_pool: [n_pages, page, D];
    block_table: [BHkv, nk] flat per-head page ids."""
    page = k_pool.shape[1]
    return decode_attention_ref(q, paged_gather(k_pool, block_table),
                                paged_gather(v_pool, block_table),
                                kv_len=kv_len, bk=page, **kw)


def flash_attention_paged_ref(q, k_pool, v_pool, block_table, *, bq,
                              kv_len=None, **kw):
    """Paged flash-attention oracle: gather, then the blocked online-softmax
    walk with ``bk`` pinned to the page size — bit-exact against
    ``flash_attention_pallas(..., block_table=)`` (same pruned schedule,
    same per-block update ops, same operand values).

    q: [BH, Sq, D]; k_pool: [n_pages, page, D]; v_pool: [n_pages, page,
    Dv]; block_table: [BKV, nk] per-KV-row page ids (BH = BKV * group)."""
    page = k_pool.shape[1]
    return flash_attention_ref(q, paged_gather(k_pool, block_table),
                               paged_gather(v_pool, block_table),
                               kv_len=kv_len, bq=bq, bk=page, **kw)


def _flag_masks_ref(x, fmt):
    """Oracle twin of ``quant_common.widen_with_flags``'s masks, derived
    independently from the softfloat oracle: the non-saturating snap's Inf
    marks OF, the FTZ'd snap's value change marks NX, tininess below min
    normal plus NX marks UF, NaN input marks NV.  Native narrow storage
    (fmt None / non-f32 input): OF := stored ±Inf, NV := stored NaN,
    UF/NX := False."""
    if fmt is not None and x.dtype == jnp.float32:
        y_ieee = softfloat.quantize(x, fmt)       # overflow -> ±Inf
        y = _ftz(y_ieee, fmt)
        nv = jnp.isnan(x)
        of = jnp.isinf(y_ieee) & ~jnp.isinf(x) & ~nv
        nx = (y != x) & ~nv
        uf = (x != 0) & (jnp.abs(x) < fmt.min_normal) & nx
        return of, uf, nx, nv
    z = jnp.zeros(x.shape, bool)
    return jnp.isinf(x), z, z, jnp.isnan(x)


def _mask_counts(masks, live):
    return jnp.stack([jnp.sum((f & live).astype(jnp.int32),
                              axis=tuple(range(1, f.ndim)))
                      for f in masks], axis=-1)


def decode_flag_counts_ref(q, k, v, *, kv_len,
                           kv_fmt_name: Optional[str] = None,
                           q_fmt_name: Optional[str] = None):
    """Per-row IEEE flag-count oracle of ``decode_attention_pallas(...,
    debug_flags=True)`` summed over KV blocks: int32 [BHkv, 4] in OF, UF,
    NX, NV order.  Each live K/V element (position < that row's kv_len)
    counts once; Q counts once per row with live length > 0; dead/padded
    slots contribute zero.  Layouts as in :func:`decode_attention_ref`."""
    bh, g, d = q.shape
    smax = k.shape[1]
    kv_len = jnp.asarray(_per_row_lens(kv_len, bh, smax), jnp.int32)
    kfmt = get_format(kv_fmt_name) if kv_fmt_name else None
    qfmt = get_format(q_fmt_name) if q_fmt_name else None
    live = (jnp.arange(smax)[None, :, None]
            < kv_len[:, None, None])                       # [BH, Smax, 1]
    cnt = (_mask_counts(_flag_masks_ref(k, kfmt), live)
           + _mask_counts(_flag_masks_ref(v, kfmt), live))
    qc = _mask_counts(_flag_masks_ref(q, qfmt), jnp.ones((bh, 1, 1), bool))
    return cnt + jnp.where((kv_len > 0)[:, None], qc, 0)


def decode_flag_counts_paged_ref(q, k_pool, v_pool, block_table, *, kv_len,
                                 **kw):
    """Paged twin: gather pages to the contiguous view first (the count is
    schedule-free — a position is live iff it is < kv_len)."""
    return decode_flag_counts_ref(q, paged_gather(k_pool, block_table),
                                  paged_gather(v_pool, block_table),
                                  kv_len=kv_len, **kw)


def flash_flag_counts_ref(q, k, v, *, group: int = 1, kv_len=None,
                          causal: bool = True,
                          window: Optional[int] = None, q_offset: int = 0,
                          src_fmt_name: Optional[str] = None,
                          bq: int = 128, bk: int = 128):
    """Per-row flag-count oracle of ``flash_attention_pallas(...,
    debug_flags=True)`` summed over steps: int32 [BH, 4].  Walks the SAME
    pruned ``block_schedule`` with the kernel's per-VISIT semantics — a KV
    block seen by several query blocks is charged at each visit, the Q
    tile once per query block at its first scheduled step, early-out steps
    (block start >= that row's kv_len) charge nothing."""
    from .flash_attention import block_schedule

    bh, sq, d = q.shape
    skv = k.shape[1]
    kv_len = _per_row_lens(kv_len, bh, skv)
    fmt = get_format(src_fmt_name) if src_fmt_name else None
    qi, ki, ff, lf = block_schedule(sq, skv, bq, bk, causal=causal,
                                    window=window, q_offset=q_offset)
    kmask = _flag_masks_ref(k, fmt)
    vmask = _flag_masks_ref(v, fmt)
    qmask = _flag_masks_ref(q, fmt)
    pos = jnp.arange(skv)[:, None]                          # [Skv, 1]
    out = []
    for h in range(bh):
        hk = h // group
        kvl = int(kv_len[h])
        cnt = jnp.zeros((N_FLAG_CH,), jnp.int32)
        for step in range(len(qi)):
            iq, ik = int(qi[step]), int(ki[step])
            if ik * bk >= kvl:
                continue
            live = pos[ik * bk:(ik + 1) * bk] < kvl
            cnt = cnt + _mask_counts(
                [f[hk, ik * bk:(ik + 1) * bk][None] for f in kmask],
                live[None])[0]
            cnt = cnt + _mask_counts(
                [f[hk, ik * bk:(ik + 1) * bk][None] for f in vmask],
                live[None])[0]
            if ff[step]:
                cnt = cnt + _mask_counts(
                    [f[h, iq * bq:(iq + 1) * bq][None] for f in qmask],
                    jnp.ones((1, 1, 1), bool))[0]
        out.append(cnt)
    return jnp.stack(out)


def flash_flag_counts_paged_ref(q, k_pool, v_pool, block_table, *, bq,
                                kv_len=None, **kw):
    """Paged twin of :func:`flash_flag_counts_ref` (bk pinned to the page
    size, like the paged output oracles)."""
    page = k_pool.shape[1]
    return flash_flag_counts_ref(q, paged_gather(k_pool, block_table),
                                 paged_gather(v_pool, block_table),
                                 kv_len=kv_len, bq=bq, bk=page, **kw)


def dotp_ex_ref(a, b, *, src_dtype=jnp.float16):
    """Expanding dot product oracle (f32 accumulate of exact products)."""
    prod = (a.astype(src_dtype).astype(jnp.float32)
            * b.astype(src_dtype).astype(jnp.float32))
    return jnp.sum(prod)


def dotp_sequential_ref(a, b, *, src_fmt="fp16", acc_fmt="fp32"):
    """Bit-exact *sequential* oracle of the paper's fmacex loop (Fig 11e):
    acc_{i+1} = round_acc(acc_i + a_i * b_i), products exact."""
    src, acc = get_format(src_fmt), get_format(acc_fmt)
    qa = softfloat.quantize(a, src)
    qb = softfloat.quantize(b, src)

    def step(acc_v, ab):
        s = softfloat.quantize(acc_v + ab[0] * ab[1], acc)
        return s, ()

    out, _ = jax.lax.scan(step, jnp.float32(0.0), (qa, qb))
    return out


def grouped_ffn_ref(x, expert_ids, w_gate, w_up, w_down, layer, *,
                    src_dtype=jnp.bfloat16, acc_dtype=jnp.float32,
                    out_dtype=jnp.bfloat16, elem_dtype=jnp.float32):
    """Oracle of the grouped expert SwiGLU: each row ``x[n]`` through
    expert ``expert_ids[n]`` of stacked layer ``layer``, with the kernel's
    roundings (``src`` operands, ``acc`` accumulation, ``g``/``u`` stored
    in ``out``, ``silu(g) * u`` in ``elem`` then ``src``, result in
    ``out``).  Every expert runs over every row and each row keeps its
    own expert's output — no grouping, no tiles."""
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=acc_dtype)
    xs = x.astype(src_dtype)
    y = jnp.zeros(x.shape, out_dtype)
    w = lambda a, e: a[layer, e].astype(src_dtype)
    for e in range(w_gate.shape[1]):
        g = dot(xs, w(w_gate, e)).astype(out_dtype)
        u = dot(xs, w(w_up, e)).astype(out_dtype)
        h = jax.nn.silu(g.astype(elem_dtype)) * u
        ye = dot(h.astype(src_dtype), w(w_down, e)).astype(out_dtype)
        y = jnp.where((expert_ids == e)[:, None], ye, y)
    return y
