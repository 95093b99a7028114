"""Pallas TPU kernel: fused single-query decode attention over a quantized
KV cache — the serving-path instantiation of FPnew's CONV->ADDMUL fusion.

FPnew's headline energy-proportionality result comes from keeping narrow
formats *on the wire* and fusing the format conversion (CONV block) into the
FMA datapath (ADDMUL block) so values are widened exactly once, at the
multiplier input (paper §II.B.4, the expanding multi-format FMA
``dst fma(src a, src b, dst c)``).  This kernel applies that contract to the
hottest serving loop — decode attention against a long KV cache:

  stage           FPnew block   what happens here
  -----------     -----------   ------------------------------------------
  KV dequant      CONV          cache lines enter in their *storage* format
                                (native bf16/fp16/fp8 dtype, or an f32
                                container holding values on the ``kv_fmt``
                                grid); they are RNE-snapped / widened
                                in-kernel, per VMEM tile — never
                                materialized wide in HBM.
  q·K^T           ADDMUL        src-format multiplies, f32 accumulation
                                (the expanding FMA; MXU semantics).
  softmax stats   COMP          max / exp / sum stay f32 (the paper keeps
                                COMP in full precision).
  p·V             ADDMUL        src-format multiplies, f32 accumulation.
  store           CONV          single cast to ``out_dtype`` on the way out.

Layout: q [BHkv, G, D] (the G = n_heads/n_kv_heads query heads that share
one KV head), k/v [BHkv, Smax, D] cache buffers, kv_len a *dynamic* per-row
[BHkv, 1] vector (SMEM) masking dead cache slots — it changes every decode
step, so it must not trigger a retrace inside the ``lax.scan`` generation
loop.  Each row's KV-block loop early-exits at its OWN length (``pl.when``
on ``j * bk < kv_len[row]``): a ragged serving batch pays per-sequence
work, not the longest sequence's grid — the work-level analogue of FPnew's
per-operand precision proportionality.  A uniform batch passes the same
scalar in every row and behaves exactly as before.

Schedule: grid (BHkv, 2, Smax/bk), kv innermost, two passes over the KV
blocks.  Pass 0 computes the exact global score max; pass 1 recomputes
scores (flash-style recompute) and accumulates the numerator / denominator
blockwise in f32 VMEM scratch.  Unlike online-softmax rescaling, the
two-pass schedule is *bit-exact* against the dense reference
(ref.decode_attention_ref with matching ``bk``): the max is exact, and the
blockwise f32 sums are part of the op's numerical contract, exactly like
tp_matmul's K-blocking.  The cost is streaming K twice (V's block index is
pinned during the max pass, so V streams once) — for single-query decode
the score pass is a thin [G, bk] strip, so the extra traffic is the K
reload, not a 2x compute or bandwidth bill.

Paged KV (``block_table``): instead of each row owning a contiguous
``[Smax, D]`` cache strip, K/V live in a shared page pool ``[n_pages, bk,
D]`` and a per-row table maps the row's logical block ``j`` to a physical
page.  Only the BlockSpec index maps change — ``(h, j, 0)`` becomes
``(bt[h, j], 0, 0)`` — dereferenced at DMA-issue time from the
scalar-prefetch table, so the kernel body (and therefore the numerics) is
IDENTICAL to the contiguous layout: paged output is bit-exact against the
contiguous kernel and the ``bk``-blocked oracle whenever the gathered
pages hold the same values.  Rows may alias pages (prefix sharing) and the
table is a traced value (page churn never retraces).  ``kv_len`` keeps
masking exactly as before, so partial tail pages need no special casing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import get_format
from .quant_common import widen as _widen
from .quant_common import widen_with_flags as _widen_flags

NEG_INF = -1e30

# flag-counter channel order (docs/KERNELS.md): OF, UF, NX, NV
N_FLAGS = 4


def _flag_counts(x_ref, fmt, src_dtype, live):
    """Per-tile OF/UF/NX/NV counts of one CONV site, masked by ``live``
    (liveness along the leading tile axis — dead/padded slots contribute
    zero).  Returns a tuple of four int32 scalars."""
    _, of, uf, nx, nv = _widen_flags(x_ref, fmt, src_dtype)
    return tuple(jnp.sum((f & live).astype(jnp.int32))
                 for f in (of, uf, nx, nv))


def _put_debug_row(ref, i, vals):
    """Set row ``i`` (a grid id) of a debug output block.

    Debug outputs are whole-row blocks ``[1, n, C]`` kept resident across
    one head row's grid steps — Mosaic's tiling rule wants a block's last
    two dims (8, 128)-aligned or spanning the array, which per-cell
    ``(1, 1)`` blocks are not — and every step selects its own row in:
    ``vals`` holds the ``C`` per-channel scalars.  Every row is written by
    some step, so no initialization is needed."""
    blk = ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    new = vals[-1]
    for c in range(len(vals) - 2, -1, -1):
        new = jnp.where(cols == c, vals[c], new)
    ref[0] = jnp.where(rows == i, new, blk)


def softcap_scores(s, cap: float):
    """Attention-logit soft-capping via exp: ``cap * tanh(s / cap)`` with
    ``tanh(x) = 1 - 2/(exp(2x) + 1)``.  Written out this way (instead of
    ``jnp.tanh``) because XLA expands tanh into a polynomial whose FMA
    contraction depends on the surrounding fusion context — the exp form
    uses only context-stable ops, so kernel and oracle stay bit-identical.
    Shared by decode_attention_pallas and ref.decode_attention_ref."""
    e = jnp.exp(s * (2.0 / cap))
    return cap * (1.0 - 2.0 / (e + 1.0))


def _decode_kernel(len_ref, *args, nk: int, bk: int, paged: bool,
                   scale: float, window: Optional[int],
                   softcap: Optional[float], kv_fmt, q_fmt, src_dtype,
                   out_dtype, debug_visits: bool, debug_flags: bool):
    if paged:
        args = args[1:]            # bt_ref: consumed by the index maps only
    q_ref, k_ref, v_ref, o_ref, *rest = args
    visits_ref = flags_ref = None
    if debug_visits:
        visits_ref, rest = rest[0], rest[1:]
    if debug_flags:
        flags_ref, rest = rest[0], rest[1:]
    m_ref, acc_ref, l_ref = rest
    ip = pl.program_id(1)          # 0 = max pass, 1 = accumulate pass
    j = pl.program_id(2)           # kv block
    kvl = len_ref[pl.program_id(0)]   # this row's own live length

    @pl.when((ip == 0) & (j == 0))
    def _init_max():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    @pl.when((ip == 1) & (j == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)

    # per-row early-exit: the whole KV block lies past this row's length.
    # Skipping is exact — a fully-masked block contributes max = NEG_INF
    # (no-op under jnp.maximum) in pass 0 and p = 0 in pass 1.
    active = j * bk < kvl

    @pl.when(active)
    def _work():
        q = _widen(q_ref[0], q_fmt, src_dtype)          # (G, D)
        k = _widen(k_ref[0], kv_fmt, src_dtype)         # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap is not None:
            s = softcap_scores(s, softcap)

        g = s.shape[0]
        k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        mask = k_idx < kvl
        if window is not None:
            mask &= k_idx > kvl - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        @pl.when(ip == 0)
        def _max_pass():
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_ref[...] = jnp.maximum(m_ref[...],
                                     jnp.broadcast_to(m_cur, m_ref.shape))

        @pl.when(ip == 1)
        def _acc_pass():
            m = m_ref[:, :1]
            # guard fully-masked rows (m == NEG_INF): keep exp arg finite
            p = jnp.exp(s - jnp.where(m <= NEG_INF / 2, 0.0, m))
            p = jnp.where(mask, p, 0.0)
            l_ref[...] = l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
            v = _widen(v_ref[0], kv_fmt, src_dtype)
            acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
                p.astype(src_dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    # the store must run even when this row's last blocks were early-outs
    @pl.when((ip == 1) & (j == nk - 1))
    def _store():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(out_dtype)

    if debug_visits:
        _put_debug_row(visits_ref, j, (active.astype(jnp.int32),))
    if debug_flags:
        # Flag accumulation mirrors debug_visits: both passes write the same
        # (h, j) cell and the accumulate pass (ip == 1) writes last, when
        # v_ref maps to block j's true page (it is pinned during the max
        # pass) — so the surviving value counts each K/V tile exactly once
        # per row.  Q's CONV site is charged to the j == 0 cell.  Slots at
        # or past this row's kv_len are masked out and early-out blocks
        # write zeros: dead/padded cache slots contribute nothing.
        live = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                ) < kvl
        kc = _flag_counts(k_ref[0], kv_fmt, src_dtype, live)
        vc = _flag_counts(v_ref[0], kv_fmt, src_dtype, live)
        qc = _flag_counts(q_ref[0], q_fmt, src_dtype,
                          jnp.ones((1, 1), jnp.bool_))
        _put_debug_row(flags_ref, j, tuple(
            jnp.where(active, a + b + jnp.where(j == 0, c, 0), 0)
            for a, b, c in zip(kc, vc, qc)))


@functools.partial(jax.jit, static_argnames=(
    "bk", "scale", "window", "softcap", "kv_fmt_name", "q_fmt_name",
    "src_dtype", "out_dtype", "interpret", "debug_visits", "debug_flags"))
def decode_attention_pallas(q, k, v, kv_len, block_table=None, *,
                            bk: int = 128,
                            scale: float = 1.0,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            kv_fmt_name: Optional[str] = None,
                            q_fmt_name: Optional[str] = None,
                            src_dtype=jnp.bfloat16,
                            out_dtype=jnp.float32,
                            interpret: bool = True,
                            debug_visits: bool = False,
                            debug_flags: bool = False):
    """q: [BHkv, G, D]; k, v: [BHkv, Smax, D]; kv_len: int32 live cache
    length(s) — a traced value, not a static.  A [1, 1] (or scalar) length
    is broadcast to every row; a per-row [BHkv, 1] (or [BHkv]) vector gives
    each row its own length and its KV-block loop early-exits there (ragged
    serving batches; ops.py expands per-sequence [B] lengths by the KV-head
    count).

    Paged layout (``block_table`` [BHkv, nk] int32, also traced): k/v are
    instead shared page POOLS [n_pages, bk, D] and row ``h``'s logical
    block ``j`` lives in physical page ``block_table[h, j]`` — only the
    BlockSpec index maps change, the kernel body (hence the numerics) is
    identical, and the logical cache capacity is ``nk * bk``.  ops.py
    expands a per-sequence [B, max_pages] table to these flat per-head
    page ids.

    Smax % bk == 0 (the ops.py wrapper pads; padded slots have
    ``k_idx >= kv_len`` and are masked).  ``kv_fmt_name`` / ``q_fmt_name``
    request the in-kernel RNE grid snap for f32-container (emulated narrow)
    storage; native narrow dtypes are widened exactly without it.  With
    ``debug_visits`` the kernel also returns an int32 [BHkv, Smax/bk] array
    flagging, per row, which KV blocks did work (early-outs write 0).

    With ``debug_flags`` the kernel additionally returns an int32
    [BHkv, Smax/bk, 4] array of per-(row, KV-block) IEEE flag counts in
    channel order OF, UF, NX, NV — the fflags its CONV sites raise
    (docs/KERNELS.md).  Each live K and V element is counted once per row,
    Q once per row in the j == 0 cell; slots at or past ``kv_len`` and
    early-out blocks contribute zero.  Extra outputs are appended in
    (visits, flags) order when both are requested.
    """
    bh, g, d = q.shape
    paged = block_table is not None
    if paged:
        n_pages, page, dk = k.shape
        assert page == bk, (k.shape, bk)
        assert block_table.shape[0] == bh, (block_table.shape, bh)
        nk = block_table.shape[1]
    else:
        bkv, smax, dk = k.shape
        assert bh == bkv, (q.shape, k.shape)
        assert smax % bk == 0, (k.shape, bk)
        nk = smax // bk
    assert d == dk, (q.shape, k.shape)
    kvl = jnp.reshape(jnp.asarray(kv_len, jnp.int32), (-1,))
    assert kvl.shape[0] in (1, bh), (kvl.shape, bh)
    kvl = jnp.broadcast_to(kvl, (bh,))

    kern = functools.partial(
        _decode_kernel, nk=nk, bk=bk, paged=paged, scale=scale,
        window=window, softcap=softcap,
        kv_fmt=get_format(kv_fmt_name) if kv_fmt_name else None,
        q_fmt=get_format(q_fmt_name) if q_fmt_name else None,
        src_dtype=src_dtype, out_dtype=out_dtype, debug_visits=debug_visits,
        debug_flags=debug_flags)
    # scalar-prefetch args (kvl, and the page table when paged) are SMEM
    # tables the index maps may read at DMA-issue time; index maps take
    # (grid ids..., *scalar refs).
    if paged:
        scalars = (kvl, jnp.asarray(block_table, jnp.int32))
        k_map = lambda h, p, j, kvl, bt: (bt[h, j], 0, 0)
        # V is only read in the accumulate pass (p == 1): pin its page to
        # the row's first during the max pass so consecutive grid steps hit
        # the same tile and Mosaic skips the copy — V streams from HBM
        # once, K twice (the cost stated in the module docstring).
        v_map = lambda h, p, j, kvl, bt: (bt[h, j * p], 0, 0)
        fixed = lambda h, p, j, kvl, bt: (h, 0, 0)
        row = lambda h, p, j, kvl, bt: (h, 0, 0)
    else:
        scalars = (kvl,)
        k_map = lambda h, p, j, kvl: (h, j, 0)
        v_map = lambda h, p, j, kvl: (h, j * p, 0)   # pinned as above
        fixed = lambda h, p, j, kvl: (h, 0, 0)
        row = lambda h, p, j, kvl: (h, 0, 0)
    out_shape = [jax.ShapeDtypeStruct((bh, g, d), out_dtype)]
    out_specs = [pl.BlockSpec((1, g, d), fixed)]
    if debug_visits:
        # both passes write the same (h, j) cell with the same value
        out_shape.append(jax.ShapeDtypeStruct((bh, nk, 1), jnp.int32))
        out_specs.append(pl.BlockSpec((1, nk, 1), row))
    if debug_flags:
        # the accumulate pass's write survives (correct V page; see kernel)
        out_shape.append(jax.ShapeDtypeStruct((bh, nk, N_FLAGS), jnp.int32))
        out_specs.append(pl.BlockSpec((1, nk, N_FLAGS), row))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(bh, 2, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), fixed),
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, d), v_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),   # running max
            pltpu.VMEM((g, d), jnp.float32),     # output accumulator
            pltpu.VMEM((g, 128), jnp.float32),   # softmax denominator
        ])
    out = pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )(*scalars, q, k, v)
    out = list(out)
    if debug_visits:
        out[1] = out[1][..., 0]
    return tuple(out) if (debug_visits or debug_flags) else out[0]
