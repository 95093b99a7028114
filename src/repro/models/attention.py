"""Attention variants: GQA/MQA (with local windows, softcap, qk-norm) and
MLA (DeepSeek/MiniCPM latent attention), in train/prefill and decode forms.

All contractions run through core.ops.tp_einsum, i.e. under the FPnew
multi-format FMA contract (operands in src_fmt, f32 accumulation).  Softmax
statistics stay f32 (the paper keeps COMP in full precision).

Training/prefill uses a lax.scan over query chunks (online-softmax-free:
each chunk sees all keys, so memory is O(chunk * S) not O(S^2)) — the
pure-JAX twin of kernels/flash_attention.py, which is the TPU perf path.

Decode uses a KV cache: dense GQA caches k/v per head; MLA caches the
compressed latent + rope key only (the paper-style "storage format" win:
the latent cache is also quantizable via policy.kv_fmt).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import ops as tp
from ..core.formats import get_format
from .layers import (batch_axes, bspec, apply_rope, dense_init,
                     residual_spec, rmsnorm, shard, softcap)
from .paged import PagedKVCache, gather_paged_kv, paged_update_rows

NEG_INF = -1e30


def kv_store_dtype(policy):
    if policy.kv_fmt is not None and policy.mode == "native":
        return policy.kv_fmt.native_dtype
    return tp.storage_dtype(policy.param_fmt, policy.mode)


def kv_swap_dtype(fmt):
    """Host-side storage dtype for KV pages swapped out of the pool under
    a transprecision degrade format (serving-loop preemption): ``fmt`` is
    a format name or ``FPFormat`` with a native container (``fp8`` ->
    ``float8_e5m2``, 1 byte/value), so a degraded victim's swapped cache
    really is 2-4x smaller in host memory; swap-in widens back to the
    pool dtype.  When the pool itself already stores ``fmt`` (e.g. the
    ``tp_bf16_kv8`` policy), the round-trip is value-exact."""
    f = get_format(fmt)
    if f.native_dtype is None:
        raise ValueError(
            f"degrade format {f.name!r} has no native container dtype to "
            f"swap KV pages into (use fp8/bf16/fp16)")
    return f.native_dtype


def _is_vec(x) -> bool:
    """True for a per-sequence [B] vector (ragged batch), False for the
    scalar (python int / 0-d array) every row shares."""
    return getattr(x, "ndim", 0) >= 1 and not isinstance(x, (int, float))


def _len_rows(kv_len):
    """Normalize scalar-or-vector ``kv_len`` to a [1]-or-[B] int32 array —
    one broadcastable shape for every dense masking site below (a [1]
    array broadcasts over the batch exactly like the old scalar did)."""
    return jnp.reshape(jnp.asarray(kv_len, jnp.int32), (-1,))


def quantize_kv_rows(x, esc_fmts, levels):
    """Write-time per-row KV quantization for precision escalation.

    ``x`` [B, ...] is a freshly computed K or V tensor about to land in a
    shared f32 pool; ``levels`` [B] int32 picks each row's rung in the
    static ``esc_fmts`` ladder (narrow -> wide).  Every rung is snapped to
    its grid with the SATURATING cast (overflow clamps to ±max_normal
    instead of ±Inf — the stored value stays finite so attention never
    poisons, while the OF flag still fires and feeds the escalation
    pressure).  Returns ``(y, counts)`` with ``counts`` [B, 2] the per-row
    OF / UF flag totals of this write (FPnew fflags at the CONV stage,
    §II.B) — the select over rungs is traced, so changing a row's level
    never retraces."""
    from ..kernels.quant_common import quantize_flag_masks
    x = x.astype(jnp.float32)
    ys, ofs, ufs = [], [], []
    for fmt in esc_fmts:
        y, of, uf, _, _ = quantize_flag_masks(x, fmt, saturate=True)
        ys.append(y)
        ofs.append(of)
        ufs.append(uf)
    lvl = levels.reshape((-1,) + (1,) * (x.ndim - 1))
    y, of, uf = ys[-1], ofs[-1], ufs[-1]
    for i in range(len(esc_fmts) - 2, -1, -1):
        sel = lvl == i
        y = jnp.where(sel, ys[i], y)
        of = jnp.where(sel, ofs[i], of)
        uf = jnp.where(sel, ufs[i], uf)
    red = tuple(range(1, x.ndim))
    counts = jnp.stack([jnp.sum(of.astype(jnp.int32), axis=red),
                        jnp.sum(uf.astype(jnp.int32), axis=red)], axis=-1)
    return y, counts


def update_cache_rows(buf, new, pos, *, axis: int):
    """Write ``new`` into the cache ``buf`` at slot ``pos`` along ``axis``
    (both batch-leading).  A scalar ``pos`` writes one shared index (the
    uniform-batch fast path — identical to the old dynamic_update_slice);
    a per-row [B] vector writes each sequence at its OWN index (ragged
    decode: every row's cache grows at its own length)."""
    new = new.astype(buf.dtype)
    if not _is_vec(pos):
        start = [0] * buf.ndim
        start[axis] = pos
        return jax.lax.dynamic_update_slice(buf, new, tuple(start))

    def one(bb, nn, pp):
        start = [0] * bb.ndim
        start[axis - 1] = pp
        return jax.lax.dynamic_update_slice(bb, nn, tuple(start))

    return jax.vmap(one)(buf, new, pos)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def gqa_params(key, d_model, n_heads, n_kv_heads, head_dim, dtype,
               qk_norm: bool = False, out_bias: bool = False):
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


def _use_pallas_prefill(backend: str, q_offset=0) -> bool:
    """Route prefill/train attention through the pruned-grid Pallas kernel?
    ``q_offset`` must then be a concrete int (a static kernel arg that
    shapes the block schedule): a traced offset raises instead of quietly
    serving the dense path under a Pallas backend."""
    if backend == "dense":
        return False
    from ..kernels.ops import resolve_backend
    if resolve_backend(backend) != "pallas":
        return False
    if not isinstance(q_offset, int):
        raise ValueError(
            f"Pallas prefill needs a static int q_offset (it shapes the "
            f"kernel's block schedule), got {type(q_offset).__name__}")
    return True


def _flash_attend(q, k, v, policy, *, causal, window, cap, q_offset=0,
                  kv_len=None):
    """q [B,H,S,Dh] vs k/v [B,Hkv,T,Dk/Dv] -> [B,H,S,Dv] via the pruned-grid
    Pallas flash-attention kernel (kernels/flash_attention.py): causal future
    blocks and blocks left of the sliding window are never visited, so the
    windowed-slice trick of ``_masked_softmax_attend`` is subsumed by the
    block schedule itself.  ``kv_len`` (scalar or per-sequence [B] vector)
    additionally prunes each row's KV walk at its own live length in-kernel
    (ragged prefill batches)."""
    from ..kernels import ops as kops
    return kops.flash_attention(q, k, v, kv_len=kv_len, policy=policy,
                                scale=q.shape[-1] ** -0.5, causal=causal,
                                window=window, softcap=cap, q_offset=q_offset)


def _flash_attend_paged(q, cache: PagedKVCache, policy, *, causal, window,
                        cap, q_offset, kv_len):
    """Prefill reads against a PAGED cache: q [B,H,S,Dh] against the page
    pools of ``cache`` through its block table — the flash kernel
    dereferences the table in its BlockSpec index maps
    (``kernels.ops.flash_attention(block_table=)``), with ``bk`` pinned to
    the page size (the page IS the KV block).  This is the chunked-prefill
    read path: ``q_offset`` is the chunk's start position in the row and
    ``kv_len`` the row's total live length (prefix + this chunk), so a
    continuation chunk attends every earlier chunk's K/V straight out of
    the pool, no contiguous view ever materialized."""
    from ..kernels import ops as kops
    return kops.flash_attention(q, cache.k_pool, cache.v_pool, kv_len=kv_len,
                                block_table=cache.block_table, policy=policy,
                                scale=q.shape[-1] ** -0.5, causal=causal,
                                window=window, softcap=cap, q_offset=q_offset)


def _masked_softmax_attend(q, k, v, policy, *, causal, window, cap,
                           q_offset, kv_len=None, chunk=512,
                           windowed_slice=False):
    """q [B,H,S,Dh] vs k/v [B,Hkv,T,Dh] -> [B,H,S,Dh]; scan over q chunks.

    ``windowed_slice`` (beyond-paper perf knob): for sliding-window layers,
    each query chunk attends only to the KV slice its window can reach —
    compute drops from O(S*T) to O(S*(window+chunk)).  The baseline
    computes full dense scores and masks (what the paper-faithful chunked
    schedule does).

    ``kv_len``: scalar (one live length for the batch) or a per-sequence
    [B] vector (ragged batch — each row masks keys past its OWN length)."""
    b, h, s, dh = q.shape
    _, hkv, t, _ = k.shape
    group = h // hkv
    scale = dh ** -0.5
    kv_len = _len_rows(t if kv_len is None else kv_len)    # [1] or [B]
    qg = q.reshape(b, hkv, group, s, dh)
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    qc = jnp.moveaxis(qg.reshape(b, hkv, group, n_chunks, chunk, dh), 3, 0)

    # KV slice width per chunk when window-sliced (128-aligned)
    use_slice = (windowed_slice and window is not None and causal
                 and q_offset == 0 and window + chunk < t)
    w_eff = min(-(-(window + chunk) // 128) * 128, t) if use_slice else t
    if use_slice:
        # broadcast KV to full heads ONCE, outside the chunk loop, so each
        # chunk's slice + einsums are collective-free on a head-sharded
        # layout (GQA's kv-head count rarely divides the model axis; the
        # baseline pays that reshard once per layer — paying it per chunk
        # would dominate, measured in §Perf iteration B_j1)
        kf = shard(jnp.repeat(k, group, axis=1), bspec("model", None, None))
        vf = shard(jnp.repeat(v, group, axis=1), bspec("model", None, None))
        qf = qc.reshape(n_chunks, b, h, chunk, dh)      # [nc,B,H,c,Dh]

    def attend_chunk(ci, qi):
        if use_slice:
            # qi: [B,H,c,Dh]; KV slice is local to every device
            start = jnp.clip(ci * chunk + chunk - w_eff, 0, t - w_eff)
            ks = jax.lax.dynamic_slice_in_dim(kf, start, w_eff, axis=2)
            vs = jax.lax.dynamic_slice_in_dim(vf, start, w_eff, axis=2)
            k_idx = start + jnp.arange(w_eff)
            scores = tp.tp_einsum("bhcd,bhtd->bhct", qi, ks, policy,
                                  out_fmt="fp32") * scale
        else:
            ks, vs = k, v
            k_idx = jnp.arange(t)
            scores = tp.tp_einsum("bhgcd,bhtd->bhgct", qi, ks, policy,
                                  out_fmt="fp32") * scale
        scores = softcap(scores, cap)
        q_idx = q_offset + ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, k_idx.shape[0]), bool)
        if causal:
            mask = mask & (q_idx[:, None] >= k_idx[None, :])
        if window is not None:
            mask = mask & ((q_idx[:, None] - k_idx[None, :]) < window)
        # per-row live length ([1] broadcasts = the uniform case): combined
        # with the static masks at [B?, 1, (1,) chunk, t] rank
        lmask = k_idx[None, :] < kv_len[:, None]            # [1 or B, t]
        if use_slice:
            scores = jnp.where(mask[None, None]
                               & lmask[:, None, None, :], scores, NEG_INF)
        else:
            scores = jnp.where(mask[None, None, None]
                               & lmask[:, None, None, None, :],
                               scores, NEG_INF)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - jnp.where(m <= NEG_INF / 2, 0.0, m))
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        if use_slice:
            return tp.tp_einsum("bhct,bhtd->bhcd", p, vs, policy,
                                out_fmt="fp32")
        return tp.tp_einsum("bhgct,bhtd->bhgcd", p, vs, policy,
                            out_fmt="fp32")

    dv = v.shape[-1]
    if use_slice:
        out = jax.lax.map(lambda args: attend_chunk(*args),
                          (jnp.arange(n_chunks), qf))
        out = jnp.moveaxis(out, 0, 2).reshape(b, h, n_chunks * chunk, dv)
        return out[..., :s, :]
    out = jax.lax.map(lambda args: attend_chunk(*args),
                      (jnp.arange(n_chunks), qc))
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, group, n_chunks * chunk, dv)
    return out[..., :s, :].reshape(b, h, s, dv)


class KVCache(NamedTuple):
    k: jnp.ndarray  # [B, Hkv, Smax, Dh]
    v: jnp.ndarray


# ---------------------------------------------------------------------------
# tensor-parallel head sharding (mesh "model" axis)
# ---------------------------------------------------------------------------
def _head_shard_size(mesh, n_heads, n_kv_heads, axis: str = "model"):
    """Tensor-parallel degree for head-sharded attention, or ``None`` for
    the single-device path: requires a mesh with a ``model`` axis of size
    > 1 that divides BOTH the query and KV head counts (every shard gets
    whole heads of each — GQA groups never straddle a shard)."""
    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        return None
    size = mesh.shape[axis]
    if size <= 1 or n_heads % size or n_kv_heads % size:
        return None
    return size


def _headshard_call(mesh, fn, q, head_ops=(), rep_ops=(),
                    axis: str = "model"):
    """Run ``fn(q, *head_ops, *rep_ops)`` under ``shard_map`` with the
    head axis (axis 1 of q and of every ``head_ops`` operand — q, K/V,
    caches and page pools all carry heads there) partitioned over the
    mesh ``axis``; ``rep_ops`` (block tables, kv_len vectors) are
    replicated.  Per-head attention outputs are independent, so the
    out-spec concatenation over heads is BIT-IDENTICAL to the unsharded
    call — the kernel bodies run unchanged on their head slice.

    Every traced operand must be passed explicitly (shard_map closures
    must not capture tracers); ``fn`` may capture only static
    configuration (policy, window, softcap, static q_offset...)."""
    hs = P(None, axis, None, None)
    in_specs = (hs,) * (1 + len(head_ops)) + (P(),) * len(rep_ops)
    f = jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=hs,
                      axis_names=set(mesh.axis_names), check_vma=False)
    return f(q, *head_ops, *rep_ops)


def _row_parallel_wo(mesh, out, wo, policy, axis: str = "model"):
    """Row-parallel output projection: ``out`` [B, S, H*Dv] arrives
    head-major from the head-sharded attend (shard k owns the contiguous
    feature block of its heads), ``wo`` [H*Dv, D] is split over the same
    rows, and the partial products ``psum`` over ``axis``.  This is the
    bit-exactness boundary: per-head attend outputs are bitwise, the
    psum's reduction order is not — projections match the single-device
    path to fp32 allclose.

    Each shard's partial product stays fp32 through the psum; the
    policy's accumulate/output format snap is applied ONCE to the full
    sum (exactly where the single-device ``tp_einsum`` applies it) — a
    per-shard snap would quantize the partials themselves and drift by a
    whole output-format ulp instead of fp32 reduction-order noise."""
    def body(o, w):
        return jax.lax.psum(
            tp.tp_einsum("bse,ed->bsd", o, w, policy, out_fmt="fp32"), axis)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, None, axis), P(axis, None)),
                      out_specs=P(), axis_names=set(mesh.axis_names),
                      check_vma=False)
    r = f(out, wo)
    pol = tp.get_policy(policy)
    mp = pol.matmul
    out_f = mp.resolved_out()
    if pol.mode == "native":
        return r.astype(out_f.native_dtype)
    if mp.acc_fmt.name != "fp32":
        r = tp.quantize_ste(r, mp.acc_fmt, pol.rounding)
    if out_f.name != "fp32":
        r = tp.quantize_ste(r, out_f, pol.rounding)
    return r


def gqa_attention(x, params, policy, *, n_heads, n_kv_heads, head_dim,
                  positions, causal=True, window=None, attn_softcap=None,
                  rope_theta=1e4, qk_norm=False, norm_eps=1e-6,
                  cache: Optional[KVCache] = None,
                  cache_pos: Optional[jnp.ndarray] = None,
                  kv_states=None, use_rope=True, chunk: int = 512,
                  windowed_slice: bool = False,
                  decode_backend: str = "dense",
                  prefill_backend: str = "dense",
                  kv_len=None, esc_fmts=None, kv_levels=None,
                  kv_scale=None, mesh=None, return_attend: bool = False,
                  verify: bool = False):
    """Returns (out [B,S,D], new_cache) — or (out, new_cache, kv_flags)
    when ``esc_fmts`` is given (the arity is static per trace).

    Train/prefill: cache None.  Decode: x is [B,1,D], cache holds Smax slots,
    cache_pos is the write index.  Cross-attention: kv_states provides
    encoder states (no cache update, no rope).

    Ragged batches: ``kv_len`` (scalar or per-sequence [B] vector) masks
    keys past each row's live length — in prefill it is the per-row prompt
    length; in decode it overrides the default ``cache_pos + s`` (EOS-frozen
    rows keep a fixed live length).  ``cache_pos`` may likewise be a [B]
    vector: each row's K/V is then written at its OWN cache index.

    Paged cache: ``cache`` may be a ``paged.PagedKVCache`` (shared page
    pools + per-row block table) instead of a contiguous ``KVCache``.
    Writes scatter through the table (``paged_update_rows``); reads —
    decode AND prefill — dereference it in the Pallas kernels' index maps
    (or gather, on the dense fallback).  Paged prefill is write-then-read:
    the chunk's K/V lands in the pool first and attention reads it back
    through the table, so a chunked continuation (``cache_pos`` = the
    chunk's start offset, ``kv_len`` = prefix + chunk live length) is the
    same code path as a fresh prompt.

    Escalation write path: ``esc_fmts`` (static tuple of FPFormat rungs,
    narrow -> wide) + ``kv_levels`` ([B] int32 per-row rung) route every
    self-attention cache write through ``quantize_kv_rows`` — K/V are
    snapped to each row's rung with the saturating cast before landing in
    the (f32) pool, and the per-row OF/UF write-flag counts come back as a
    third return value ``kv_flags`` [B, 2].  ``kv_scale`` (traced scalar,
    default off) multiplies K/V pre-quantization — the fault-injection
    hook that forces narrow-rung overflow on demand.

    Speculative verify: ``verify=True`` with ``s > 1`` and a cache is the
    multi-query verify read mode.  The chunk's K/V is written first
    (chunk-form writes are bit-identical to the step-form writes plain
    decode performs), then the s query positions FOLD INTO THE BATCH
    dimension — ``kv_len`` must be a [B, S] matrix of per-query live
    lengths (query i of row b attends ``kv_len[b, i]`` slots) — and the
    folded [B*S] pseudo-batch takes the EXACT decode attend path
    (``_decode_attend`` / ``_decode_attend_paged``, dense or Pallas).
    Decode attend is per-row independent, so each folded query's output
    is bitwise what a sequential decode step at that position would
    produce: speculative verification inherits bit-parity with plain
    decode by construction instead of by numerical accident.  Block
    tables are tiled per query (the pool is shared); contiguous caches
    are repeated along batch.

    Tensor parallelism: ``mesh`` with a ``model`` axis whose size divides
    both head counts runs every attend (dense AND Pallas, prefill AND
    decode, contiguous AND paged) under ``shard_map`` on its head slice —
    bit-identical per head to the single-device path — and the output
    projection row-parallel with a ``psum`` (fp32-allclose; see
    ``_row_parallel_wo``).  Cache writes stay outside the shard_map
    regions (the pool arrays carry their own shardings); block tables and
    ``kv_len`` are replicated.  An absent/size-1/indivisible axis falls
    back to the unsharded path.  ``return_attend=True`` (debug/test hook)
    returns the pre-projection per-head attend output [B, H, S, Dv]
    instead of the projected residual contribution.
    """
    b, s, d = x.shape
    with jax.named_scope("attn.proj"):
        q = tp.tp_einsum("bsd,de->bse", x, params["wq"], policy)
        q = q.reshape(b, s, n_heads, head_dim)
        kv_src = kv_states if kv_states is not None else x
        t = kv_src.shape[1]
        k = tp.tp_einsum("bsd,de->bse", kv_src, params["wk"], policy)
        v = tp.tp_einsum("bsd,de->bse", kv_src, params["wv"], policy)
        k = k.reshape(b, t, n_kv_heads, head_dim)
        v = v.reshape(b, t, n_kv_heads, head_dim)

        if qk_norm:
            q = rmsnorm(q, params["q_norm"], norm_eps)
            k = rmsnorm(k, params["k_norm"], norm_eps)
        if use_rope:
            kv_pos = positions if kv_states is None else jnp.arange(t)
            q = apply_rope(q.swapaxes(1, 2), positions,
                           rope_theta).swapaxes(1, 2)
            k = apply_rope(k.swapaxes(1, 2), kv_pos,
                           rope_theta).swapaxes(1, 2)

        q = shard(q.swapaxes(1, 2), bspec("model", None, None))
        k = shard(k.swapaxes(1, 2), bspec("model", None, None))
        v = shard(v.swapaxes(1, 2), bspec("model", None, None))

    tp_size = _head_shard_size(mesh, n_heads, n_kv_heads)

    def _attend(fn, head_ops=(), rep_ops=(), q_op=None):
        qq = q if q_op is None else q_op
        with jax.named_scope("attn.kernel"):
            if tp_size is None:
                return fn(qq, *head_ops, *rep_ops)
            return _headshard_call(mesh, fn, qq, head_ops, rep_ops)

    new_cache = None
    kv_flags = jnp.zeros((b, 2), jnp.int32)  # OF, UF write counts per row
    if kv_states is not None:
        # cross-attention: optionally persist the encoder K/V into the
        # cache (prefill), attend non-causally over all encoder states.
        if cache is not None:
            cdt = cache.k.dtype
            new_cache = KVCache(
                jax.lax.dynamic_update_slice(cache.k, k.astype(cdt),
                                             (0, 0, 0, 0)),
                jax.lax.dynamic_update_slice(cache.v, v.astype(cdt),
                                             (0, 0, 0, 0)))
        out = _attend(
            lambda q_, k_, v_: _masked_softmax_attend(
                q_, k_, v_, policy, causal=False, window=None,
                cap=attn_softcap, q_offset=0, chunk=chunk),
            head_ops=(k, v))
    elif cache is not None:
        paged = isinstance(cache, PagedKVCache)
        if esc_fmts is not None:
            if kv_scale is not None:
                k = k * kv_scale
                v = v * kv_scale
            k, kf = quantize_kv_rows(k, esc_fmts, kv_levels)
            v, vf = quantize_kv_rows(v, esc_fmts, kv_levels)
            kv_flags = kf + vf
        with jax.named_scope("kv.write"):
            if paged:
                # paged cache: K/V scatter through the block table into the
                # shared page pool instead of a per-row contiguous strip
                new_cache = PagedKVCache(
                    paged_update_rows(cache.k_pool, cache.block_table, k,
                                      cache_pos),
                    paged_update_rows(cache.v_pool, cache.block_table, v,
                                      cache_pos),
                    cache.block_table)
            else:
                ck = update_cache_rows(cache.k, k, cache_pos, axis=2)
                cv = update_cache_rows(cache.v, v, cache_pos, axis=2)
                new_cache = KVCache(ck, cv)
        if verify and s > 1:
            # speculative verify: fold the s chunk queries into the batch
            # dimension and take the exact decode read path — query i of
            # row b becomes pseudo-row b*s+i attending kv_len[b, i] slots
            # of row b's (just-updated) cache.  Decode attend is per-row
            # independent, so every folded query is bitwise identical to
            # the sequential decode step at its position; slots at or past
            # a query's kv_len (later chunk positions, rejected drafts)
            # are masked dead exactly as in plain decode.
            kvl = jnp.reshape(jnp.asarray(kv_len, jnp.int32), (b * s,))
            qv = q.swapaxes(1, 2).reshape(b * s, n_heads, head_dim)[
                :, :, None, :]
            if paged:
                bt = jnp.repeat(new_cache.block_table, s, axis=0)
                out = _attend(
                    lambda q_, kp, vp, bt_, lv: _decode_attend_paged(
                        q_, PagedKVCache(kp, vp, bt_), policy, kv_len=lv,
                        window=window, cap=attn_softcap,
                        backend=decode_backend),
                    head_ops=(new_cache.k_pool, new_cache.v_pool),
                    rep_ops=(bt, kvl), q_op=qv)
            else:
                ckr = jnp.repeat(ck, s, axis=0)
                cvr = jnp.repeat(cv, s, axis=0)
                out = _attend(
                    lambda q_, k_, v_, lv: _decode_attend(
                        q_, k_, v_, policy, kv_len=lv, window=window,
                        cap=attn_softcap, backend=decode_backend),
                    head_ops=(ckr, cvr), rep_ops=(kvl,), q_op=qv)
            out = out.reshape(b, s, n_heads, head_dim).swapaxes(1, 2)
        elif s > 1 and paged:
            # paged prefill attends THROUGH the pool just written
            # (write-then-read) instead of the freshly computed k/v: the
            # same read path a chunked continuation takes, so chunk
            # boundaries are invisible and decode later dereferences
            # exactly what prefill attended.  ``kv_len`` is each row's
            # TOTAL live length (prefix + this chunk's live tail);
            # ``cache_pos`` is the chunk's static query offset.  Pallas
            # keeps the indirection down to the kernel's index maps; the
            # dense fallback gathers the pool (pure data movement, so it
            # is bit-identical to attending the contiguous values).
            live = jnp.asarray(
                kv_len if kv_len is not None else cache_pos + s, jnp.int32)
            if _use_pallas_prefill(prefill_backend, cache_pos):
                out = _attend(
                    lambda q_, kp, vp, bt, lv: _flash_attend_paged(
                        q_, PagedKVCache(kp, vp, bt), policy, causal=causal,
                        window=window, cap=attn_softcap, q_offset=cache_pos,
                        kv_len=lv),
                    head_ops=(new_cache.k_pool, new_cache.v_pool),
                    rep_ops=(new_cache.block_table, live))
            else:
                out = _attend(
                    lambda q_, kp, vp, bt, lv: _masked_softmax_attend(
                        q_, gather_paged_kv(kp, bt), gather_paged_kv(vp, bt),
                        policy, causal=causal, window=window,
                        cap=attn_softcap, q_offset=cache_pos, chunk=chunk,
                        kv_len=lv, windowed_slice=windowed_slice),
                    head_ops=(new_cache.k_pool, new_cache.v_pool),
                    rep_ops=(new_cache.block_table, live))
        elif s > 1:
            # prefill: the prompt itself is the entire live cache content —
            # attend over the *current* k/v, not the cache buffer (kv_len
            # carries the per-row prompt lengths of a ragged batch).
            lv_ops = (() if kv_len is None
                      else (jnp.asarray(kv_len, jnp.int32),))
            if _use_pallas_prefill(prefill_backend, cache_pos):
                out = _attend(
                    lambda q_, k_, v_, *lv: _flash_attend(
                        q_, k_, v_, policy, causal=causal, window=window,
                        cap=attn_softcap, q_offset=cache_pos,
                        kv_len=lv[0] if lv else None),
                    head_ops=(k, v), rep_ops=lv_ops)
            else:
                out = _attend(
                    lambda q_, k_, v_, *lv: _masked_softmax_attend(
                        q_, k_, v_, policy, causal=causal, window=window,
                        cap=attn_softcap, q_offset=cache_pos, chunk=chunk,
                        kv_len=lv[0] if lv else None,
                        windowed_slice=windowed_slice),
                    head_ops=(k, v), rep_ops=lv_ops)
        else:
            if kv_len is None:
                kv_len = cache_pos + s     # [B] vector when cache_pos is one
            kvl = jnp.asarray(kv_len, jnp.int32)
            if paged:
                out = _attend(
                    lambda q_, kp, vp, bt, lv: _decode_attend_paged(
                        q_, PagedKVCache(kp, vp, bt), policy, kv_len=lv,
                        window=window, cap=attn_softcap,
                        backend=decode_backend),
                    head_ops=(new_cache.k_pool, new_cache.v_pool),
                    rep_ops=(new_cache.block_table, kvl))
            else:
                out = _attend(
                    lambda q_, k_, v_, lv: _decode_attend(
                        q_, k_, v_, policy, kv_len=lv, window=window,
                        cap=attn_softcap, backend=decode_backend),
                    head_ops=(ck, cv), rep_ops=(kvl,))
    else:
        lv_ops = (() if kv_len is None
                  else (jnp.asarray(kv_len, jnp.int32),))
        if _use_pallas_prefill(prefill_backend):
            out = _attend(
                lambda q_, k_, v_, *lv: _flash_attend(
                    q_, k_, v_, policy, causal=causal, window=window,
                    cap=attn_softcap, q_offset=0,
                    kv_len=lv[0] if lv else None),
                head_ops=(k, v), rep_ops=lv_ops)
        else:
            out = _attend(
                lambda q_, k_, v_, *lv: _masked_softmax_attend(
                    q_, k_, v_, policy, causal=causal, window=window,
                    cap=attn_softcap, q_offset=0, chunk=chunk,
                    kv_len=lv[0] if lv else None,
                    windowed_slice=windowed_slice),
                head_ops=(k, v), rep_ops=lv_ops)

    if return_attend:
        return out, new_cache

    with jax.named_scope("attn.proj"):
        out = out.swapaxes(1, 2).reshape(b, s, n_heads * head_dim)
        if tp_size is None:
            proj = tp.tp_einsum("bse,ed->bsd", out, params["wo"], policy)
        else:
            proj = _row_parallel_wo(mesh, out, params["wo"], policy)
        proj = shard(proj, residual_spec())
    if esc_fmts is not None:
        return proj, new_cache, kv_flags
    return proj, new_cache


def _decode_attend(q, ck, cv, policy, *, kv_len, window, cap,
                   backend: str = "dense"):
    """q [B,H,1,Dh] vs cache [B,Hkv,Smax,Dh].

    ``backend="pallas"`` routes through the fused decode-attention kernel
    (kernels/decode_attention.py): the cache stays in its narrow storage
    format until the in-kernel CONV->ADDMUL widening, and ``kv_len`` is a
    dynamic kernel input so scan-based generation never retraces.
    ``kv_len`` may be a per-sequence [B] vector (ragged batch): the kernel
    early-exits each row's KV loop at its own length; the dense path masks
    per row.  ``backend="auto"`` resolves via
    ``kernels.ops.resolve_backend`` (pallas off-CPU only — shared with the
    prefill path)."""
    if backend != "dense":
        from ..kernels import ops as kops
        if kops.resolve_backend(backend) == "pallas":
            return kops.decode_attention(q, ck, cv, kv_len=kv_len,
                                         policy=policy, window=window,
                                         softcap=cap)
    b, h, s, dh = q.shape
    _, hkv, smax, _ = ck.shape
    group = h // hkv
    qg = q.reshape(b, hkv, group * s, dh)
    scores = tp.tp_einsum("bhqd,bhtd->bhqt", qg, ck, policy,
                          out_fmt="fp32") * (dh ** -0.5)
    scores = softcap(scores, cap)
    idx = jnp.arange(smax)
    kvl = _len_rows(kv_len)[:, None]                    # [1 or B, 1]
    mask = idx[None, :] < kvl
    if window is not None:
        mask = mask & (idx[None, :] > kvl - 1 - window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    # fully-masked rows (kv_len == 0, an empty ragged-batch slot): emit
    # zeros like the kernel's l == 0 store guard, not a uniform softmax
    # over dead cache slots
    p = p * jnp.any(mask, axis=-1).astype(p.dtype)[:, None, None, None]
    out = tp.tp_einsum("bhqt,bhtd->bhqd", p, cv, policy, out_fmt="fp32")
    return out.reshape(b, h, s, dh)


def _decode_attend_paged(q, cache: PagedKVCache, policy, *, kv_len, window,
                         cap, backend: str = "dense"):
    """Paged decode attention: q [B,H,1,Dh] against the page pools of
    ``cache`` through its block table.

    ``backend="pallas"`` keeps the indirection all the way down — the
    fused decode kernel's BlockSpec index maps dereference the table at
    DMA time, and no contiguous view is ever materialized (THE paged win:
    HBM traffic per row is its own page run).  The dense fallback gathers
    pages back into the contiguous layout first (pure data movement, so it
    is bit-identical to contiguous dense attention on the same values) —
    the CPU correctness path, not a serving path."""
    if backend != "dense":
        from ..kernels import ops as kops
        if kops.resolve_backend(backend) == "pallas":
            return kops.decode_attention(
                q, cache.k_pool, cache.v_pool, kv_len=kv_len,
                block_table=cache.block_table, policy=policy, window=window,
                softcap=cap)
    return _decode_attend(q, gather_paged_kv(cache.k_pool, cache.block_table),
                          gather_paged_kv(cache.v_pool, cache.block_table),
                          policy, kv_len=kv_len, window=window, cap=cap,
                          backend="dense")


def init_kv_cache(batch, n_kv_heads, max_len, head_dim, dtype):
    shape = (batch, n_kv_heads, max_len, head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def cross_attend_cached(x, params, cache: KVCache, policy, *, n_heads,
                        n_kv_heads, head_dim):
    """Decode-time cross-attention against fully-populated cached K/V
    (whisper decoder: the encoder states never change during decoding)."""
    b, s, d = x.shape
    q = tp.tp_einsum("bsd,de->bse", x, params["wq"], policy)
    q = q.reshape(b, s, n_heads, head_dim).swapaxes(1, 2)
    out = _decode_attend(q, cache.k, cache.v, policy,
                         kv_len=cache.k.shape[2], window=None, cap=None)
    out = out.swapaxes(1, 2).reshape(b, s, n_heads * head_dim)
    return tp.tp_einsum("bse,ed->bsd", out, params["wo"], policy)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------
class MLACache(NamedTuple):
    c_kv: jnp.ndarray   # [B, Smax, kv_lora]
    k_pe: jnp.ndarray   # [B, Smax, rope_dim]


def mla_params(key, d_model, n_heads, *, q_lora, kv_lora, nope_dim, rope_dim,
               v_head_dim, dtype):
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": dense_init(ks[0], d_model, kv_lora, dtype),
        "w_kr": dense_init(ks[1], d_model, rope_dim, dtype),
        "kv_norm": jnp.zeros((kv_lora,), dtype),
        "w_uk": dense_init(ks[2], kv_lora, n_heads * nope_dim, dtype),
        "w_uv": dense_init(ks[3], kv_lora, n_heads * v_head_dim, dtype),
        "wo": dense_init(ks[4], n_heads * v_head_dim, d_model, dtype),
    }
    if q_lora:
        p["w_dq"] = dense_init(ks[5], d_model, q_lora, dtype)
        p["q_norm"] = jnp.zeros((q_lora,), dtype)
        p["w_uq"] = dense_init(ks[6], q_lora, n_heads * (nope_dim + rope_dim),
                               dtype)
    else:
        p["w_q"] = dense_init(ks[5], d_model, n_heads * (nope_dim + rope_dim),
                              dtype)
    return p


def mla_attention(x, params, policy, *, n_heads, nope_dim, rope_dim,
                  v_head_dim, positions, rope_theta=1e4, norm_eps=1e-6,
                  cache: Optional[MLACache] = None,
                  cache_pos: Optional[jnp.ndarray] = None, chunk: int = 512,
                  prefill_backend: str = "dense", kv_len=None):
    """MLA with decoupled rope.  Prefill expands k/v; decode runs the
    absorbed form directly against the latent cache.  ``kv_len`` /
    ``cache_pos`` follow the gqa_attention ragged contract: scalar, or a
    per-sequence [B] vector (per-row length masking and per-row latent
    cache write indices)."""
    b, s, d = x.shape
    qd = nope_dim + rope_dim

    if "w_dq" in params:
        cq = tp.tp_einsum("bsd,dr->bsr", x, params["w_dq"], policy)
        cq = rmsnorm(cq, params["q_norm"], norm_eps)
        q = tp.tp_einsum("bsr,re->bse", cq, params["w_uq"], policy)
    else:
        q = tp.tp_einsum("bsd,de->bse", x, params["w_q"], policy)
    q = q.reshape(b, s, n_heads, qd)
    q_nope, q_pe = q[..., :nope_dim], q[..., nope_dim:]
    q_pe = apply_rope(q_pe.swapaxes(1, 2), positions, rope_theta).swapaxes(1, 2)

    c_kv = tp.tp_einsum("bsd,dr->bsr", x, params["w_dkv"], policy)
    c_kv = rmsnorm(c_kv, params["kv_norm"], norm_eps)
    k_pe = tp.tp_einsum("bsd,dr->bsr", x, params["w_kr"], policy)
    k_pe = apply_rope(k_pe[:, :, None], positions, rope_theta)[:, :, 0]

    scale = (nope_dim + rope_dim) ** -0.5

    new_cache = None
    if cache is not None:
        cc = update_cache_rows(cache.c_kv, c_kv, cache_pos, axis=1)
        cp = update_cache_rows(cache.k_pe, k_pe, cache_pos, axis=1)
        new_cache = MLACache(cc, cp)
    if cache is not None and s == 1:
        if kv_len is None:
            kv_len = cache_pos + s
        # absorbed decode: q_nope -> latent space via W_uk
        cc, cp = new_cache
        kv_lora = cc.shape[-1]
        w_uk = params["w_uk"].reshape(kv_lora, n_heads, nope_dim)
        q_lat = tp.tp_einsum("bshn,rhn->bshr", q_nope, w_uk, policy)
        smax = cc.shape[1]
        scores = (tp.tp_einsum("bshr,btr->bhst", q_lat, cc, policy,
                               out_fmt="fp32")
                  + tp.tp_einsum("bshr,btr->bhst", q_pe, cp, policy,
                                 out_fmt="fp32")) * scale
        mask = jnp.arange(smax)[None, :] < _len_rows(kv_len)[:, None]
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        # kv_len == 0 rows: zeros, not uniform weights over dead slots
        p = p * jnp.any(mask, axis=-1).astype(p.dtype)[:, None, None, None]
        o_lat = tp.tp_einsum("bhst,btr->bshr", p, cc, policy, out_fmt="fp32")
        w_uv = params["w_uv"].reshape(kv_lora, n_heads, v_head_dim)
        out = tp.tp_einsum("bshr,rhv->bshv", o_lat, w_uv, policy)
    else:
        # train / prefill (cache written above if present): expanded form
        k_nope = tp.tp_einsum("bsr,re->bse", c_kv, params["w_uk"], policy)
        k_nope = k_nope.reshape(b, s, n_heads, nope_dim)
        v = tp.tp_einsum("bsr,re->bse", c_kv, params["w_uv"], policy)
        v = v.reshape(b, s, n_heads, v_head_dim)
        k_pe_b = jnp.broadcast_to(k_pe[:, :, None], (b, s, n_heads, rope_dim))
        qq = jnp.concatenate([q_nope, q_pe], axis=-1).swapaxes(1, 2)
        kk = jnp.concatenate([k_nope, k_pe_b], axis=-1).swapaxes(1, 2)
        vv = v.swapaxes(1, 2)
        qq = shard(qq, bspec("model", None, None))
        kk = shard(kk, bspec("model", None, None))
        vv = shard(vv, bspec("model", None, None))
        if _use_pallas_prefill(prefill_backend):
            # the kernel supports Dv != Dqk directly (expanded MLA prefill)
            out = _flash_attend(qq, kk, vv, policy, causal=True, window=None,
                                cap=None, q_offset=0, kv_len=kv_len)
        else:
            # _masked_softmax_attend scales by qd**-0.5 internally == MLA
            out = _masked_softmax_attend(qq, kk, vv, policy, causal=True,
                                         window=None, cap=None, q_offset=0,
                                         chunk=chunk, kv_len=kv_len)
        out = out.swapaxes(1, 2)

    out = out.reshape(b, s, n_heads * v_head_dim)
    proj = tp.tp_einsum("bse,ed->bsd", out, params["wo"], policy)
    return shard(proj, residual_spec()), new_cache


def init_mla_cache(batch, max_len, kv_lora, rope_dim, dtype):
    return MLACache(jnp.zeros((batch, max_len, kv_lora), dtype),
                    jnp.zeros((batch, max_len, rope_dim), dtype))
