"""Pallas TPU kernel: grouped expert SwiGLU over expert-sorted rows.

A decode round of a mixture-of-experts model touches only the experts its
tokens route to, yet a dense ``[E, cap, D] x [E, D, F]`` einsum streams
every expert's weights.  In decode that is a weight-bandwidth cost, not a
FLOP cost: the round is bound by the bytes of all experts.  This kernel
visits only the experts that received at least one row.

Rows arrive sorted by expert (``x [N, D]``, ``expert_ids [N]``
non-decreasing), so each expert's rows are one contiguous group.  Rows are
cut into fixed tiles of ``TM``; the grid walks *visits*, one per (group,
row tile) pair a group overlaps, in order (megablox's group-metadata
pattern).  ``group_schedule`` builds the visit tables that drive the block
index maps through scalar prefetch:

  * a visit's weight blocks are ``(layer, expert, 0, 0)`` of the STACKED
    ``[L, E, D, F]`` / ``[L, E, F, D]`` weights — the layer index is a
    prefetched scalar, so a caller inside a layer scan passes the whole
    stack and XLA never materialises one layer's slice;
  * consecutive visits of one expert keep the same weight block index, and
    visits past the last live one repeat the last block indices, so Pallas
    issues no new DMA for them (``pl.when`` skips their compute): each
    active expert's weights are read exactly once;
  * the output tile stays resident across the visits that share it; each
    visit writes only its own group's rows.

Numerics follow the policy's native-mode contraction contract, as the
einsum path applies it: ``src`` operands, ``acc`` accumulation, ``g`` and
``u`` stored in ``out``, ``h = silu(g) * u`` in the elementwise format and
cast to ``src`` for the down projection, the result stored in ``out``.
Each row's result depends on that row alone: the tile height and the
contractions (whole ``D``, whole ``F``) are fixed, whatever the group
sizes, so a row is bitwise the same alone or inside any batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows per tile: the bf16 sublane tile (16, 128)
TM = 16


def group_schedule(expert_ids, n_experts: int, n_rows: int, tm: int = TM):
    """Visit tables for ``n_rows`` (a multiple of ``tm``) rows whose first
    ``len(expert_ids)`` rows carry the sorted ``expert_ids``; the rest are
    padding that no group owns.

    Returns ``(expert, tile, lo, hi, n_live, n_active)``: per visit the
    expert, the row tile, and the group's row range ``[lo, hi)``; the
    number of live visits; the number of active experts.  There are
    ``n_rows // tm + min(n_experts, n) - 1`` visits (each group boundary
    inside a tile adds one); visits past ``n_live`` repeat the last live
    one."""
    n = expert_ids.shape[0]
    n_groups = min(n_experts, n)
    n_visits = n_rows // tm + n_groups - 1
    e = expert_ids.astype(jnp.int32)
    starts_mask = jnp.concatenate([jnp.ones((1,), bool), e[1:] != e[:-1]])
    n_active = jnp.sum(starts_mask.astype(jnp.int32))
    start = jnp.nonzero(starts_mask, size=n_groups, fill_value=n)[0]
    end = jnp.concatenate([start[1:], jnp.full((1,), n, start.dtype)])
    expert = e[jnp.minimum(start, n - 1)]
    first = start // tm
    ntiles = jnp.where(jnp.arange(n_groups) < n_active,
                       (end - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(ntiles)
    n_live = cum[-1]
    v = jnp.minimum(jnp.arange(n_visits), n_live - 1)
    g = jnp.searchsorted(cum, v, side="right")
    tile = first[g] + v - (cum[g] - ntiles[g])
    return (expert[g], tile, start[g], end[g], jnp.reshape(n_live, (1,)),
            n_active)


def _kernel(layer_ref, ex_ref, tile_ref, lo_ref, hi_ref, nlive_ref,
            x_ref, wg_ref, wu_ref, wd_ref, o_ref, *rest, tm: int,
            src_dtype, acc_dtype, out_dtype, elem_dtype,
            debug_fetches: bool):
    v = pl.program_id(0)

    @pl.when(v < nlive_ref[0])
    def _visit():
        # operands in src (weights stored narrower widen exactly)
        x = x_ref[...].astype(src_dtype)
        wg, wu, wd = (r[0, 0].astype(src_dtype)
                      for r in (wg_ref, wu_ref, wd_ref))
        g = jnp.dot(x, wg, preferred_element_type=acc_dtype
                    ).astype(out_dtype)
        u = jnp.dot(x, wu, preferred_element_type=acc_dtype
                    ).astype(out_dtype)
        h = jax.nn.silu(g.astype(elem_dtype)) * u
        y = jnp.dot(h.astype(src_dtype), wd,
                    preferred_element_type=acc_dtype).astype(out_dtype)
        rows = (tile_ref[v] * tm
                + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0))
        mine = (rows >= lo_ref[v]) & (rows < hi_ref[v])
        o_ref[...] = jnp.where(mine, y, o_ref[...])

    if debug_fetches:
        # a weight fetch happens where the weight block index changes:
        # at the first visit and wherever the expert changes
        (f_ref,) = rest
        fetched = (v == 0) | (ex_ref[v] != ex_ref[jnp.maximum(v - 1, 0)])
        blk = f_ref[...]
        idx = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
        f_ref[...] = jnp.where(idx == v, fetched.astype(jnp.int32), blk)


@functools.partial(jax.jit, static_argnames=(
    "src_dtype", "acc_dtype", "out_dtype", "elem_dtype", "interpret",
    "debug_fetches"))
def grouped_ffn_pallas(x, expert_ids, w_gate, w_up, w_down, layer, *,
                       src_dtype=jnp.bfloat16,
                       acc_dtype=jnp.float32, out_dtype=jnp.bfloat16,
                       elem_dtype=jnp.float32, interpret: bool = True,
                       debug_fetches: bool = False):
    """x [N, D] rows sorted by ``expert_ids`` [N]; ``w_gate``/``w_up``
    [L, E, D, F], ``w_down`` [L, E, F, D]; ``layer`` a traced int scalar.

    Returns ``(y [N, D] out_dtype, n_active)``, and with ``debug_fetches``
    also an int32 [n_visits] array marking the visits at which the weight
    blocks change (their sum is the number of weight fetches)."""
    n, d = x.shape
    _, n_experts, _, f = w_gate.shape
    assert w_down.shape[-2:] == (f, d), (w_gate.shape, w_down.shape)
    n_rows = -(-n // TM) * TM
    if n_rows != n:
        x = jnp.pad(x, ((0, n_rows - n), (0, 0)))
    ex, tile, lo, hi, n_live, n_active = group_schedule(
        expert_ids, n_experts, n_rows)
    n_visits = ex.shape[0]
    scalars = (jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
               ex, tile, lo, hi, n_live)

    row_map = lambda v, l, ex, t, lo, hi, nl: (t[v], 0)
    w_map = lambda v, l, ex, t, lo, hi, nl: (l[0], ex[v], 0, 0)
    out_shape = [jax.ShapeDtypeStruct((n_rows, d), out_dtype)]
    out_specs = [pl.BlockSpec((TM, d), row_map)]
    if debug_fetches:
        out_shape.append(jax.ShapeDtypeStruct((n_visits, 1), jnp.int32))
        out_specs.append(pl.BlockSpec(
            (n_visits, 1), lambda v, l, ex, t, lo, hi, nl: (0, 0)))
    w_bytes = 3 * d * f * max(jnp.dtype(w_gate.dtype).itemsize,
                              jnp.dtype(src_dtype).itemsize)
    kern = functools.partial(
        _kernel, tm=TM, src_dtype=src_dtype, acc_dtype=acc_dtype,
        out_dtype=out_dtype, elem_dtype=elem_dtype,
        debug_fetches=debug_fetches)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_visits,),
            in_specs=[pl.BlockSpec((TM, d), row_map),
                      pl.BlockSpec((1, 1, d, f), w_map),
                      pl.BlockSpec((1, 1, d, f), w_map),
                      pl.BlockSpec((1, 1, f, d), w_map)],
            out_specs=out_specs),
        out_shape=out_shape,
        # the weight blocks of two visits in flight, plus row tiles
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(2 * w_bytes + (16 << 20))),
        interpret=interpret,
    )(*scalars, x, w_gate, w_up, w_down)
    y = out[0][:n]
    if debug_fetches:
        return y, n_active, out[1][:, 0]
    return y, n_active
