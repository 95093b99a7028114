"""Mixture-of-Experts with production expert parallelism.

Dispatch is the TPU-idiomatic *sort-based capacity* scheme (no [T, E, C]
one-hot tensors):

  1. router top-k -> flat (token, slot) -> expert assignments,
  2. stable argsort by expert, per-expert rank via run-starts,
  3. scatter into an [E, C, D] buffer — with the default drop-free
     capacity (``capacity_factor=None``) every assignment fits, so
     per-token outputs are batch-composition-invariant (serving needs
     this: chunked verify must equal sequential decode bitwise); an
     explicit finite factor restores training-style over-capacity drops,
  4. expert-parallel all_to_all over the ``model`` mesh axis (each data row
     exchanges expert slabs within itself; expert weights are sharded over
     ``model`` and replicated over ``data`` like every other weight),
  5. batched expert SwiGLU ([E_loc, M*C, D] x [E_loc, D, F]),
  6. reverse all_to_all, gather back, gate-weighted combine, unsort.

The same core runs without collectives when ``ep_axis`` is None (single
device smoke tests); the EP path is wrapped in shard_map by the caller.

Transprecision: expert matmuls follow the multi-format FMA policy; the
router runs in f32 (FPnew keeps the COMP group full-precision) — exactly
the per-op-group format split of paper §II.B.2.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import ops as tp
from ..core.policy import get_policy
from .layers import batch_axes, bspec, dense_init, residual_spec, shard


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    # None = drop-free dispatch (capacity >= n_tokens, so no expert can
    # overflow and no token is ever dropped).  Serving REQUIRES drop-free:
    # capacity scales with the total token count, so with a finite factor a
    # token's keep/drop decision depends on what else is in the batch — and
    # then chunked verify (B*(k+1) pseudo-rows) diverges from sequential
    # decode (B rows).  Set an explicit factor only for training-style
    # load-balancing experiments.
    capacity_factor: float | None = None
    router_norm_topk: bool = True   # normalize top-k gates to sum to 1


def moe_params(key, d_model, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_expert
    p = {
        "router": dense_init(ks[0], d_model, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d_model, f), jnp.float32)
                   * d_model ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d_model, f), jnp.float32)
                 * d_model ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d_model), jnp.float32)
                   * f ** -0.5).astype(dtype),
    }
    if cfg.n_shared:
        fs = cfg.n_shared * f
        km = jax.random.split(ks[4], 3)
        p["shared"] = {"gate": dense_init(km[0], d_model, fs, dtype),
                       "up": dense_init(km[1], d_model, fs, dtype),
                       "down": dense_init(km[2], fs, d_model, dtype)}
    return p


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    if cfg.capacity_factor is None:
        # Drop-free: each token assigns an expert at most once, so one
        # expert receives at most n_tokens rows.  cap >= n_tokens makes
        # per-token outputs independent of batch composition (bitwise).
        return max(8, -(-n_tokens // 8) * 8)
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)


def _expert_ffn(buf, w_gate, w_up, w_down, policy):
    """buf [E, C, D] -> [E, C, D] batched SwiGLU."""
    g = tp.tp_einsum("ecd,edf->ecf", buf, w_gate, policy)
    u = tp.tp_einsum("ecd,edf->ecf", buf, w_up, policy)
    h = tp.tp_elementwise("silu", g, policy=policy) * u
    return tp.tp_einsum("ecf,efd->ecd", h, w_down, policy)


def _route(x_flat, params, cfg: MoEConfig):
    """Router (f32; COMP group): top-k gates and experts per token, and
    the Switch-style load-balancing loss."""
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                            params["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)   # [T, k]
        if cfg.router_norm_topk:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        # aux load-balancing loss (Switch-style)
        me = probs.mean(axis=0)                        # mean prob per expert
        onehot_top1 = jax.nn.one_hot(idx[:, 0], cfg.n_experts)
        ce = onehot_top1.mean(axis=0)                  # dispatch fraction
        aux = cfg.n_experts * jnp.sum(me * ce)
    return gates, idx, aux


def _unsort_combine(rows, order, gates, dtype):
    """Expert outputs in sorted (expert-major) order [T*k, D] -> the
    gate-weighted sum per token [T, D]."""
    t, k = gates.shape
    unsort = jnp.argsort(order, stable=True)
    gathered = rows[unsort].reshape(t, k, rows.shape[-1])
    return jnp.einsum("tkd,tk->td", gathered.astype(jnp.float32),
                      gates.astype(jnp.float32)).astype(dtype)


def moe_core(x_flat, params, cfg: MoEConfig, policy, *,
             ep_axis: Optional[str] = None, ep_size: int = 1):
    """x_flat [T, D] -> (y [T, D], aux_loss scalar).

    When ``ep_axis`` is set, this runs *inside shard_map*: experts arrive
    sharded [E_loc, ...] and tokens are the per-device shard; all_to_all
    exchanges expert slabs across ``ep_axis``.
    """
    t, d = x_flat.shape
    e_total = cfg.n_experts
    e_loc = params["w_gate"].shape[0]     # == e_total/ep_size under EP
    k = cfg.top_k
    cap = _capacity(t, cfg)

    gates, idx, aux = _route(x_flat, params, cfg)

    # --- sort-based dispatch ----------------------------------------------
    with jax.named_scope("moe.dispatch"):
        flat_e = idx.reshape(-1)                       # [T*k]
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, jnp.arange(e_total), side="left")
        rank = jnp.arange(t * k) - first[sorted_e]
        keep = rank < cap
        slot = jnp.where(keep, sorted_e * cap + rank, e_total * cap)
        src_tok = order // k
        buf = jnp.zeros((e_total * cap + 1, d), x_flat.dtype)
        buf = buf.at[slot].set(x_flat[src_tok], mode="drop",
                               unique_indices=True)
        buf = buf[:-1].reshape(e_total, cap, d)

        # --- EP exchange ---------------------------------------------------
        # all_to_all(split=0, concat=0, tiled=False) swaps the leading
        # destination-shard axis for a source-shard axis in place.
        if ep_axis is not None and ep_size > 1:
            # [E, C, D] -> [M(dest), E_loc, C, D] -> a2a
            #   -> [M(src), E_loc, C, D]
            buf = buf.reshape(ep_size, e_loc, cap, d)
            buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            # -> [E_loc, M(src), C, D] -> [E_loc, M*C, D]
            buf = buf.swapaxes(0, 1).reshape(e_loc, ep_size * cap, d)

    with jax.named_scope("moe.experts"):
        out = _expert_ffn(buf, params["w_gate"], params["w_up"],
                          params["w_down"], policy)

    with jax.named_scope("moe.combine"):
        if ep_axis is not None and ep_size > 1:
            # [E_loc, M(src), C, D] -> [M(src=dest now), E_loc, C, D] -> a2a
            out = out.reshape(e_loc, ep_size, cap, d).swapaxes(0, 1)
            out = jax.lax.all_to_all(out, ep_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            # [M(expert-shard), E_loc, C, D] == [E, C, D], expert-major
            out = out.reshape(e_total * cap, d)
        else:
            out = out.reshape(e_total * cap, d)

        out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], axis=0)
        y = _unsort_combine(out[slot], order, gates, x_flat.dtype)

    return y, aux


def grouped_path(cfg: Optional[MoEConfig], policy, mesh) -> bool:
    """Whether serving runs the routed experts through the grouped kernel
    (``moe_core_grouped``) rather than the capacity einsum.  It follows
    what the program can observe: a compiled-kernel platform (not the
    CPU, as ``kernels.ops.resolve_backend("auto")``), drop-free dispatch
    (finite capacity's drops are part of its semantics), a native-mode
    policy (emulate mode snaps operands), and a single-device program (no
    expert-parallel or data axis to partition the kernel over)."""
    return (cfg is not None and cfg.capacity_factor is None
            and get_policy(policy).mode == "native"
            and (mesh is None or mesh.size == 1)
            and jax.default_backend() != "cpu")


def moe_core_grouped(x_flat, params, experts, cfg: MoEConfig, policy):
    """x_flat [T, D] -> (y [T, D], experts read): the serving path.

    ``experts`` is ``(w_gate, w_up, w_down, layer)``: the STACKED
    [L, E, D, F] / [L, E, F, D] expert weights and this layer's index
    into them, so that a caller inside the layer scan never slices one
    layer's experts out.  The T*k assignments, sorted by expert, run
    through ``kernels.grouped_ffn``, which reads the weights of only the
    experts that received a row; the count of those comes back with
    ``y``."""
    from ..kernels import ops as kops
    w_gate, w_up, w_down, layer = experts
    gates, idx, _ = _route(x_flat, params, cfg)
    with jax.named_scope("moe.dispatch"):
        flat_e = idx.reshape(-1)                       # [T*k]
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        rows = x_flat[order // cfg.top_k]              # [T*k, D] sorted
    with jax.named_scope("moe.experts"):
        out, n_read = kops.grouped_ffn(rows, sorted_e, w_gate, w_up,
                                       w_down, layer, policy=policy)
    with jax.named_scope("moe.combine"):
        y = _unsort_combine(out, order, gates, x_flat.dtype)
    return y, n_read


def moe_block(x, params, cfg: MoEConfig, policy, *, mesh=None,
              ep_axis: Optional[str] = "model", serving: bool = False,
              experts=None):
    """x [B, S, D] -> (y, aux).  Uses shard_map EP when a mesh with the
    ``ep_axis`` is provided (production path); plain local dispatch
    otherwise (tests / single device).

    ``serving=True`` (the serving entry points, which train nothing)
    makes ``aux`` the number of experts whose weights the layer read in
    place of the load-balancing loss: with ``experts`` (the stacked
    weights and layer index, see ``moe_core_grouped``; the caller passes
    them where ``grouped_path`` holds) only the routed experts, on the
    capacity einsum every expert."""
    b, s, d = x.shape
    y_shared = None
    if cfg.n_shared:
        from .layers import swiglu
        y_shared = swiglu(x, params["shared"]["gate"], params["shared"]["up"],
                          params["shared"]["down"], policy)

    xf = x.reshape(b * s, d)
    routed = {k: v for k, v in params.items() if k != "shared"}

    # under an explicit mesh the specs may only name ITS axes: a serving
    # replica's ("model",) sub-mesh has no "data" axis to batch-shard over
    ba = tuple(a for a in batch_axes()
               if mesh is None or a in mesh.axis_names)
    if mesh is not None and ep_axis in mesh.axis_names and \
            mesh.shape[ep_axis] > 1:
        ep = mesh.shape[ep_axis]
        espec = P(ep_axis)
        pspec = {"router": P(), "w_gate": espec, "w_up": espec,
                 "w_down": espec}

        def body(xb, pb):
            yb, auxb = moe_core(xb, pb, cfg, policy,
                                ep_axis=ep_axis, ep_size=ep)
            return yb, auxb.reshape((1,) * max(len(ba), 1))

        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(ba if ba else None), pspec),
            out_specs=(P(ba), P(*ba) if ba else P()),
            check_vma=False,
        )(xf, routed)
        aux = aux.mean()
    elif experts is not None:
        y, aux = moe_core_grouped(xf, routed, experts, cfg, policy)
    else:
        y, aux = moe_core(xf, routed, cfg, policy)
    if serving and experts is None:
        aux = jnp.asarray(cfg.n_experts, jnp.int32)

    y = y.reshape(b, s, d)
    y = shard(y, residual_spec())
    if y_shared is not None:
        y = y + y_shared
    return y, aux
