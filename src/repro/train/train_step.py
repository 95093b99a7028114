"""Train-step factory: model + optimizer + policy -> one jitted SPMD step.

The step is a pure function
    (params, opt_state, batch[, ef, sr_key]) ->
    (params, opt_state, metrics[, ef])
with explicit in/out shardings so the same factory serves the smoke tests
(1 device), the single-pod mesh (256) and the multi-pod mesh (512).

Distributed-optimization features (all policy/flag driven):
  * gradient compression (fp8/bf16 + stochastic rounding + error feedback):
    the whole fwd/bwd runs inside ``shard_map`` with the data axes manual
    (per-replica local gradients) and the model axis auto (GSPMD tensor
    parallelism); the data-parallel gradient sync is then an explicit psum
    whose wire payload is the narrow format — width-proportional ICI
    bytes, the paper's SIMD-lane insight applied to the dominant
    collective.  Error-feedback state is carried as a [n_dp, ...] buffer
    sharded over the data axes (each replica owns its slice).
  * ZeRO-1 optimizer-state sharding over ``data``,
  * remat (activation checkpointing) around each scanned layer group,
  * stochastic rounding when re-quantizing params from fp32 master.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.policy import PrecisionPolicy, get_policy
from ..models import sharding as shd
from ..models.layers import set_batch_axes
from ..models.transformer import Model
from ..optim import grad_compress
from ..optim.optimizer import OptConfig, apply_update, init_opt_state, \
    opt_state_specs

F32 = jnp.float32


def train_input_shardings(mesh, batch: int, dp_axes=("data",),
                          with_frontend=False):
    ba = shd.batch_spec_axes(batch, dp_axes, mesh)
    specs = {"tokens": P(ba, None), "labels": P(ba, None)}
    if with_frontend:
        specs["frontend_embeds"] = P(ba, None, None)
    return specs


def _dp_size(mesh, dp_axes):
    n = 1
    for a in dp_axes:
        n *= mesh.shape[a]
    return n


def init_error_feedback(params, mesh=None, dp_axes=()):
    """[n_dp, ...]-leading error-feedback buffers (one slice per replica)."""
    n = _dp_size(mesh, dp_axes) if mesh is not None else 1
    return jax.tree.map(lambda p: jnp.zeros((n,) + p.shape, F32), params)


def make_train_step(model: Model, opt_cfg: OptConfig, mesh, *,
                    dp_axes: Tuple[str, ...] = ("data",),
                    model_axis: str = "model",
                    compress_grads: Optional[str] = None,
                    remat: bool = True, aux_coef: float = 0.01,
                    loss_chunk: int = 1024):
    """Returns step(params, opt_state, batch[, ef][, key_data]) -> ... .

    ``compress_grads``: None (GSPMD all-reduce in the compute dtype) or a
    format name ('fp8', 'fp16alt') for the explicit compressed sync."""
    policy = model.policy
    use_compress = compress_grads is not None and mesh is not None
    use_key = use_compress or policy.stochastic_grad_round

    def loss_fn(params, batch):
        return model.forward_train(
            params, batch["tokens"], batch["labels"],
            frontend_embeds=batch.get("frontend_embeds"), mesh=mesh,
            remat=remat, aux_coef=aux_coef, loss_chunk=loss_chunk)

    if not use_compress:
        set_batch_axes(dp_axes)

        def step(params, opt_state, batch, key=None):
            if key is not None:
                key = jax.random.wrap_key_data(key)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, opt_state, metrics = apply_update(
                params, grads, opt_state, opt_cfg, policy, sr_key=key)
            metrics["loss"] = loss
            return params, opt_state, metrics

        return step

    # ---- compressed-gradient variant: grads computed per data replica ----
    set_batch_axes(())         # inside shard_map the batch dim is local
    n_dp = _dp_size(mesh, dp_axes)
    fmt = compress_grads

    def local_grad_body(params, batch, ef, key):
        """Runs with dp_axes manual, model axis auto.  ef leaves arrive as
        [1, ...] slices; key is a shared typed PRNG key."""
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        idx = jax.lax.axis_index(dp_axes)
        key = jax.random.fold_in(key, idx)  # decorrelate SR across replicas
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        ef_leaves = treedef.flatten_up_to(ef)
        keys = jax.random.split(key, len(leaves))
        synced, new_ef = [], []
        for g, e, kk in zip(leaves, ef_leaves, keys):
            s, e2 = grad_compress.compress_sync_local(
                g, e[0], axes=dp_axes, fmt=fmt, key=kk, n_replicas=n_dp)
            synced.append(s)
            new_ef.append(e2[None])
        loss = jax.lax.pmean(loss, dp_axes)
        return (loss, jax.tree_util.tree_unflatten(treedef, synced),
                jax.tree_util.tree_unflatten(treedef, new_ef))

    dpa = tuple(dp_axes)
    ef_spec = P(dpa)

    def step(params, opt_state, batch, ef, key):
        key = jax.random.wrap_key_data(key)
        kq, ksr = jax.random.split(key)
        batch_specs = {k: P(dpa, *([None] * (v.ndim - 1)))
                       for k, v in batch.items()}
        loss, grads, ef = jax.shard_map(
            local_grad_body, mesh=mesh,
            in_specs=(P(), batch_specs, ef_spec, P()),
            out_specs=(P(), P(), ef_spec),
            axis_names=set(dpa), check_vma=False,
        )(params, batch, ef, kq)
        params, opt_state, metrics = apply_update(
            params, grads, opt_state, opt_cfg, policy,
            sr_key=ksr if policy.stochastic_grad_round else None)
        metrics["loss"] = loss
        return params, opt_state, metrics, ef

    return step


def jit_train_step(model: Model, opt_cfg: OptConfig, mesh, *,
                   batch_size: int, seq_len: int = 4096, dp_axes=("data",),
                   model_axis="model", compress_grads=None, donate=True,
                   **kw):
    """Fully-sharded jit of the step for real execution or dry-run lowering.

    Returns (jitted, example_args_as_ShapeDtypeStructs, spec dict)."""
    cfg = model.cfg
    step = make_train_step(model, opt_cfg, mesh, dp_axes=dp_axes,
                           model_axis=model_axis,
                           compress_grads=compress_grads, **kw)
    msize = mesh.shape[model_axis]
    use_compress = compress_grads is not None
    use_key = use_compress or model.policy.stochastic_grad_round

    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    overrides = ({"embed": "rep", "lm_head": "rep"}
                 if model.cfg.embed_sharding == "replicated" else None)
    pspecs = shd.param_specs(params_shape, model_axis, msize,
                             overrides=overrides)
    opt_shape = jax.eval_shape(
        lambda p: init_opt_state(p, opt_cfg, model.policy), params_shape)
    ospecs = opt_state_specs(pspecs, opt_shape, zero_axis=dp_axes[-1],
                             mesh=mesh)
    bspecs = train_input_shardings(mesh, batch_size, dp_axes,
                                   with_frontend=cfg.frontend is not None)

    in_shardings = [shd.named(mesh, pspecs), shd.named(mesh, ospecs),
                    shd.named(mesh, bspecs)]
    out_shardings = [shd.named(mesh, pspecs), shd.named(mesh, ospecs), None]
    args = [params_shape, opt_shape,
            _batch_shapes(cfg, batch_size, seq_len)]
    if use_compress:
        ef_shape = jax.eval_shape(
            lambda p: init_error_feedback(p, mesh, dp_axes), params_shape)
        efspecs = jax.tree.map(
            lambda _: P(tuple(dp_axes)), ef_shape)
        in_shardings.append(shd.named(mesh, efspecs))
        out_shardings.append(shd.named(mesh, efspecs))
        args.append(ef_shape)
    if use_key:
        args.append(jax.ShapeDtypeStruct((2,), jnp.uint32))
        in_shardings.append(NamedSharding(mesh, P()))

    jitted = jax.jit(step,
                     in_shardings=tuple(in_shardings),
                     out_shardings=tuple(out_shardings),
                     donate_argnums=(0, 1) if donate else ())
    return jitted, tuple(args), {"params": pspecs, "opt": ospecs,
                                 "batch": bspecs}


def _batch_shapes(cfg, batch_size, seq_len=4096):
    shapes = {"tokens": jax.ShapeDtypeStruct((batch_size, seq_len),
                                             jnp.int32),
              "labels": jax.ShapeDtypeStruct((batch_size, seq_len),
                                             jnp.int32)}
    if cfg.frontend == "patch":
        shapes["frontend_embeds"] = jax.ShapeDtypeStruct(
            (batch_size, cfg.n_frontend_tokens, cfg.d_model), F32)
    elif cfg.frontend == "audio":
        shapes["frontend_embeds"] = jax.ShapeDtypeStruct(
            (batch_size, cfg.encoder.n_frames, cfg.d_model), F32)
    return shapes
