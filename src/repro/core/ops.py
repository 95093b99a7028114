"""Transprecision operations — FPnew's functional units as JAX ops.

Every op takes a :class:`PrecisionPolicy` and computes with the paper's
multi-format semantics:

  * ``tp_fma``     — expanding FMA ``dst fma(src a, src b, dst c)`` with a
                     single rounding into dst (paper §II.B.4, Fig 11e).
  * ``tp_matmul``/``tp_einsum`` — the same contract lifted to contractions:
                     operands in ``src_fmt``, accumulation in ``acc_fmt``
                     (MXU semantics), result stored in ``out_fmt``.
  * ``cast_and_pack`` — convert two scalar streams and pack them as vector
                     elements (paper §III.A.2c).
  * ``tp_cast``    — CONV block: format conversion with any rounding mode.
  * ``quantize_ste`` — straight-through-estimator quantization for training.

In ``native`` mode the ops emit real narrow dtypes (what a TPU executes and
what the roofline measures); in ``emulate`` mode they snap f32 containers to
the target grid bit-exactly (what the numerics tests validate).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from . import softfloat
from .formats import FPFormat, get_format
from .policy import MatmulPolicy, PrecisionPolicy, get_policy

__all__ = [
    "tp_cast", "quantize_ste", "tp_fma", "tp_matmul", "tp_einsum",
    "cast_and_pack", "tp_elementwise", "storage_dtype", "set_mixed_dot",
]

# Native-mode contractions emit true mixed-precision dots (bf16 x bf16 ->
# f32 via ``preferred_element_type``, the MXU's native expanding FMA) on
# every platform but the CPU.  XLA:CPU can *compile* these but its thunk
# runtime cannot execute every layout, so CPU execution upcasts the operands
# first (bit-identical results — narrow->f32 casts are exact).  ``None``
# lets the platform decide; the dry-run, which only lowers and compiles on
# CPU host devices, forces ``True`` so its HLO and cost analysis match what
# a TPU runs.
_MIXED_DOT: Optional[bool] = None


def set_mixed_dot(enable: Optional[bool]) -> None:
    global _MIXED_DOT
    _MIXED_DOT = enable


def _mixed_dot() -> bool:
    if _MIXED_DOT is not None:
        return _MIXED_DOT
    return jax.default_backend() != "cpu"


def storage_dtype(fmt, mode: str):
    """dtype used to store values of ``fmt`` under the given mode."""
    fmt = get_format(fmt)
    if mode == "native":
        assert fmt.native_dtype is not None, f"{fmt} has no native dtype"
        return fmt.native_dtype
    return fmt.container_dtype() if fmt.container_dtype() == jnp.float32 else jnp.float32


def tp_cast(x, fmt, policy=None, *, rounding: Optional[str] = None,
            key=None, saturate: bool = False):
    """CONV block: convert ``x`` to ``fmt`` under the policy's mode."""
    fmt = get_format(fmt)
    policy = get_policy(policy) if policy is not None else None
    mode = policy.mode if policy is not None else "native"
    rounding = rounding or (policy.rounding if policy is not None else "rne")
    if mode == "native":
        if rounding == "stochastic":
            # stochastic rounding has no native lowering — emulate the grid
            # then bitcast down (values are exactly representable)
            q = softfloat.quantize(jnp.asarray(x, jnp.float32), fmt,
                                   "stochastic", key=key, saturate=saturate)
            return q.astype(fmt.native_dtype)
        return jnp.asarray(x).astype(fmt.native_dtype)
    return softfloat.quantize(x, fmt, rounding, key=key, saturate=saturate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quantize_ste(x, fmt, rounding="rne"):
    """Quantize to ``fmt``'s grid with a straight-through gradient."""
    return softfloat.quantize(x, fmt, rounding)


def _ste_fwd(x, fmt, rounding):
    return softfloat.quantize(x, fmt, rounding), None


def _ste_bwd(fmt, rounding, _, g):
    return (g,)


quantize_ste.defvjp(_ste_fwd, _ste_bwd)


def tp_fma(a, b, c, policy, *, key=None):
    """Expanding FMA: multiply ``a*b`` in ``src_fmt`` (exact product),
    accumulate with ``c`` in ``acc_fmt`` with a single rounding.

    Emulation exactness: products of two src_fmt values are exactly
    representable in the f32 container whenever 2*p_src <= 24, which holds
    for all of the paper's sub-32-bit formats; the one rounding then happens
    in the quantize to acc_fmt (innocuous double rounding per Figueroa).
    """
    policy = get_policy(policy)
    mp = policy.matmul
    if policy.mode == "native":
        sa = a.astype(mp.src_fmt.native_dtype)
        sb = b.astype(mp.src_fmt.native_dtype)
        acc_dt = storage_dtype(mp.acc_fmt, "native")
        return (sa.astype(acc_dt) * sb.astype(acc_dt)
                + c.astype(acc_dt)).astype(acc_dt)
    qa = softfloat.quantize(a, mp.src_fmt, policy.rounding, key=key)
    qb = softfloat.quantize(b, mp.src_fmt, policy.rounding, key=key)
    prod = qa * qb  # exact in container
    return softfloat.quantize(prod + c, mp.acc_fmt, policy.rounding, key=key)


def tp_einsum(spec: str, a, b, policy, *, out_fmt=None, use_ste: bool = True,
              precision=None):
    """Contraction with multi-format FMA semantics.

    native : operands cast to src_fmt's dtype, dot with
             ``preferred_element_type`` = acc dtype (MXU expanding FMA),
             output cast to out_fmt.
    emulate: operands snapped to src_fmt grid (STE for training), f32
             accumulation (the acc grid for acc_fmt==fp32), output snapped.
    """
    policy = get_policy(policy)
    mp = policy.matmul
    out = get_format(out_fmt) if out_fmt is not None else mp.resolved_out()
    if policy.mode == "native":
        sa = a.astype(mp.src_fmt.native_dtype)
        sb = b.astype(mp.src_fmt.native_dtype)
        acc_dt = storage_dtype(mp.acc_fmt, "native")
        if policy.narrow_partials and out.width < mp.acc_fmt.width \
                and out.native_dtype is not None:
            # emit the dot with a narrow output element type: XLA's
            # cross-shard partial-sum all-reduce then runs in the narrow
            # format (per-tile MXU accumulation is still f32)
            acc_dt = out.native_dtype
        if _mixed_dot():
            r = jnp.einsum(spec, sa, sb, preferred_element_type=acc_dt,
                           precision=precision)
        else:
            r = jnp.einsum(spec, sa.astype(acc_dt), sb.astype(acc_dt),
                           precision=precision)
        return r.astype(out.native_dtype)
    q = quantize_ste if use_ste else (lambda x, f, r: softfloat.quantize(x, f, r))
    qa = q(a, mp.src_fmt, policy.rounding)
    qb = q(b, mp.src_fmt, policy.rounding)
    r = jnp.einsum(spec, qa, qb, preferred_element_type=jnp.float32,
                   precision=precision)
    # accumulate grid: f32 container accumulation == acc_fmt when acc is
    # fp32; narrower acc grids get a final snap (chunkwise-rounded model)
    if mp.acc_fmt.name != "fp32":
        r = q(r, mp.acc_fmt, policy.rounding)
    if out.name != "fp32":
        r = q(r, out, policy.rounding)
    return r


def tp_matmul(a, b, policy, *, out_fmt=None, use_pallas: bool = False,
              **kw):
    """2D+ matmul ``a @ b`` under the policy; optionally via the Pallas
    tp_matmul kernel (perf path)."""
    if use_pallas:
        from ..kernels import ops as kops
        return kops.tp_matmul(a, b, policy=get_policy(policy),
                              out_fmt=out_fmt, **kw)
    return tp_einsum("...ij,jk->...ik", a, b, policy, out_fmt=out_fmt, **kw)


def cast_and_pack(a, b, fmt, policy=None, *, axis: int = -1):
    """Paper §III.A.2c: convert two scalar operand streams to ``fmt`` and
    pack them as interleaved elements of the destination vector along
    ``axis``: ``out[.., 2i, ..] = a[.., i, ..]`` and ``out[.., 2i+1, ..] =
    b[.., i, ..]``, so ``out.shape[axis] == 2 * a.shape[axis]``."""
    fmt = get_format(fmt)
    qa = tp_cast(a, fmt, policy)
    qb = tp_cast(b, fmt, policy)
    axis = axis % qa.ndim
    stacked = jnp.stack([qa, qb], axis=axis + 1)
    shape = list(qa.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


# -- DIVSQRT / elementwise group --------------------------------------------
_ELEM_FNS = {
    "exp": jnp.exp, "log": jnp.log, "rsqrt": jax.lax.rsqrt,
    "sqrt": jnp.sqrt, "div": lambda a, b: a / b, "recip": lambda a: 1.0 / a,
    "tanh": jnp.tanh, "silu": jax.nn.silu, "gelu": jax.nn.gelu,
    "sigmoid": jax.nn.sigmoid,
}


def tp_elementwise(fn: str, *args, policy, out_fmt=None):
    """DIVSQRT-group op computed in ``elem_fmt`` (paper's iterative unit has
    a per-format precision knob; here the knob is the compute format)."""
    policy = get_policy(policy)
    ef = policy.elem_fmt
    if policy.mode == "native":
        cdt = storage_dtype(ef, "native")
        r = _ELEM_FNS[fn](*[jnp.asarray(x).astype(cdt) for x in args])
        if out_fmt is not None:
            r = r.astype(get_format(out_fmt).native_dtype)
        return r
    qargs = [softfloat.quantize(x, ef, policy.rounding) for x in args]
    r = softfloat.quantize(_ELEM_FNS[fn](*qargs), ef, policy.rounding)
    if out_fmt is not None:
        r = softfloat.quantize(r, out_fmt, policy.rounding)
    return r
