"""Shared in-kernel quantization primitives (FPnew CONV block).

Integer-space rounding of f32 containers onto an arbitrary (e, m) grid —
the bit-twiddling core used by every Pallas kernel that fuses a format
conversion into its datapath: tp_quant (standalone CONV), tp_matmul
(CONV->ADDMUL operand snap), and decode_attention (CONV->ADDMUL dequant of
the narrow KV cache inside the attention loop).

Hoisted here so kernels share one bit-exact implementation; the pure-jnp
oracle is ``softfloat.quantize`` + FTZ (see kernels/ref.py), and
tests/test_kernels.py pins the two against each other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.formats import FPFormat


def quantize_bits(x, rbits, fmt: FPFormat, stochastic: bool,
                  saturate: bool = False):
    """Integer-space rounding onto fmt's grid (normals; FTZ below min normal,
    matching the MXU input stage; softfloat.quantize keeps the gradual-
    underflow oracle).

    ``rbits`` is a uint32 array of x's shape supplying the stochastic
    addend; ignored (may be None) when ``stochastic`` is False.
    ``saturate=True`` clamps overflow to ±max_normal instead of ±Inf (the
    non-IEEE saturating CONV mode: a finite, degraded value instead of an
    Inf that poisons every downstream FMA).
    """
    m, emax, emin = fmt.m_bits, fmt.emax, fmt.emin
    s = 23 - m
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits & jnp.uint32(0x80000000)
    mag = bits ^ sign
    if stochastic:
        addend = rbits & jnp.uint32((1 << s) - 1)
    else:
        tie = (mag >> s) & jnp.uint32(1)
        addend = (jnp.uint32(1) << (s - 1)) - jnp.uint32(1) + tie
    special = mag >= jnp.uint32(0xFF << 23)
    rmag = ((mag + addend) >> s) << s
    max_bits = jnp.uint32(((emax + 127) << 23) | (((1 << m) - 1) << s))
    ovf = max_bits if saturate else jnp.uint32(0xFF << 23)
    rmag = jnp.where(rmag > max_bits, ovf, rmag)
    # FTZ below min normal, except the RNE subnormal-boundary band
    # [min_normal*(1-2^-(m+1)), min_normal) which rounds up to min_normal
    # on the true IEEE grid (deterministic mode only; stochastic keeps the
    # plain flush — the bias is confined to that half-ulp band).
    min_bits = jnp.uint32((emin + 127) << 23)
    if stochastic:
        rmag = jnp.where(rmag < min_bits, jnp.uint32(0), rmag)
    else:
        # boundary = 2^(emin-1) * (2 - 2^-m) = min_normal * (1 - 2^-(m+1))
        boundary = jnp.uint32(((emin - 1 + 127) << 23)
                              | (((1 << m) - 1) << (23 - m)))
        rmag = jnp.where(rmag < min_bits,
                         jnp.where(mag >= boundary, min_bits, jnp.uint32(0)),
                         rmag)
    rmag = jnp.where(special, mag, rmag)
    return jax.lax.bitcast_convert_type(sign | rmag, jnp.float32)


def quantize_rne_bits(x, fmt: FPFormat, saturate: bool = False):
    """RNE grid snap of an f32 array onto ``fmt`` (no randomness operand) —
    the in-kernel dequant step for narrow formats stored in f32 containers."""
    return quantize_bits(x, None, fmt, stochastic=False, saturate=saturate)


def quantize_flag_masks(x, fmt: FPFormat, saturate: bool = False):
    """RNE grid snap plus the IEEE status flags it raises (FPnew's fflags,
    §II.B, FTZ flavor): ``(y, of, uf, nx, nv)`` with per-element bool masks.

    OF: |x| rounded beyond max normal (raised in BOTH overflow modes —
    saturation changes the value written, not the telemetry).  UF: nonzero
    |x| below min normal AND inexact (FTZ makes every flush inexact, so a
    target-exact subnormal still reports the damage).  NX: y != x.  NV:
    x is NaN.  Specials (Inf in, NaN in) pass through and raise only NV.
    """
    m, emax, emin = fmt.m_bits, fmt.emax, fmt.emin
    s = 23 - m
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits & jnp.uint32(0x80000000)
    mag = bits ^ sign
    tie = (mag >> s) & jnp.uint32(1)
    addend = (jnp.uint32(1) << (s - 1)) - jnp.uint32(1) + tie
    special = mag >= jnp.uint32(0xFF << 23)
    nv = mag > jnp.uint32(0xFF << 23)
    rmag = ((mag + addend) >> s) << s
    max_bits = jnp.uint32(((emax + 127) << 23) | (((1 << m) - 1) << s))
    over = rmag > max_bits
    ovf = max_bits if saturate else jnp.uint32(0xFF << 23)
    rmag = jnp.where(over, ovf, rmag)
    min_bits = jnp.uint32((emin + 127) << 23)
    boundary = jnp.uint32(((emin - 1 + 127) << 23)
                          | (((1 << m) - 1) << (23 - m)))
    rmag = jnp.where(rmag < min_bits,
                     jnp.where(mag >= boundary, min_bits, jnp.uint32(0)),
                     rmag)
    of = over & ~special
    nx = (rmag != mag) & ~special
    tiny = (mag != jnp.uint32(0)) & (mag < min_bits)
    uf = tiny & nx
    rmag = jnp.where(special, mag, rmag)
    return jax.lax.bitcast_convert_type(sign | rmag, jnp.float32), of, uf, nx, nv


def widen(x, fmt, src_dtype):
    """CONV stage: storage format -> compute format at the FMA input.
    Native narrow dtypes widen exactly; f32 containers RNE-snap onto the
    storage grid first (emulated narrow storage).  Shared by the decode-
    and prefill-attention kernels."""
    if fmt is not None and x.dtype == jnp.float32:
        x = quantize_rne_bits(x, fmt)
    return x.astype(src_dtype)


def widen_with_flags(x, fmt, src_dtype):
    """:func:`widen` plus the flag masks the CONV stage raises:
    ``(y, of, uf, nx, nv)``.

    Emulated narrow storage (f32 container + fmt) reports the full set
    from the in-kernel grid snap.  Native narrow storage widens exactly,
    so the snap-time flags are gone — what remains observable is the
    damage already stored in the cache: OF := stored ±Inf, NV := stored
    NaN, UF/NX := False.  Telemetry consumers must read the two modes
    accordingly (docs/KERNELS.md)."""
    if fmt is not None and x.dtype == jnp.float32:
        y, of, uf, nx, nv = quantize_flag_masks(x, fmt)
        return y.astype(src_dtype), of, uf, nx, nv
    y = x.astype(src_dtype)
    none = jnp.zeros(x.shape, jnp.bool_)
    # classify on the exact f32 widening: the TPU vector unit has no
    # narrow-float compares (Mosaic refuses bf16 cmpf on v5e)
    xf = x.astype(jnp.float32)
    return y, jnp.isinf(xf), none, none, jnp.isnan(xf)
