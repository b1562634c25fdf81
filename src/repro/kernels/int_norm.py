"""Pallas TPU kernels: fused integer layer-norm and RMS-norm, fwd AND bwd.

All four kernels consume the DFX mantissas directly (int8/int16) so the
normalization never materializes an FP32 copy of the activation in HBM: a
row-block is staged in VMEM, the moment sums run over the *integer*
mantissas (exact — see ``_exact_moments``), the rsqrt is FP32
(precision-critical, the paper's rule) — or the fixed-point Newton form
from ``core/iapprox.py`` when the forward entry points get
``integer_rsqrt=True`` (kept_ops="integer", DESIGN.md §10) — and the
affine epilogue is fused.

Forward kernels are **multi-output**: alongside ``y`` they return the
per-row statistics (``mu``/``rstd`` for LN, ``rstd`` for RMS) in the value
domain — these are the statistics the kernel *actually normalized with*,
saved as backward residuals.  The backward then differentiates exactly the
forward that ran, instead of a recompute that only approximately bit-matches
it (the statistics-mismatch bug this module fixes), and the second full HBM
pass over every normalized activation disappears.

Backward kernels produce ``dx`` plus **per-row-block partial reductions**
for ``dgamma``/``dbeta`` (entry ``i`` of an ``(R/br, 1, D)`` output is block
``i``'s contribution); the cross-block combine is a small XLA tree-sum in
the ops.py wrapper.  ``dbeta`` partials are exact int32 sums of the gradient
mantissas; ``dgamma`` partials multiply the integer gradient mantissas by
the in-kernel recomputed ``xn``.

Row block (br, D) must fit VMEM: the fwd default br=8 rows of D=12288 int16
+ f32 out is ~600 KiB; the bwd default br=64 stages two mantissa blocks and
an f32 dx block, ~7 MiB at D=12288 — both inside the ~16 MiB VMEM budget
with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import iapprox

#: the scalar scale exponents ride in SMEM (Mosaic loads scalars only from
#: SMEM/VMEM refs).
_EXP_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _partial_spec(d: int) -> pl.BlockSpec:
    """Block ``i`` of the backward's ``(R/br, 1, D)`` parameter-gradient
    partials.  The trailing ``(1, D)`` block spans the array's full trailing
    dims, which the (8, 128) tiling rule admits; a ``(1, D)`` block over an
    ``(R/br, D)`` array does not."""
    return pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0))


def _exact_moments(xi: jax.Array):
    """Row sums ``s1 = Σx`` and ``s2 = Σx²`` over int32 mantissas, exact.

    A direct f32 evaluation of ``s2`` is NOT exact for wide mantissas: the
    budget is ``2(b-1) + log2 D`` bits (~40 for int16 at D=768) and f32
    holds 24 — for b > 13 even the individual products ``x²`` (up to 2^30)
    round before the sum starts.  Instead the mantissa is split into
    balanced base-2⁸ digits ``x = hi·2⁸ + lo`` (|hi|, |lo| <= 128, so every
    digit product fits 14 bits) and the three partial sums

        s2 = 2^16·Σhi² + 2^9·Σhi·lo + Σlo²

    accumulate exactly in int32 (14 + log2 D <= 31 for any D < 2^17).  The
    final f32 recombination and the cast of each int32 partial round at most
    ~2 ulp of s2 (relative 2^-23) — f32-optimal, vs the old direct sum whose
    error grew linearly in D.  ``s1`` is a plain int32 sum, exact for
    (b-1) + log2 D < 31.  Returns ``(s1, s2)`` as f32 keep-dims rows.
    """
    s1 = jnp.sum(xi, axis=-1, keepdims=True).astype(jnp.float32)
    lo = jnp.bitwise_and(xi + 128, 255) - 128
    hi = jnp.right_shift(xi - lo, 8)          # exact: xi - lo divisible by 256
    a = jnp.sum(hi * hi, axis=-1, keepdims=True).astype(jnp.float32)
    b = jnp.sum(hi * lo, axis=-1, keepdims=True).astype(jnp.float32)
    c = jnp.sum(lo * lo, axis=-1, keepdims=True).astype(jnp.float32)
    return s1, a * 65536.0 + b * 512.0 + c


# =========================================================================
# Layer norm
# =========================================================================

def _rstd(ms: jax.Array, eps: float, integer_rsqrt: bool) -> jax.Array:
    """In-kernel reciprocal std: the paper's FP32 rsqrt, or the fixed-point
    Newton form (``iapprox.i_rsqrt``) under ``kept_ops="integer"``.  The
    static flag is threaded from the resolved ``QuantConfig`` — the swap is
    in-kernel, so the dispatch count is unchanged either way."""
    if integer_rsqrt:
        return iapprox.i_rsqrt(ms + eps)
    return jax.lax.rsqrt(ms + eps)


def _ln_fwd_kernel(xm_ref, exp_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref, *,
                   eps: float, integer_rsqrt: bool):
    xi = xm_ref[...].astype(jnp.int32)
    d = xi.shape[-1]
    s1, s2 = _exact_moments(xi)
    mu_m = s1 / d
    # One-pass E[x²] − μ² over mantissas.  The true variance is >= 0, but the
    # f32 recombination of s2 and the s1 cast round ~2 ulp of magnitudes up
    # to 2^39, so near-constant rows can come out slightly negative (beyond
    # the value-domain eps guard) — clamp, or rsqrt returns NaN.
    var_m = jnp.maximum(s2 / d - mu_m * mu_m, 0.0)
    # Apply the shared scale to return to value domain for the eps guard.
    scale = jnp.exp2(exp_ref[0].astype(jnp.float32))
    mu = mu_m * scale
    rstd = _rstd(var_m * (scale * scale), eps, integer_rsqrt)
    xn = (xi.astype(jnp.float32) * scale - mu) * rstd
    y_ref[...] = xn * g_ref[...] + b_ref[...]
    # Residual statistics = what THIS kernel normalized with, not a recompute.
    mu_ref[...] = mu
    rstd_ref[...] = rstd


@functools.partial(jax.jit, static_argnames=("br", "eps", "interpret",
                                             "integer_rsqrt"))
def int_layernorm_fwd(
    xm: jax.Array,          # (R, D) int8/int16 mantissas
    x_exp: jax.Array,       # scalar int32
    gamma: jax.Array,       # (D,) float32 (dequantized values)
    beta: jax.Array,        # (D,) float32
    *,
    br: int = 8,
    eps: float = 1e-5,
    interpret: bool = False,
    integer_rsqrt: bool = False,
):
    """Fused LN forward. Returns ``(y, mu, rstd)`` — y (R, D) f32 plus the
    (R, 1) value-domain statistics used for the normalization.

    ``integer_rsqrt=True`` swaps the FP32 rsqrt for the iapprox fixed-point
    form (kept_ops="integer"); the backward consumes the forward-saved rstd
    either way, so it needs no flag — there is no rsqrt in the bwd kernels.
    """
    R, D = xm.shape
    assert R % br == 0, (R, br)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps,
                          integer_rsqrt=integer_rsqrt),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            _EXP_SPEC,
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((R, D), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="int_layernorm_fwd",
        interpret=interpret,
    )(xm, jnp.reshape(x_exp, (1,)).astype(jnp.int32),
      gamma.reshape(1, D), beta.reshape(1, D))


def _ln_bwd_kernel(xm_ref, gm_ref, xexp_ref, gexp_ref, gv_ref, mu_ref,
                   rstd_ref, dx_ref, dg_ref, db_ref):
    xi = xm_ref[...].astype(jnp.int32)
    gi = gm_ref[...].astype(jnp.int32)
    d = xi.shape[-1]
    xscale = jnp.exp2(xexp_ref[0].astype(jnp.float32))
    gscale = jnp.exp2(gexp_ref[0].astype(jnp.float32))
    # xn recomputed from the integer mantissas and the forward-saved
    # statistics — bit-identical to the xn the forward normalized with.
    xn = (xi.astype(jnp.float32) * xscale - mu_ref[...]) * rstd_ref[...]
    gq = gi.astype(jnp.float32) * gscale
    gg = gq * gv_ref[...]
    mean_gg = jnp.sum(gg, axis=-1, keepdims=True) / d
    mean_ggxn = jnp.sum(gg * xn, axis=-1, keepdims=True) / d
    dx_ref[...] = rstd_ref[...] * (gg - mean_gg - xn * mean_ggxn)
    # Per-block partials; dbeta's row sum is exact int32 over the gradient
    # mantissas (|g| <= 2^15, br <= 128 ⇒ 22 bits), scaled once.
    db_ref[0] = jnp.sum(gi, axis=0, keepdims=True).astype(jnp.float32) * gscale
    dg_ref[0] = jnp.sum(gq * xn, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def int_layernorm_bwd(
    xm: jax.Array,          # (R, D) activation mantissas (fwd residual)
    gm: jax.Array,          # (R, D) quantized upstream-gradient mantissas
    x_exp: jax.Array,       # scalar int32
    g_exp: jax.Array,       # scalar int32
    gamma: jax.Array,       # (D,) float32 (dequantized values)
    mu: jax.Array,          # (R, 1) f32, forward-saved
    rstd: jax.Array,        # (R, 1) f32, forward-saved
    *,
    br: int = 64,
    interpret: bool = False,
):
    """Fused LN backward. Returns ``(dx, dgamma_partials, dbeta_partials)``
    with partials of shape (R/br, 1, D) — entry i is block i's
    contribution."""
    R, D = xm.shape
    assert R % br == 0, (R, br)
    nb = R // br
    return pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            _EXP_SPEC,
            _EXP_SPEC,
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            _partial_spec(D),
            _partial_spec(D),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((R, D), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="int_layernorm_bwd",
        interpret=interpret,
    )(xm, gm, jnp.reshape(x_exp, (1,)).astype(jnp.int32),
      jnp.reshape(g_exp, (1,)).astype(jnp.int32), gamma.reshape(1, D),
      mu, rstd)


# =========================================================================
# RMS norm — same structure, no mean/beta
# =========================================================================

def _rms_fwd_kernel(xm_ref, exp_ref, g_ref, y_ref, rstd_ref, *, eps: float,
                    integer_rsqrt: bool):
    xi = xm_ref[...].astype(jnp.int32)
    d = xi.shape[-1]
    _, s2 = _exact_moments(xi)
    scale = jnp.exp2(exp_ref[0].astype(jnp.float32))
    ms = (s2 / d) * (scale * scale)           # value-domain mean square
    rstd = _rstd(ms, eps, integer_rsqrt)
    xn = xi.astype(jnp.float32) * scale * rstd
    y_ref[...] = xn * g_ref[...]
    rstd_ref[...] = rstd


@functools.partial(jax.jit, static_argnames=("br", "eps", "interpret",
                                             "integer_rsqrt"))
def int_rmsnorm_fwd(
    xm: jax.Array,          # (R, D) int8/int16 mantissas
    x_exp: jax.Array,       # scalar int32
    gamma: jax.Array,       # (D,) float32 (dequantized values)
    *,
    br: int = 8,
    eps: float = 1e-6,
    interpret: bool = False,
    integer_rsqrt: bool = False,
):
    """Fused RMS-norm forward. Returns ``(y, rstd)``.  ``integer_rsqrt``
    as in ``int_layernorm_fwd`` (the bwd consumes the saved rstd)."""
    R, D = xm.shape
    assert R % br == 0, (R, br)
    return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps,
                          integer_rsqrt=integer_rsqrt),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            _EXP_SPEC,
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((R, D), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="int_rmsnorm_fwd",
        interpret=interpret,
    )(xm, jnp.reshape(x_exp, (1,)).astype(jnp.int32), gamma.reshape(1, D))


def _rms_bwd_kernel(xm_ref, gm_ref, xexp_ref, gexp_ref, gv_ref, rstd_ref,
                    dx_ref, dg_ref):
    xi = xm_ref[...].astype(jnp.int32)
    gi = gm_ref[...].astype(jnp.int32)
    d = xi.shape[-1]
    xscale = jnp.exp2(xexp_ref[0].astype(jnp.float32))
    gscale = jnp.exp2(gexp_ref[0].astype(jnp.float32))
    xn = xi.astype(jnp.float32) * xscale * rstd_ref[...]
    gq = gi.astype(jnp.float32) * gscale
    gg = gq * gv_ref[...]
    mean_ggxn = jnp.sum(gg * xn, axis=-1, keepdims=True) / d
    dx_ref[...] = rstd_ref[...] * (gg - xn * mean_ggxn)
    dg_ref[0] = jnp.sum(gq * xn, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def int_rmsnorm_bwd(
    xm: jax.Array,          # (R, D) activation mantissas (fwd residual)
    gm: jax.Array,          # (R, D) quantized upstream-gradient mantissas
    x_exp: jax.Array,       # scalar int32
    g_exp: jax.Array,       # scalar int32
    gamma: jax.Array,       # (D,) float32 (dequantized values)
    rstd: jax.Array,        # (R, 1) f32, forward-saved
    *,
    br: int = 64,
    interpret: bool = False,
):
    """Fused RMS-norm backward. Returns ``(dx, dgamma_partials)``."""
    R, D = xm.shape
    assert R % br == 0, (R, br)
    nb = R // br
    return pl.pallas_call(
        _rms_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            _EXP_SPEC,
            _EXP_SPEC,
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            _partial_spec(D),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((R, D), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="int_rmsnorm_bwd",
        interpret=interpret,
    )(xm, gm, jnp.reshape(x_exp, (1,)).astype(jnp.int32),
      jnp.reshape(g_exp, (1,)).astype(jnp.int32), gamma.reshape(1, D), rstd)
