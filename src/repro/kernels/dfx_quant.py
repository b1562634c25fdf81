"""Pallas TPU kernel: the shift-and-round pass of the DFX linear mapping.

Two-pass structure (DESIGN.md §2): pass 1 is the max-abs exponent reduction
(left to XLA — a bandwidth-bound reduce the compiler already fuses); pass 2
(this kernel) streams the tensor once through VMEM doing

    m = clip(round(x * 2^-exp  [+ u]), ±(2^(b-1)-1)) -> int8/int16

with optional stochastic rounding (``u`` uniform noise, drawn in XLA from
the layer's key and streamed in beside ``x``, so both backends round with
the same bits).  The scale exponent is a scalar operand in SMEM.

**Fused limb splitting** (``limb_planes=True``): the matmul kernels consume
``b``-bit mantissas as stacked int8 **balanced base-2⁷ limb planes**
``m = Σ_j limb_j · 2^(7j)`` (kernels/bfp_matmul.py).  Instead of emitting a
logical int8/int16 mantissa and re-deriving the limbs in an XLA shift/round
chain afterwards, this kernel performs the digit extraction in-register on
the just-rounded mantissa and writes the ``(L, M, N)`` int8 plane stack
directly — the mantissa never round-trips HBM in its logical form, and the
traced jaxpr between quantize and matmul contains no split arithmetic at
all.  The extraction is exact f32 integer arithmetic (values ≤ 2^15 ≪ 2^23):

    carry  = floor((m + 64) / 128)        — balanced round toward the carry
    limb_j = m - 128·carry,  limb_j ∈ [-64, 63];  m ← carry

and the LAST plane stores the raw remaining carry (|carry| ≤ 64 for every
supported width — this also fixes the b=14 corner where a final
mod-extraction dropped a carry of ±1·2^14).

``dfx_quantize_grouped`` is the per-leading-slice (grouped-scale) variant for
MoE expert stacks: ``x`` is (E, M, N), ``exp`` an (E,) vector, and grid slice
``(e, i)`` shifts by ``exp[e]`` — one kernel launch quantizes all E experts
with their own scales (DESIGN.md §2); with ``limb_planes=True`` it emits the
plane-major ``(L, E, M, N)`` stack the batched matmul kernels take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: scale exponents (a scalar, or the grouped kernel's (E,) vector) ride in
#: SMEM: Mosaic loads scalars only from SMEM/VMEM refs.
_EXP_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

#: balanced-digit radix: every non-final limb lies in [-64, 63] and the final
#: carry in [-64, 64] — all int8, and every limb product fits the MXU's
#: int8×int8→int32 path with room to spare (≤ 2^12 magnitude).  Single
#: source of truth: the matmul combine (kernels/bfp_matmul.py) and the XLA
#: reference split (kernels/ops.py) import this — the digit split and the
#: cross-limb shifts must encode the same radix.
LIMB_BITS = 7


def n_limbs(bits: int) -> int:
    """Number of int8 limb planes of a ``bits``-bit mantissa (1/2/3)."""
    return 1 if bits <= 8 else -(-bits // LIMB_BITS)


def _round_clip(y, bits: int):
    lim = float(2 ** (bits - 1) - 1)
    return jnp.clip(y, -lim, lim)


def _split_planes(m, n: int):
    """Balanced base-2⁷ digit planes of an integer-valued f32 tensor.

    Exact f32 arithmetic throughout (|m| ≤ 2^15, the radix is a power of
    two).  The final plane keeps the raw carry — see module docstring.
    """
    planes = []
    for _ in range(n - 1):
        carry = jnp.floor((m + 64.0) * (1.0 / 128.0))
        planes.append(m - carry * 128.0)
        m = carry
    planes.append(m)
    return planes


def _quant_kernel(x_ref, exp_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[0].astype(jnp.float32))
    y = jnp.round(x_ref[...] * scale)
    o_ref[...] = _round_clip(y, bits).astype(o_ref.dtype)


def _quant_kernel_stoch(x_ref, exp_ref, u_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[0].astype(jnp.float32))
    y = jnp.floor(x_ref[...] * scale + u_ref[...])
    o_ref[...] = _round_clip(y, bits).astype(o_ref.dtype)


def _quant_kernel_limbs(x_ref, exp_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[0].astype(jnp.float32))
    y = _round_clip(jnp.round(x_ref[...] * scale), bits)
    for j, plane in enumerate(_split_planes(y, n_limbs(bits))):
        o_ref[j] = plane.astype(jnp.int8)


def _quant_kernel_limbs_stoch(x_ref, exp_ref, u_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[0].astype(jnp.float32))
    y = _round_clip(jnp.floor(x_ref[...] * scale + u_ref[...]), bits)
    for j, plane in enumerate(_split_planes(y, n_limbs(bits))):
        o_ref[j] = plane.astype(jnp.int8)


def _out_dtype(bits: int):
    return jnp.int8 if bits <= 8 else (jnp.int16 if bits <= 16 else jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("bits", "br", "interpret", "limb_planes",
                                    "vmem_limit"))
def dfx_quantize(
    x: jax.Array,            # (M, N) float32
    exp: jax.Array,          # scalar int32 (e_max - bits + 1)
    *,
    bits: int,
    u: jax.Array | None = None,   # (M, N) uniform [0,1) noise, optional
    br: int = 256,
    interpret: bool = False,
    limb_planes: bool = False,
    vmem_limit: int | None = None,
) -> jax.Array:
    """Shift-round-clip pass; one streaming kernel launch.

    ``limb_planes=False`` returns the logical (M, N) int8/int16 mantissa
    (norm layers, embedding tables).  ``limb_planes=True`` returns the
    (L, M, N) int8 limb-plane stack the matmul kernels consume — the digit
    split is fused into this same launch.  ``vmem_limit`` is the scoped
    VMEM to ask Mosaic for; None leaves its default.
    """
    M, N = x.shape
    assert M % br == 0, (M, br)
    grid = (M // br,)
    exp = jnp.reshape(exp, (1,)).astype(jnp.int32)
    if limb_planes:
        L = n_limbs(bits)
        out_spec = pl.BlockSpec((L, br, N), lambda i: (0, i, 0))
        out_shape = jax.ShapeDtypeStruct((L, M, N), jnp.int8)
        kern, kern_stoch = _quant_kernel_limbs, _quant_kernel_limbs_stoch
    else:
        out_spec = pl.BlockSpec((br, N), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((M, N), _out_dtype(bits))
        kern, kern_stoch = _quant_kernel, _quant_kernel_stoch
    limit = {} if vmem_limit is None else {"vmem_limit_bytes": vmem_limit}
    common = dict(
        grid=grid,
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             **limit),
        name="dfx_quantize",
        interpret=interpret,
    )
    if u is None:
        return pl.pallas_call(
            functools.partial(kern, bits=bits),
            in_specs=[pl.BlockSpec((br, N), lambda i: (i, 0)),
                      _EXP_SPEC],
            **common,
        )(x, exp)
    return pl.pallas_call(
        functools.partial(kern_stoch, bits=bits),
        in_specs=[pl.BlockSpec((br, N), lambda i: (i, 0)),
                  _EXP_SPEC,
                  pl.BlockSpec((br, N), lambda i: (i, 0))],
        **common,
    )(x, exp, u)


# =========================================================================
# Grouped-scale (per-leading-slice) variant — exp is an (E,) vector
# =========================================================================

def _quant_kernel_grouped(x_ref, exp_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[pl.program_id(0)].astype(jnp.float32))
    y = jnp.round(x_ref[0] * scale)
    o_ref[0] = _round_clip(y, bits).astype(o_ref.dtype)


def _quant_kernel_grouped_stoch(x_ref, exp_ref, u_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[pl.program_id(0)].astype(jnp.float32))
    y = jnp.floor(x_ref[0] * scale + u_ref[0])
    o_ref[0] = _round_clip(y, bits).astype(o_ref.dtype)


def _quant_kernel_grouped_limbs(x_ref, exp_ref, o_ref, *, bits: int):
    scale = jnp.exp2(-exp_ref[pl.program_id(0)].astype(jnp.float32))
    y = _round_clip(jnp.round(x_ref[0] * scale), bits)
    for j, plane in enumerate(_split_planes(y, n_limbs(bits))):
        o_ref[j, 0] = plane.astype(jnp.int8)


def _quant_kernel_grouped_limbs_stoch(x_ref, exp_ref, u_ref, o_ref, *,
                                      bits: int):
    scale = jnp.exp2(-exp_ref[pl.program_id(0)].astype(jnp.float32))
    y = _round_clip(jnp.floor(x_ref[0] * scale + u_ref[0]), bits)
    for j, plane in enumerate(_split_planes(y, n_limbs(bits))):
        o_ref[j, 0] = plane.astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("bits", "br", "interpret", "limb_planes"))
def dfx_quantize_grouped(
    x: jax.Array,            # (E, M, N) float32
    exp: jax.Array,          # (E,) int32 per-slice scale exponents
    *,
    bits: int,
    u: jax.Array | None = None,   # (E, M, N) uniform [0,1) noise, optional
    br: int = 256,
    interpret: bool = False,
    limb_planes: bool = False,
) -> jax.Array:
    """Grouped-scale shift-round-clip; with ``limb_planes=True`` emits the
    plane-major (L, E, M, N) int8 stack for the batched matmul kernels."""
    E, M, N = x.shape
    assert M % br == 0, (M, br)
    assert exp.shape == (E,), (exp.shape, E)
    grid = (E, M // br)
    exp = exp.astype(jnp.int32)
    blk = pl.BlockSpec((1, br, N), lambda e, i: (e, i, 0))
    if limb_planes:
        L = n_limbs(bits)
        out_spec = pl.BlockSpec((L, 1, br, N), lambda e, i: (0, e, i, 0))
        out_shape = jax.ShapeDtypeStruct((L, E, M, N), jnp.int8)
        kern = _quant_kernel_grouped_limbs
        kern_stoch = _quant_kernel_grouped_limbs_stoch
    else:
        out_spec = blk
        out_shape = jax.ShapeDtypeStruct((E, M, N), _out_dtype(bits))
        kern, kern_stoch = _quant_kernel_grouped, _quant_kernel_grouped_stoch
    common = dict(
        grid=grid,
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="dfx_quantize_grouped",
        interpret=interpret,
    )
    if u is None:
        return pl.pallas_call(
            functools.partial(kern, bits=bits),
            in_specs=[blk, _EXP_SPEC],
            **common,
        )(x, exp)
    return pl.pallas_call(
        functools.partial(kern_stoch, bits=bits),
        in_specs=[blk, _EXP_SPEC, blk],
        **common,
    )(x, exp, u)
