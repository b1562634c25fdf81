"""b-bit dynamic fixed-point (DFX) mapping — the paper's numeric core.

The *linear fixed-point mapping* of Ghaffari et al. (2022), as used by the
paper, shares the **maximum IEEE-754 exponent** of a tensor across all its
elements, shifts every mantissa right by the exponent gap, and rounds to
``b-1`` magnitude bits plus a sign bit.  Arithmetically this is exactly

    e_scale = exponent of max|x|          (frexp convention: max|x| in [0.5,1)·2^e)
    delta   = 2^(e_scale - b + 1)         (the quantization step)
    m_i     = round(x_i / delta)          with |m_i| <= 2^(b-1)

and the *non-linear inverse mapping* is ``x̂_i = m_i · delta`` (the paper's
per-element renormalization of mantissa/exponent produces the same value; we
use the arithmetic form, which is TPU-friendly — see DESIGN.md §2).

Proposition 1 of the paper bounds the mapping error by
``|x̂_i - x_i| <= 2^(e_scale_ieee - b + 2) = delta`` and its variance by
``delta²`` — property-tested in ``tests/test_dfx_properties.py``.

A ``DfxTensor`` carries the integer mantissa and the scale *exponent*
(``value = m · 2^exp``), so an integer matmul of two DfxTensors produces an
integer mantissa whose scale exponent is the **sum** of the input exponents —
the "single add" of the paper's Figure 2.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def storage_dtype(bits: int):
    """Narrowest signed-integer dtype that holds a ``bits``-bit mantissa.

    Narrow storage is a real memory win: residual activations saved for the
    backward pass are int8/int16 mantissas instead of FP32 (4x/2x smaller).
    """
    if bits <= 8:
        return jnp.int8
    if bits <= 16:
        return jnp.int16
    return jnp.int32


class DfxTensor(NamedTuple):
    """Dynamic fixed-point tensor: ``value = m * 2.0**exp``.

    ``m``   — integer mantissa (narrowest int dtype that fits ``b`` bits)
    ``exp`` — scale exponent, int32. Shape broadcasts against ``m`` (scalar
              for per-tensor scale; keep-dims shape for per-axis scales).
    """

    m: jax.Array
    exp: jax.Array

    @property
    def shape(self):  # convenience
        return self.m.shape


def _scale_exponent(x: jax.Array, reduce_axes: Optional[Sequence[int]]) -> jax.Array:
    """Exponent ``e`` with ``max|x| <= 2**e`` (frexp convention), per scale group.

    Zero tensors get exponent 0 (mantissas are all-zero anyway, any exponent
    is exact).
    """
    absmax = jnp.max(jnp.abs(x), axis=reduce_axes, keepdims=reduce_axes is not None)
    # frexp: absmax = f * 2**e with f in [0.5, 1). Exact for finite inputs.
    _, e = jnp.frexp(absmax)
    return jnp.where(absmax > 0, e, 0).astype(jnp.int32)


def _round_to_nearest(y: jax.Array) -> jax.Array:
    # IEEE round-half-to-even, matching hardware RN.
    return jnp.round(y)


def _round_stochastic(y: jax.Array, key: jax.Array) -> jax.Array:
    u = jax.random.uniform(key, y.shape, dtype=y.dtype)
    return jnp.floor(y + u)


@jax.named_scope("quantize")
def quantize(
    x: jax.Array,
    bits: int,
    *,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
    reduce_axes: Optional[Sequence[int]] = None,
) -> DfxTensor:
    """Linear fixed-point mapping: FP32 tensor → b-bit DFX mantissa + scale.

    ``reduce_axes=None`` shares one scale over the whole tensor (the paper's
    per-tensor mapping).  Passing a subset of axes yields per-channel /
    per-row scales (beyond-paper extension; the axes listed are the ones the
    scale is shared *over*).
    """
    if stochastic and key is None:
        raise ValueError("stochastic rounding requires a PRNG key")
    x = x.astype(jnp.float32)
    e = _scale_exponent(x, reduce_axes)
    # step = 2**(e - bits + 1); scale mantissa so |m| <= 2**(bits-1).
    exp = (e - (bits - 1)).astype(jnp.int32)
    y = x * jnp.exp2(-exp.astype(jnp.float32))
    y = _round_stochastic(y, key) if stochastic else _round_to_nearest(y)
    # Clip the (rare) max element that rounds up to 2**(b-1) so the mantissa
    # fits signed-b-bit storage; clip error < step, inside Prop. 1's bound.
    lim = float(2 ** (bits - 1) - 1)
    m = jnp.clip(y, -lim, lim).astype(storage_dtype(bits))
    return DfxTensor(m=m, exp=exp)


def dequantize(t: DfxTensor, dtype=jnp.float32) -> jax.Array:
    """Non-linear inverse mapping: DFX → floating point (exact)."""
    return (t.m.astype(dtype) * jnp.exp2(t.exp.astype(dtype)))


def quantize_dequantize(
    x: jax.Array,
    bits: int,
    *,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
    reduce_axes: Optional[Sequence[int]] = None,
) -> jax.Array:
    """Fake-quant helper (map + inverse-map) used for non-matmul tensors."""
    return dequantize(quantize(x, bits, stochastic=stochastic, key=key,
                               reduce_axes=reduce_axes))


# ---------------------------------------------------------------------------
# Integer contractions on DFX tensors
# ---------------------------------------------------------------------------

#: Largest mantissa-bit budget for which an f32 MAC chain is *bit-exact*
#: (int32 limb kernels take over beyond this on TPU; see kernels/bfp_matmul).
_EXACT_F32_BITS = 24

#: f64 mantissa budget (52 explicit bits) — the escalation target when x64 is
#: enabled and the product+accumulation budget overflows f32.
_EXACT_F64_BITS = 52


def accum_bits_needed(bits_a: int, bits_b: int, contraction: int) -> int:
    """Worst-case bit budget of the integer contraction.

    Each product needs ``bits_a + bits_b - 2`` magnitude bits; summing ``K``
    of them adds ``ceil(log2(K))`` carry bits (DESIGN.md §2).
    """
    return bits_a + bits_b - 2 + max(1, int(np.ceil(np.log2(max(contraction, 2)))))


def sim_accum_exact(bits_a: int, bits_b: int, contraction: int) -> bool:
    """True when f32 accumulation of the sim-path mantissa matmul is bit-exact."""
    return accum_bits_needed(bits_a, bits_b, contraction) <= _EXACT_F32_BITS


#: (bits_a, bits_b) pairs already warned about — one warning per shape class
#: per process, not one per traced matmul.
_INEXACT_WARNED: set = set()


def acc_dtype(bits_a: int, bits_b: int, contraction: int) -> jnp.dtype:
    """Accumulator dtype that keeps the sim-path integer matmul exact.

    ``bits_a + bits_b - 2 + ceil(log2(K))`` bits are needed.  Up to 24 we may
    accumulate in f32 exactly; up to 52 in f64 (only when jax x64 is on);
    beyond that — or when x64 is off — the sim path is *inexact* and we warn:
    the Pallas kernel path (``QuantConfig(backend="pallas")``) is the exact
    alternative, accumulating in int32 over int8 limbs (kernels/bfp_matmul,
    DESIGN.md §2).
    """
    need = accum_bits_needed(bits_a, bits_b, contraction)
    if need <= _EXACT_F32_BITS:
        return jnp.float32
    if jax.config.jax_enable_x64 and need <= _EXACT_F64_BITS:
        return jnp.float64
    if (bits_a, bits_b) not in _INEXACT_WARNED:
        _INEXACT_WARNED.add((bits_a, bits_b))
        warnings.warn(
            f"sim-path integer matmul needs {need} accumulator bits "
            f"(b_a={bits_a}, b_b={bits_b}, K={contraction}) but f32 holds "
            f"{_EXACT_F32_BITS}: accumulation may round. Use "
            f"QuantConfig(backend='pallas') for bit-exact int32 limb "
            f"accumulation, or enable jax x64.",
            RuntimeWarning,
            stacklevel=2,
        )
    return jnp.float32


def _storage_bits(m: jax.Array) -> int:
    """Upper bound on the mantissa bit-width implied by the storage dtype."""
    return {jnp.int8: 8, jnp.int16: 16, jnp.int32: 24}.get(
        jnp.dtype(m.dtype).type, 24)


def dfx_dot_general(
    a: DfxTensor,
    b: DfxTensor,
    dimension_numbers,
    preferred_element_type=None,
    bits: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Integer ``dot_general`` of two DFX tensors, dequantized output.

    The mantissa contraction is integer-valued; the output scale is the sum
    of the two input scale exponents (paper Fig. 2: "a single add").  Scales
    must be per-tensor or constant along the contracted axes.

    The accumulator dtype escalates via ``acc_dtype`` when the worst-case
    bit budget overflows f32 (warns when no exact dtype is available — the
    Pallas backend is the exact path in that regime).  Pass ``bits``
    (mantissa bit-widths of a and b) when known; otherwise the storage
    dtype provides a conservative upper bound.
    """
    (lhs_c, rhs_c), (lhs_b, rhs_b) = dimension_numbers
    _check_exp_constant_over(a.exp, a.m.ndim, lhs_c, "lhs")
    _check_exp_constant_over(b.exp, b.m.ndim, rhs_c, "rhs")
    if preferred_element_type is None:
        contraction = int(np.prod([a.m.shape[ax] for ax in lhs_c])) or 1
        bits_a, bits_b = bits if bits is not None else (
            _storage_bits(a.m), _storage_bits(b.m))
        preferred_element_type = acc_dtype(bits_a, bits_b, contraction)
    # HIGHEST: a TPU otherwise runs f32 matmuls as bf16 passes, which would
    # round the integer-valued mantissas the exactness bound above assumes.
    prod = jax.lax.dot_general(
        a.m.astype(preferred_element_type), b.m.astype(preferred_element_type),
        dimension_numbers=dimension_numbers,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=preferred_element_type,
    )
    # Per-axis scales are re-laid-out to the dot_general output convention
    # (batch..., lhs free..., rhs free...) so each kept axis scales the
    # output axis it actually produced — positional broadcast alone would
    # silently hit the wrong axis for non-standard contraction layouts.
    n_lhs_free = a.m.ndim - len(lhs_c) - len(lhs_b)
    n_rhs_free = b.m.ndim - len(rhs_c) - len(rhs_b)
    ea = _aligned_exp(a.exp, a.m.ndim, lhs_c, lhs_b, n_rhs_free, "lhs")
    eb = _aligned_exp(b.exp, b.m.ndim, rhs_c, rhs_b, n_lhs_free, "rhs")
    out_exp = (ea + eb).astype(prod.dtype)
    out = prod * jnp.exp2(_broadcast_out_exp(out_exp, prod.shape))
    return out.astype(jnp.float32)


def _aligned_exp(exp: jax.Array, m_ndim: int, c_axes, b_axes,
                 other_free: int, side: str) -> jax.Array:
    """Map an operand's keep-dims scale exponent to the output axis layout.

    ``dot_general`` output dims are (batch..., lhs free..., rhs free...).
    The operand's contracted axes are squeezed (validated size 1), its kept
    axes are permuted to (batch..., free...), and the *other* operand's free
    axes get size-1 slots — trailing for the lhs, between batch and free for
    the rhs — so the summed exponent broadcasts against the true output axes.
    """
    if exp.ndim == 0:
        return exp
    squeezed = jnp.squeeze(exp, axis=tuple(c_axes))
    kept = [ax for ax in range(m_ndim) if ax not in c_axes]
    pos = {ax: i for i, ax in enumerate(kept)}
    free = [ax for ax in kept if ax not in b_axes]
    e = jnp.transpose(squeezed, [pos[ax] for ax in b_axes]
                      + [pos[ax] for ax in free])
    nb = len(b_axes)
    if side == "lhs":
        shape = e.shape + (1,) * other_free
    else:
        shape = e.shape[:nb] + (1,) * other_free + e.shape[nb:]
    return e.reshape(shape)


def _check_exp_constant_over(exp: jax.Array, m_ndim: int, axes, side: str):
    """Reject per-axis scales that vary along a contracted axis.

    A scale that changes *along* the contraction cannot be factored out of
    the integer sum — the output scale would be ill-defined and the result
    silently mis-scaled.  Scalar (per-tensor) exponents always pass; keep-dims
    per-axis exponents must be size 1 on every contracted axis.
    """
    if exp.ndim == 0:
        return
    if exp.ndim != m_ndim:
        raise ValueError(
            f"{side} scale exponent has shape {exp.shape} but the mantissa "
            f"is rank {m_ndim}; per-axis scales must use the keep-dims "
            "layout produced by dfx.quantize(reduce_axes=...)")
    bad = [ax for ax in axes if exp.shape[ax] != 1]
    if bad:
        raise ValueError(
            f"{side} scale exponent {exp.shape} varies along contracted "
            f"axes {bad}; scales must be per-tensor or constant over the "
            "contraction (quantize with the contracted axes in reduce_axes)")


def _broadcast_out_exp(out_exp: jax.Array, out_shape) -> jax.Array:
    """Align the summed scale exponent with the contraction output shape.

    Per-tensor (scalar) exponents pass through; keep-dims per-axis exponents
    must numpy-broadcast to exactly ``out_shape``.  Anything else raises —
    the old silent fallback returned the unaligned exponent and could scale
    the output wrongly (or trip an opaque shape error downstream).
    """
    out_shape = tuple(out_shape)
    if out_exp.ndim == 0 or out_exp.shape == out_shape:
        return out_exp
    try:
        if jnp.broadcast_shapes(out_exp.shape, out_shape) == out_shape:
            return out_exp
    except ValueError:
        pass
    # A keep-dims exponent that is all-size-1 is really a per-tensor scale.
    squeezed = jnp.squeeze(out_exp)
    if squeezed.ndim == 0:
        return squeezed
    raise ValueError(
        f"scale exponent of shape {out_exp.shape} does not broadcast to the "
        f"contraction output shape {out_shape}; per-axis scales must keep "
        "dims so the summed exponent aligns with the output "
        "(see dfx.quantize(reduce_axes=...))")


def dfx_matmul(a: DfxTensor, b: DfxTensor,
               bits: Optional[Tuple[int, int]] = None) -> jax.Array:
    """``a @ b`` for stacked matrices: contracts last dim of a, first of b."""
    nd_a = a.m.ndim
    dn = (((nd_a - 1,), (0,)), ((), ()))
    return dfx_dot_general(a, b, dn, bits=bits)


# ---------------------------------------------------------------------------
# Health counters (runtime sentinel probes — core/health.py)
# ---------------------------------------------------------------------------

def health_stats(x: jax.Array, bits: int) -> dict:
    """Counters of mapping ``x`` at ``bits``: clip rate at the
    ``jnp.clip(y, -lim, lim)`` saturation point of :func:`quantize`, mantissa
    zero-fraction (underflow proxy), step exponent, non-finite count.

    Same frexp/step arithmetic as ``quantize`` but on sanitized magnitudes —
    a single NaN must raise the ``nonfinite`` counter, not poison the amax
    (and thereby every other counter).  Plain XLA reductions over a tensor
    already resident: zero extra ``pallas_call`` dispatches.
    """
    x = x.astype(jnp.float32)
    finite = jnp.isfinite(x)
    ax = jnp.where(finite, jnp.abs(x), 0.0)
    e = _scale_exponent(ax, None)
    exp = (e - (bits - 1)).astype(jnp.int32)
    y = jnp.round(ax * jnp.exp2(-exp.astype(jnp.float32)))
    lim = float(2 ** (bits - 1) - 1)
    return {
        "clip": jnp.mean((y >= lim).astype(jnp.float32)),
        "zero": jnp.mean((y == 0).astype(jnp.float32)),
        "nonfinite": jnp.sum(~finite).astype(jnp.float32),
        "exp": exp.astype(jnp.float32),
    }


# ---------------------------------------------------------------------------
# Error-bound helpers (Proposition 1) — used by property tests and monitors
# ---------------------------------------------------------------------------

def error_bound(x: jax.Array, bits: int) -> jax.Array:
    """Prop. 1 bound on |x̂ - x|: the quantization step ``2^(e_scale-b+1)``
    (RN halves it; stochastic rounding meets it)."""
    e = _scale_exponent(x, None)
    return jnp.exp2((e - (bits - 1)).astype(jnp.float32))


def variance_bound(x: jax.Array, bits: int) -> jax.Array:
    """Prop. 1: V{delta} <= 2^(2(e_scale_ieee - b + 2)) = step^2."""
    return error_bound(x, bits) ** 2
