"""DFX-compressed cross-pod gradient all-reduce (beyond-paper extension).

The paper quantizes the *local* gradient tensors; here we promote its own
mapping to the collective level: the cross-pod data-parallel all-reduce
(the slowest link in a multi-pod mesh — ~1/10th the ICI bandwidth) moves
**int8 mantissas** instead of FP32:

  1. each pod computes its local gradient (XLA SPMD over data/model inside),
  2. the shared scale is pre-synced with a tiny ``pmax`` of the exponent,
  3. ``psum`` of the int8 mantissas (int32 accumulator, exact),
  4. inverse-map + **error feedback**: the quantization residual is carried
     into the next step's gradient so the compression is unbiased over time
     (Karimireddy et al. 2019 — without EF, signSGD-style compression can
     stall; with EF it matches full-precision convergence rates).

4x fewer bytes over the pod interconnect (a model, not measured on a chip).

The wire format is a :class:`repro.core.qtensor.QTensor` quantized against
a ``pmax``-shared scale (the ``exp=`` override), so this module carries no
private packing of its own — the same limb planes the FSDP gather and the
optimizer moments use (DESIGN.md §7).  ``psum`` runs over the recombined
int32 logical mantissa, which is exact.

Implemented with ``shard_map`` over the ``pod`` axis with ``data``/``model``
left to XLA auto partitioning inside the body.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qtensor


def _compress_leaf(g: jax.Array, residual: Optional[jax.Array], bits: int,
                   axis: str, npods: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantized psum of one gradient leaf along ``axis`` with error feedback.

    Returns (all-reduced gradient estimate, new residual).
    """
    g32 = g.astype(jnp.float32)
    if residual is not None:
        g32 = g32 + residual
    # pre-sync the shared scale: max step exponent across the axis (scalar)
    exp = jax.lax.pmax(qtensor.step_exponent(g32, bits), axis)
    t = qtensor.quantize(g32, bits, exp=exp)
    new_residual = g32 - qtensor.dequantize(t)
    # int32 psum of mantissas (exact for <= 2^(31-b-log2(npods)) pods)
    summed = jax.lax.psum(qtensor.int_mantissa(t), axis)
    out = summed.astype(jnp.float32) * jnp.exp2(exp.astype(jnp.float32)) / npods
    return out, new_residual


def compressed_psum_mean(grads: Any, residuals: Optional[Any], *,
                         bits: int = 8, axis: str = "pod",
                         min_size: int = 65536) -> Tuple[Any, Any]:
    """Tree-wise compressed mean-all-reduce along a mesh axis.

    Leaves smaller than ``min_size`` elements go through a plain FP32 psum
    (scales/norms/biases are latency- not bandwidth-bound). Must be called
    inside a ``shard_map`` that names ``axis``.
    """
    flat, tdef = jax.tree.flatten(grads)
    if residuals is None:
        res_flat = [None] * len(flat)
    else:
        res_flat, res_tdef = jax.tree.flatten(residuals)
        if res_tdef != tdef:
            # a silent zip() over mismatched trees would pair residuals with
            # the wrong leaves and corrupt the error feedback
            raise ValueError(
                "residual tree does not match the gradient tree "
                f"(grads: {tdef}, residuals: {res_tdef}); build residuals "
                "with init_residuals(params)")
    # one axis-size psum shared by every leaf (was one per leaf)
    npods = jax.lax.psum(1, axis)
    out, new_res = [], []
    for g, r in zip(flat, res_flat):
        if g.size < min_size:
            out.append(jax.lax.psum(g.astype(jnp.float32), axis) / npods)
            new_res.append(jnp.zeros_like(g, jnp.float32))
        else:
            o, nr = _compress_leaf(g, r, bits, axis, npods)
            out.append(o)
            new_res.append(nr)
    return jax.tree.unflatten(tdef, out), jax.tree.unflatten(tdef, new_res)


def init_residuals(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
