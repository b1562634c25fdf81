"""Pallas TPU kernel: block-floating-point (DFX) integer matmul.

The paper's compute hot-spot is the integer mantissa matmul at the heart of
every integer layer (forward ``q(X)·q(W)`` and both backward products).  On
TPU the natural engine is the **MXU int8×int8→int32 systolic path**; wider
mantissas (the paper's 10/12/16-bit formats) arrive as **stacked int8 limb
planes** ``(L, M, K)`` — balanced base-2⁷ digits emitted directly by the
quantize kernel (kernels/dfx_quant.py) — and ALL limb pairs of a matmul run
in ONE ``pallas_call``:

* every grid step loads the full limb stack of an operand tile (the leading
  ``L`` axis rides the block, not the grid), so each X/W tile streams from
  HBM **once** instead of once per limb pair (up to 3× before);
* the limb-pair loop is a statically unrolled in-kernel loop over plane
  slices, one int8×int8→int32 MXU contraction per pair per K step;
* each pair accumulates bit-exactly into its own int32 VMEM scratch plane
  across the K grid dimension;
* the epilogue combines the partials in f32 with their ``2^(7(jx+jw))``
  limb shifts and the fused dequant scale ``2^out_exp`` (the single scale
  multiply of the paper's Fig. 2) — in the exact summation order of the
  removed per-pair dispatch loop, so results are bit-identical to it.

Traced dispatch count per matmul direction is therefore 1 at every
bit-width (it was ``Lx·Lw`` ≤ 9 separate ``pallas_call``s, re-streaming
every operand tile per pair and combining partials in XLA — DESIGN.md §2).

Three contraction layouts cover forward and backward (DESIGN.md §2):

* ``bfp_matmul``     — NN: ``X (M,K) · W (K,N)``       (forward)
* ``bfp_matmul_nt``  — NT: ``G (M,N) · Wᵀ, W (K,N)``   (backward dX)
* ``bfp_matmul_tn``  — TN: ``Xᵀ · G,  X (M,K), G (M,N)`` (backward dW)

The NT/TN kernels contract the shared axis *in place* (dot_general dimension
numbers inside the kernel) — the transposed operand is never materialized in
HBM; only its block index map changes.

Each layout also has a **batched** variant (``bfp_matmul_batched{,_nt,_tn}``)
for the MoE expert stack ``Y[e] = X[e] · W[e]``: operands are plane-major
``(L, E, M, K)`` stacks, the grid gains a leading expert dimension (which
composes with the in-block limb planes — one ``pallas_call`` covers all
experts AND all limb pairs), and the scalar ``out_exp`` operand becomes a
per-expert **vector** ``(E,)`` — the epilogue of grid slice ``e`` scales by
``2**out_exp[e]``.

MXU alignment: block shapes are multiples of 128 in the N/K lanes and 8 in
sublanes; defaults (128, 128, 128) match the MXU natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single source of the limb radix: the combine's 2^(7(jx+jw)) shifts MUST
# match the digit split in the quantize kernel.
from repro.kernels.dfx_quant import LIMB_BITS  # noqa: E402


def _combine_partials(acc_ref, exp_f32, lx: int, lw: int):
    """Ordered f32 combine of the per-pair int32 partials.

    Iterates x-limbs outer / w-limbs inner and sums sequentially — the exact
    order of the per-pair dispatch loop this kernel replaced.  The scale is
    applied as ``exp2(exp) * 2^(7(jx+jw))`` — ``exp2`` once on the raw
    exponent (what each of the old per-pair kernels computed) and then a
    power-of-two literal multiply (exact; what the old XLA combine applied)
    — NOT as ``exp2(exp + 7(jx+jw))``: this backend's ``exp2`` is not
    correctly rounded at every integer argument, so folding the shift into
    the exp2 argument would change the result.  Keeping the two-multiply
    form makes the fused output bit-identical to the removed path.
    """
    scale0 = jnp.exp2(exp_f32)
    out = None
    for jx in range(lx):
        for jw in range(lw):
            part = (acc_ref[jx * lw + jw].astype(jnp.float32) * scale0
                    ) * (2.0 ** (LIMB_BITS * (jx + jw)))
            out = part if out is None else out + part
    return out


def _bfp_matmul_kernel(x_ref, w_ref, exp_ref, o_ref, acc_ref, *,
                       n_k: int, dims, lx: int, lw: int):
    """One (i, j, k) grid step: acc[q] += contract(x_blk[jx], w_blk[jw]).

    ``x_ref``/``w_ref`` hold the FULL limb stacks of the operand tiles
    (shape ``(lx, bm, bk)`` / ``(lw, bk, bn)``); the limb-pair loop is
    statically unrolled, one int32 MXU contraction per pair into its own
    accumulator plane.  ``dims`` is the in-kernel dot_general contraction:
    (1,0) for NN, (1,1) for NT, (0,0) for TN.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 limb mantissas -> int32 MXU accumulate, bit-exact per pair.
    lc, rc = dims
    for jx in range(lx):
        for jw in range(lw):
            acc_ref[jx * lw + jw] += jax.lax.dot_general(
                x_ref[jx], w_ref[jw],
                (((lc,), (rc,)), ((), ())),
                preferred_element_type=jnp.int32,
            )

    @pl.when(k == n_k - 1)
    def _epilogue():
        # Cross-limb combine + fused non-linear inverse mapping (Fig. 2).
        o_ref[...] = _combine_partials(
            acc_ref, exp_ref[0].astype(jnp.float32), lx, lw)


def _bfp_call(xm, wm, out_exp, *, name, out_shape, grid, x_spec, w_spec,
              out_spec, dims, interpret):
    assert xm.dtype == jnp.int8 and wm.dtype == jnp.int8, (xm.dtype, wm.dtype)
    n_k = grid[2]
    lx, lw = xm.shape[0], wm.shape[0]
    return pl.pallas_call(
        functools.partial(_bfp_matmul_kernel, n_k=n_k, dims=dims,
                          lx=lx, lw=lw),
        grid=grid,
        in_specs=[
            x_spec,
            w_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalar exp
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((lx * lw,) + out_spec.block_shape, jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(xm, wm, jnp.reshape(out_exp, (1,)).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul(
    xm: jax.Array,          # (Lx, M, K) int8 limb planes
    wm: jax.Array,          # (Lw, K, N) int8 limb planes
    out_exp: jax.Array,     # scalar int32: x_exp + w_exp
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """NN: ``(x @ w) * 2**out_exp`` -> (M, N) f32, all limb pairs fused."""
    Lx, M, K = xm.shape
    Lw, K2, N = wm.shape
    assert K == K2, (xm.shape, wm.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shapes ({M},{K})x({K},{N}) must tile by ({bm},{bn},{bk})")
    return _bfp_call(
        xm, wm, out_exp,
        name="bfp_matmul",
        out_shape=(M, N),
        grid=(M // bm, N // bn, K // bk),
        x_spec=pl.BlockSpec((Lx, bm, bk), lambda i, j, k: (0, i, k)),
        w_spec=pl.BlockSpec((Lw, bk, bn), lambda i, j, k: (0, k, j)),
        out_spec=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        dims=(1, 0),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_nt(
    gm: jax.Array,          # (Lg, M, N) int8 limb planes (upstream grad)
    wm: jax.Array,          # (Lw, K, N) int8 limb planes (weight, row-major)
    out_exp: jax.Array,     # scalar int32: g_exp + w_exp
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """NT: ``(g @ wᵀ) * 2**out_exp`` -> (M, K) f32 — the dX product.

    The contracted axis is N (last of both operands); wm keeps its forward
    (K, N) layout, the kernel swaps its block index map instead of
    materializing a transpose.
    """
    Lg, M, N = gm.shape
    Lw, K, N2 = wm.shape
    assert N == N2, (gm.shape, wm.shape)
    assert M % bm == 0 and K % bn == 0 and N % bk == 0, (
        f"shapes ({M},{N})x({K},{N}) must tile by ({bm},{bn},{bk})")
    return _bfp_call(
        gm, wm, out_exp,
        name="bfp_matmul_nt",
        out_shape=(M, K),
        grid=(M // bm, K // bn, N // bk),
        x_spec=pl.BlockSpec((Lg, bm, bk), lambda i, j, k: (0, i, k)),
        w_spec=pl.BlockSpec((Lw, bn, bk), lambda i, j, k: (0, j, k)),
        out_spec=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        dims=(1, 1),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_tn(
    xm: jax.Array,          # (Lx, M, K) int8 limb planes (saved activation)
    gm: jax.Array,          # (Lg, M, N) int8 limb planes (upstream grad)
    out_exp: jax.Array,     # scalar int32: x_exp + g_exp
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """TN: ``(xᵀ @ g) * 2**out_exp`` -> (K, N) f32 — the dW product.

    The contracted axis is M (first mantissa axis of both operands); xm keeps
    its forward (M, K) layout, the kernel swaps its block index map.
    """
    Lx, M, K = xm.shape
    Lg, M2, N = gm.shape
    assert M == M2, (xm.shape, gm.shape)
    assert K % bm == 0 and N % bn == 0 and M % bk == 0, (
        f"shapes ({M},{K})x({M},{N}) must tile by ({bm},{bn},{bk})")
    return _bfp_call(
        xm, gm, out_exp,
        name="bfp_matmul_tn",
        out_shape=(K, N),
        grid=(K // bm, N // bn, M // bk),
        x_spec=pl.BlockSpec((Lx, bk, bm), lambda i, j, k: (0, k, i)),
        w_spec=pl.BlockSpec((Lg, bk, bn), lambda i, j, k: (0, k, j)),
        out_spec=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        dims=(0, 0),
        interpret=interpret,
    )


# =========================================================================
# Batched (expert-axis) variants — grid: (E, i, j, k), exp: (E,) vector
# =========================================================================

def _bfp_matmul_batched_kernel(x_ref, w_ref, exp_ref, o_ref, acc_ref, *,
                               n_k: int, dims, lx: int, lw: int):
    """One (e, i, j, k) grid step over the full limb stacks of expert ``e``.

    Identical limb-pair contraction to the unbatched kernel on the trailing
    two block dims; the epilogue scale is the *per-expert* exponent
    ``exp_ref[e]``.
    """
    e = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lc, rc = dims
    for jx in range(lx):
        for jw in range(lw):
            acc_ref[jx * lw + jw] += jax.lax.dot_general(
                x_ref[jx, 0], w_ref[jw, 0],
                (((lc,), (rc,)), ((), ())),
                preferred_element_type=jnp.int32,
            )

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[0] = _combine_partials(
            acc_ref, exp_ref[e].astype(jnp.float32), lx, lw)


def _bfp_batched_call(xm, wm, out_exp, *, name, out_shape, grid, x_spec,
                      w_spec, out_spec, dims, interpret):
    assert xm.dtype == jnp.int8 and wm.dtype == jnp.int8, (xm.dtype, wm.dtype)
    n_k = grid[3]
    lx, lw = xm.shape[0], wm.shape[0]
    return pl.pallas_call(
        functools.partial(_bfp_matmul_batched_kernel, n_k=n_k, dims=dims,
                          lx=lx, lw=lw),
        grid=grid,
        in_specs=[
            x_spec,
            w_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),   # (E,) exp vector
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((lx * lw,) + out_spec.block_shape[1:], jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name=name,
        interpret=interpret,
    )(xm, wm, jnp.reshape(out_exp, (-1,)).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_batched(
    xm: jax.Array,          # (Lx, E, M, K) int8 limb planes
    wm: jax.Array,          # (Lw, E, K, N) int8 limb planes
    out_exp: jax.Array,     # (E,) int32: x_exp[e] + w_exp[e]
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Batched NN: ``(x[e] @ w[e]) * 2**out_exp[e]`` -> (E, M, N) f32."""
    Lx, E, M, K = xm.shape
    Lw, E2, K2, N = wm.shape
    assert E == E2 and K == K2, (xm.shape, wm.shape)
    assert out_exp.shape == (E,), (out_exp.shape, E)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shapes ({E},{M},{K})x({E},{K},{N}) must tile by ({bm},{bn},{bk})")
    return _bfp_batched_call(
        xm, wm, out_exp,
        name="bfp_matmul_batched",
        out_shape=(E, M, N),
        grid=(E, M // bm, N // bn, K // bk),
        x_spec=pl.BlockSpec((Lx, 1, bm, bk), lambda e, i, j, k: (0, e, i, k)),
        w_spec=pl.BlockSpec((Lw, 1, bk, bn), lambda e, i, j, k: (0, e, k, j)),
        out_spec=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        dims=(1, 0),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_batched_nt(
    gm: jax.Array,          # (Lg, E, M, N) grad limb planes
    wm: jax.Array,          # (Lw, E, K, N) weight limb planes, forward layout
    out_exp: jax.Array,     # (E,) int32: g_exp[e] + w_exp[e]
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Batched NT: ``(g[e] @ w[e]ᵀ) * 2**out_exp[e]`` -> (E, M, K) f32."""
    Lg, E, M, N = gm.shape
    Lw, E2, K, N2 = wm.shape
    assert E == E2 and N == N2, (gm.shape, wm.shape)
    assert out_exp.shape == (E,), (out_exp.shape, E)
    assert M % bm == 0 and K % bn == 0 and N % bk == 0, (
        f"shapes ({E},{M},{N})x({E},{K},{N}) must tile by ({bm},{bn},{bk})")
    return _bfp_batched_call(
        gm, wm, out_exp,
        name="bfp_matmul_batched_nt",
        out_shape=(E, M, K),
        grid=(E, M // bm, K // bn, N // bk),
        x_spec=pl.BlockSpec((Lg, 1, bm, bk), lambda e, i, j, k: (0, e, i, k)),
        w_spec=pl.BlockSpec((Lw, 1, bn, bk), lambda e, i, j, k: (0, e, j, k)),
        out_spec=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        dims=(1, 1),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_batched_tn(
    xm: jax.Array,          # (Lx, E, M, K) activation limb planes
    gm: jax.Array,          # (Lg, E, M, N) grad limb planes
    out_exp: jax.Array,     # (E,) int32: x_exp[e] + g_exp[e]
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Batched TN: ``(x[e]ᵀ @ g[e]) * 2**out_exp[e]`` -> (E, K, N) f32."""
    Lx, E, M, K = xm.shape
    Lg, E2, M2, N = gm.shape
    assert E == E2 and M == M2, (xm.shape, gm.shape)
    assert out_exp.shape == (E,), (out_exp.shape, E)
    assert K % bm == 0 and N % bn == 0 and M % bk == 0, (
        f"shapes ({E},{M},{K})x({E},{M},{N}) must tile by ({bm},{bn},{bk})")
    return _bfp_batched_call(
        xm, gm, out_exp,
        name="bfp_matmul_batched_tn",
        out_shape=(E, K, N),
        grid=(E, K // bm, N // bn, M // bk),
        x_spec=pl.BlockSpec((Lx, 1, bk, bm), lambda e, i, j, k: (0, e, k, i)),
        w_spec=pl.BlockSpec((Lg, 1, bk, bn), lambda e, i, j, k: (0, e, k, j)),
        out_spec=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        dims=(0, 0),
        interpret=interpret,
    )
