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
* when the contraction spans several grid steps, each pair accumulates
  bit-exactly into its own int32 VMEM scratch plane across them; when one
  block holds the whole contraction, the pair products go straight to the
  combine (integer sums are exact, so the bits are the same);
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

The **grouped** variants (``bfp_matmul_grouped{,_nt,_tn}``) take rows
sorted by expert with run-time group offsets (an expert share's SwiGLU
products, see the section at the end of this file).

Blocks: ``(bm, bn, bk)`` is the output tile and the contracted block in
every layout; lane dims take multiples of 128 and sublane dims multiples of
8.  The callers size them to the shapes and limb count
(``kernels/ops.py::_pick_blocks``), and each call asks Mosaic for the
scoped VMEM its blocks need (``_vmem_limit``).  The (128, 128, 128)
defaults are the smallest MXU-native tiles.
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


#: scoped VMEM a Mosaic kernel gets when it asks for no limit.
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024


def matmul_vmem_bytes(bm: int, bn: int, bk: int, lx: int = 1, lw: int = 1,
                      n_k: int = 1) -> int:
    """VMEM bytes one grid step of the fused limb matmul keeps resident.

    ``bm``×``bn`` is the output tile and ``bk`` the contracted block, in
    every layout.  Counted: the double-buffered int8 operand blocks (all
    ``lx``/``lw`` planes of a tile arrive together), the double-buffered f32
    output block, one int32 pair product and the f32 running sum of the
    combine, and — only when the contraction spans ``n_k > 1`` grid steps —
    one int32 accumulator plane per limb pair.
    """
    ws = (2 * (lx * bm * bk + lw * bk * bn)     # int8 operand stacks
          + 2 * bm * bn * 4                     # f32 output block
          + 2 * bm * bn * 4)                    # pair product, running sum
    if n_k > 1:
        ws += lx * lw * bm * bn * 4             # per-pair accumulators
    return ws


def _vmem_limit(working_set: int) -> int:
    """Scoped VMEM to ask Mosaic for: the modelled working set plus half
    again for the compiler's own temporaries, never below the default."""
    return max(_SCOPED_VMEM_DEFAULT, working_set + working_set // 2)


def _combine_partials(partial, exp_f32, lx: int, lw: int):
    """Ordered f32 combine of the per-pair int32 partials ``partial(jx, jw)``.

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
            part = (partial(jx, jw).astype(jnp.float32) * scale0
                    ) * (2.0 ** (LIMB_BITS * (jx + jw)))
            out = part if out is None else out + part
    return out


def _bfp_matmul_kernel(x_ref, w_ref, exp_ref, o_ref, *acc_ref,
                       n_k: int, dims, lx: int, lw: int, batched: bool):
    """One grid step: every limb pair of one output tile.

    ``x_ref``/``w_ref`` hold the FULL limb stacks of the operand tiles
    (``(lx, ·, ·)`` / ``(lw, ·, ·)``); the limb-pair loop is statically
    unrolled, one int8×int8→int32 MXU contraction per pair.  ``dims`` is the
    in-kernel dot_general contraction: (1,0) for NN, (1,1) for NT, (0,0) for
    TN.  With the whole contraction in one block (``n_k == 1``) the pair
    products feed the combine directly; otherwise each accumulates into its
    own int32 scratch plane across the last grid axis and the combine runs
    on the last step.  Integer accumulation is exact, so both give the same
    bits.  Batched grids lead with the expert axis, whose exponent is
    ``exp_ref[e]``.
    """
    lc, rc = dims
    e = pl.program_id(0) if batched else 0

    def product(jx, jw):
        return jax.lax.dot_general(
            x_ref[jx], w_ref[jw], (((lc,), (rc,)), ((), ())),
            preferred_element_type=jnp.int32)

    if n_k == 1:
        o_ref[...] = _combine_partials(
            product, exp_ref[e].astype(jnp.float32), lx, lw)
        return

    acc_ref, = acc_ref
    k = pl.program_id(3 if batched else 2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for jx in range(lx):
        for jw in range(lw):
            acc_ref[jx * lw + jw] += product(jx, jw)

    @pl.when(k == n_k - 1)
    def _epilogue():
        # Cross-limb combine + fused non-linear inverse mapping (Fig. 2).
        o_ref[...] = _combine_partials(
            lambda jx, jw: acc_ref[jx * lw + jw],
            exp_ref[e].astype(jnp.float32), lx, lw)


def _bfp_call(xm, wm, out_exp, *, name, out_shape, blocks, grid, x_spec,
              w_spec, out_spec, dims, interpret):
    """One ``pallas_call`` over all limb pairs; a 4-D grid is batched
    (expert axis first) and takes an ``(E,)`` exponent vector."""
    assert xm.dtype == jnp.int8 and wm.dtype == jnp.int8, (xm.dtype, wm.dtype)
    batched = len(grid) == 4
    n_k = grid[-1]
    lx, lw = xm.shape[0], wm.shape[0]
    bm, bn, bk = blocks
    scratch = ([pltpu.VMEM((lx * lw, bm, bn), jnp.int32)] if n_k > 1
               else [])
    vmem_limit = _vmem_limit(matmul_vmem_bytes(bm, bn, bk, lx, lw, n_k))
    out = pl.pallas_call(
        functools.partial(_bfp_matmul_kernel, n_k=n_k, dims=dims,
                          lx=lx, lw=lw, batched=batched),
        grid=grid,
        in_specs=[
            x_spec,
            w_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),   # exp scalar / vector
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        name=name,
        interpret=interpret,
    )(xm, wm, jnp.reshape(out_exp, (-1,)).astype(jnp.int32))
    if vmem_limit > _SCOPED_VMEM_DEFAULT and dims == (0, 0):
        # A dW (TN) product feeds the layer scan's stacked gradient: XLA
        # fuses that dynamic-update-slice into the kernel and compiles the
        # fusion under the default scoped VMEM, not this call's limit.
        out = jax.lax.optimization_barrier(out)
    return out


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
        blocks=(bm, bn, bk),
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
        blocks=(bm, bn, bk),
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
        blocks=(bm, bn, bk),
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
    return _bfp_call(
        xm, wm, out_exp,
        name="bfp_matmul_batched",
        out_shape=(E, M, N),
        blocks=(bm, bn, bk),
        grid=(E, M // bm, N // bn, K // bk),
        x_spec=pl.BlockSpec((Lx, None, bm, bk),
                            lambda e, i, j, k: (0, e, i, k)),
        w_spec=pl.BlockSpec((Lw, None, bk, bn),
                            lambda e, i, j, k: (0, e, k, j)),
        out_spec=pl.BlockSpec((None, bm, bn), lambda e, i, j, k: (e, i, j)),
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
    return _bfp_call(
        gm, wm, out_exp,
        name="bfp_matmul_batched_nt",
        out_shape=(E, M, K),
        blocks=(bm, bn, bk),
        grid=(E, M // bm, K // bn, N // bk),
        x_spec=pl.BlockSpec((Lg, None, bm, bk),
                            lambda e, i, j, k: (0, e, i, k)),
        w_spec=pl.BlockSpec((Lw, None, bn, bk),
                            lambda e, i, j, k: (0, e, j, k)),
        out_spec=pl.BlockSpec((None, bm, bn), lambda e, i, j, k: (e, i, j)),
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
    return _bfp_call(
        xm, gm, out_exp,
        name="bfp_matmul_batched_tn",
        out_shape=(E, K, N),
        blocks=(bm, bn, bk),
        grid=(E, K // bm, N // bn, M // bk),
        x_spec=pl.BlockSpec((Lx, None, bk, bm),
                            lambda e, i, j, k: (0, e, k, i)),
        w_spec=pl.BlockSpec((Lg, None, bk, bn),
                            lambda e, i, j, k: (0, e, k, j)),
        out_spec=pl.BlockSpec((None, bm, bn), lambda e, i, j, k: (e, i, j)),
        dims=(0, 0),
        interpret=interpret,
    )


# =========================================================================
# Grouped (sorted-rows) variants — rows sorted by expert, each group padded
# to the row tile; the group offsets ride in as a scalar-prefetch operand
# =========================================================================
#
# ``offsets`` is ``(G+1,)`` int32: group ``g`` owns rows ``[offsets[g],
# offsets[g+1])``, every group at least one row tile long, and rows at and
# past ``offsets[G]`` are unused.  The sizes are known only at run time, so
# the grid covers every row block the buffer can hold; a block past the
# used rows re-points its inputs at the last used block (a repeated block
# index is not fetched again) and does no MXU work.  Its rows of a
# row-split output (NN, NT) are written as zeros, so what follows the
# kernel never reads stale memory.

def _group_of(off_ref, row, groups: int):
    """The group that holds ``row``: how many group starts past the first
    lie at or below it."""
    g = jnp.int32(0)
    for h in range(1, groups):
        g = g + jnp.where(row >= off_ref[h], 1, 0).astype(jnp.int32)
    return g


def _last_block(off_ref, groups: int, block: int):
    """Index of the last used block of ``block`` rows."""
    return off_ref[groups] // block - 1


def _bfp_grouped_rows_kernel(off_ref, x_ref, w_ref, exp_ref, o_ref, *acc_ref,
                             n_k: int, dims, lx: int, lw: int, groups: int,
                             bm: int):
    """NN / NT over sorted rows: the row block's group picks the weight
    block and the exponent; a block past the used rows writes zeros."""
    lc, rc = dims
    row0 = pl.program_id(0) * bm
    live = row0 < off_ref[groups]
    g = _group_of(off_ref, row0, groups)

    def product(jx, jw):
        return jax.lax.dot_general(
            x_ref[jx], w_ref[jw], (((lc,), (rc,)), ((), ())),
            preferred_element_type=jnp.int32)

    def dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    if n_k == 1:
        @pl.when(live)
        def _live():
            o_ref[...] = _combine_partials(
                product, exp_ref[g].astype(jnp.float32), lx, lw)

        pl.when(jnp.logical_not(live))(dead)
        return

    acc_ref, = acc_ref
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(live, k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        for jx in range(lx):
            for jw in range(lw):
                acc_ref[jx * lw + jw] += product(jx, jw)

    @pl.when(jnp.logical_and(live, k == n_k - 1))
    def _epilogue():
        o_ref[...] = _combine_partials(
            lambda jx, jw: acc_ref[jx * lw + jw],
            exp_ref[g].astype(jnp.float32), lx, lw)

    pl.when(jnp.logical_and(jnp.logical_not(live), k == n_k - 1))(dead)


def _bfp_grouped_tn_kernel(off_ref, x_ref, g_ref, exp_ref, o_ref, acc_ref, *,
                           lx: int, lg: int, groups: int, bk: int):
    """TN over sorted rows: the contraction runs over one group's row blocks
    in turn, accumulating exactly in int32, and the group's (K, N) product
    is combined on its last block."""
    row0 = pl.program_id(2) * bk
    live = row0 < off_ref[groups]
    g = _group_of(off_ref, row0, groups)

    @pl.when(jnp.logical_and(live, row0 == off_ref[g]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        for jx in range(lx):
            for jg in range(lg):
                acc_ref[jx * lg + jg] += jax.lax.dot_general(
                    x_ref[jx], g_ref[jg], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)

    @pl.when(jnp.logical_and(live, row0 + bk == off_ref[g + 1]))
    def _epilogue():
        o_ref[...] = _combine_partials(
            lambda jx, jg: acc_ref[jx * lg + jg],
            exp_ref[g].astype(jnp.float32), lx, lg)


def _grouped_call(kernel, lhs, rhs, out_exp, offsets, *, name, out_shape,
                  grid, lhs_spec, rhs_spec, out_spec, blocks, n_k, accumulate,
                  interpret):
    """One ``pallas_call`` over sorted rows; ``offsets`` is the scalar
    prefetch operand every index map and the kernel read."""
    assert lhs.dtype == jnp.int8 and rhs.dtype == jnp.int8, (lhs.dtype,
                                                            rhs.dtype)
    lx, lw = lhs.shape[0], rhs.shape[0]
    bm, bn, bk = blocks
    scratch = ([pltpu.VMEM((lx * lw, bm, bn), jnp.int32)] if accumulate
               else [])
    vmem_limit = _vmem_limit(matmul_vmem_bytes(bm, bn, bk, lx, lw,
                                               2 if accumulate else n_k))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[lhs_spec, rhs_spec,
                      pl.BlockSpec(memory_space=pltpu.SMEM)],  # (G,) exps
            out_specs=out_spec,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        name=name,
        interpret=interpret,
    )(offsets.astype(jnp.int32), lhs, rhs, out_exp.astype(jnp.int32))


def _grouped_rows(xm, wm, out_exp, offsets, *, name, dims, bm, bn, bk,
                  interpret):
    """NN (``dims`` (1, 0), W (G, K, N)) or NT (``dims`` (1, 1), W (G, N,
    K) in forward layout) over the sorted rows of ``xm`` (L, M, K)."""
    lx, M, K = xm.shape
    lw, G = wm.shape[:2]
    N = wm.shape[3] if dims == (1, 0) else wm.shape[2]
    assert wm.shape[2 if dims == (1, 0) else 3] == K, (xm.shape, wm.shape)
    assert offsets.shape == (G + 1,) and out_exp.shape == (G,), (
        offsets.shape, out_exp.shape, G)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"rows ({M},{K}) x groups {wm.shape[1:]} must tile by ({bm},{bn},{bk})")
    n_k = K // bk

    def rows(i, off):
        return jnp.minimum(i, _last_block(off, G, bm))

    def group(i, off):
        return _group_of(off, rows(i, off) * bm, G)

    if dims == (1, 0):
        w_spec = pl.BlockSpec((lw, None, bk, bn),
                              lambda i, j, k, off: (0, group(i, off), k, j))
    else:
        w_spec = pl.BlockSpec((lw, None, bn, bk),
                              lambda i, j, k, off: (0, group(i, off), j, k))
    return _grouped_call(
        functools.partial(_bfp_grouped_rows_kernel, n_k=n_k, dims=dims,
                          lx=lx, lw=lw, groups=G, bm=bm),
        xm, wm, out_exp, offsets, name=name, out_shape=(M, N),
        grid=(M // bm, N // bn, n_k),
        lhs_spec=pl.BlockSpec((lx, bm, bk),
                              lambda i, j, k, off: (0, rows(i, off), k)),
        rhs_spec=w_spec,
        out_spec=pl.BlockSpec((bm, bn), lambda i, j, k, off: (i, j)),
        blocks=(bm, bn, bk), n_k=n_k, accumulate=n_k > 1,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_grouped(
    xm: jax.Array,          # (Lx, M, K) int8 limb planes, rows sorted by group
    wm: jax.Array,          # (Lw, G, K, N) int8 limb planes
    out_exp: jax.Array,     # (G,) int32: x_exp[g] + w_exp[g]
    offsets: jax.Array,     # (G+1,) int32 group starts, then the used rows
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Grouped NN: row ``r`` of group ``g`` -> ``(x[r] @ w[g]) *
    2**out_exp[g]``; (M, N) f32, zero past the used rows."""
    return _grouped_rows(xm, wm, out_exp, offsets, name="bfp_matmul_grouped",
                         dims=(1, 0), bm=bm, bn=bn, bk=bk,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_grouped_nt(
    gm: jax.Array,          # (Lg, M, N) grad limb planes, rows sorted
    wm: jax.Array,          # (Lw, G, K, N) weight limb planes, forward layout
    out_exp: jax.Array,     # (G,) int32: g_exp[g] + w_exp[g]
    offsets: jax.Array,     # (G+1,) int32
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Grouped NT: ``(g[r] @ w[g]ᵀ) * 2**out_exp[g]`` -> (M, K) f32 — the
    dX product, W in its forward layout."""
    return _grouped_rows(gm, wm, out_exp, offsets,
                         name="bfp_matmul_grouped_nt", dims=(1, 1),
                         bm=bm, bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bfp_matmul_grouped_tn(
    xm: jax.Array,          # (Lx, M, K) activation limb planes, rows sorted
    gm: jax.Array,          # (Lg, M, N) grad limb planes, rows sorted
    out_exp: jax.Array,     # (G,) int32: x_exp[g] + g_exp[g]
    offsets: jax.Array,     # (G+1,) int32
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Grouped TN: ``(x[rows of g]ᵀ @ g[rows of g]) * 2**out_exp[g]`` ->
    (G, K, N) f32 — the dW product.  ``bk`` row blocks tile every group, so
    a group's contraction is its own blocks, accumulated in int32."""
    lx, M, K = xm.shape
    lg, M2, N = gm.shape
    G = out_exp.shape[0]
    assert M == M2 and offsets.shape == (G + 1,), (xm.shape, gm.shape,
                                                   offsets.shape)
    assert K % bm == 0 and N % bn == 0 and M % bk == 0, (
        f"rows ({M},{K})x({M},{N}) must tile by ({bm},{bn},{bk})")

    def rows(r, off):
        return jnp.minimum(r, _last_block(off, G, bk))

    return _grouped_call(
        functools.partial(_bfp_grouped_tn_kernel, lx=lx, lg=lg, groups=G,
                          bk=bk),
        xm, gm, out_exp, offsets, name="bfp_matmul_grouped_tn",
        out_shape=(G, K, N), grid=(K // bm, N // bn, M // bk),
        lhs_spec=pl.BlockSpec((lx, bk, bm),
                              lambda i, j, r, off: (0, rows(r, off), i)),
        rhs_spec=pl.BlockSpec((lg, bk, bn),
                              lambda i, j, r, off: (0, rows(r, off), j)),
        out_spec=pl.BlockSpec(
            (None, bm, bn),
            lambda i, j, r, off: (_group_of(off, rows(r, off) * bk, G), i, j)),
        blocks=(bm, bn, bk), n_k=M // bk, accumulate=True,
        interpret=interpret)
