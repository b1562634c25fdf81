"""jit'd public wrappers over the Pallas kernels.

Adds the pieces that keep the kernels simple:

* **int8 limb-plane layout** for mantissas wider than 8 bits — the TPU MXU
  multiplies int8×int8, so a ``b <= 16``-bit mantissa is carried as a stack
  of **balanced base-2⁷ digit planes** ``m = sum_j plane_j · 2^(7j)`` with
  every non-final digit in ``[-64, 63]`` (the final plane keeps the raw
  carry, ``|carry| <= 64``).  b<=8 is 1 plane, 8<b<=14 is 2, b<=16 is 3.
  The split is **fused into the quantize kernel** (``dfx_quantize(...,
  limb_planes=True)``) and ALL limb pairs of a matmul run in ONE
  ``pallas_call`` (in-kernel unrolled pair loop, per-pair bit-exact int32
  accumulators, ordered f32 cross-limb combine in the epilogue — rounding
  ~1 ulp of the largest partial, DESIGN.md §2).  Dispatch count per matmul
  direction is 1 at every bit-width; the former per-pair dispatch loop
  issued up to 3×3 = 9 kernel launches and re-streamed every operand tile
  from HBM once per pair.
* shape padding to MXU tile multiples, and un-padding of the result;
* automatic ``interpret=True`` when not running on real TPU hardware;
* one ``shard_map`` per kernel call over the active mesh's batch axes
  (``sharding.over_batch``): XLA cannot partition a Mosaic kernel, so each
  chip runs the kernel on its share of rows with the weights replicated,
  and the products that contract over rows (dW, the norm dgamma/dbeta) are
  summed across chips with ``psum``.

Three matmul layouts cover the integer layers end-to-end (DESIGN.md §2):

* ``dfx_matmul_tiled``    — forward  ``q(X)·q(W)``
* ``dfx_matmul_tiled_nt`` — backward ``dX = q(G)·q(W)ᵀ``
* ``dfx_matmul_tiled_tn`` — backward ``dW = q(X)ᵀ·q(G)``

Each accepts either the stacked limb planes emitted by the quantize kernel
(the layer hot path — no split arithmetic appears in the traced jaxpr) or a
logical int mantissa tensor, which is converted via ``split_limbs_stacked``
(an XLA convenience path for tests and ad-hoc callers).

The NT/TN variants keep both operands in their forward (row-major) layout —
the transpose happens inside the kernel via the block index maps, never as a
materialized HBM copy.

Each layout has a **batched** twin for the MoE expert stack —
``dfx_matmul_tiled_batched{,_nt,_tn}`` take plane-major (L, E, ...) mantissa
stacks and (E,)-vector scale exponents and issue ONE ``pallas_call`` per
direction with the expert axis as a leading parallel grid dimension
composing with the in-block limb planes.  ``quantize_pallas_batched`` is the
matching grouped-scale quantizer.

The norm layers get four fused entry points over ``kernels/int_norm.py`` —
``layernorm_pallas`` / ``layernorm_bwd_pallas`` and ``rmsnorm_pallas`` /
``rmsnorm_bwd_pallas``: the forwards are multi-output (y + the value-domain
statistics the kernel normalized with, saved as backward residuals), the
backwards compute dx plus per-row-block dgamma/dbeta partials whose
cross-block combine is the only XLA epilogue.  All four share the same
row-padding pattern (zero rows are exact; padded gradient mantissas are
zero, so padded rows contribute nothing to the parameter-gradient partials).
They consume *logical* mantissas (int16 at b=16), not limb planes.

Attention gets three fused entry points over ``kernels/int_attention.py`` —
``attention_fwd`` (o + per-row lse) and ``attention_bwd`` (dq, dk, dv via
the two FA2-style kernels).  These wrappers own the "rows" layout
transform: model-layout limb planes (L, B, Sq, KV, G, hd) / (L, B, Sk, KV,
hd) are transposed + zero-padded + reshaped to the kernels' (L, B·KV,
G·Sq_p, hd_p) / (L, B·KV, Sk_p, hd_p) form and the outputs trimmed back.
Zero-padding is exact everywhere except the backward's saved ``lse`` rows,
which pad with **+1e30** so recomputed ``p = exp(s - lse)`` vanishes on
padded rows (a zero-padded lse would make it blow up instead).
"""
from __future__ import annotations

import functools
import itertools

import numpy as np

import jax
import jax.numpy as jnp

from repro import sharding
from repro.kernels.bfp_matmul import (bfp_matmul, bfp_matmul_batched,
                                      bfp_matmul_batched_nt,
                                      bfp_matmul_batched_tn, bfp_matmul_grouped,
                                      bfp_matmul_grouped_nt,
                                      bfp_matmul_grouped_tn, bfp_matmul_nt,
                                      bfp_matmul_tn, matmul_vmem_bytes)
from repro.kernels.dfx_quant import (LIMB_BITS as _LIMB_BITS, _out_dtype,
                                     dfx_quantize, dfx_quantize_grouped,
                                     n_limbs)
from repro.kernels import int_attention as _ia
from repro.kernels.int_attention import (attention_vmem_bytes,
                                         int_attn_bwd_dkv, int_attn_bwd_dq,
                                         int_attn_fwd)
from repro.kernels.int_norm import (int_layernorm_bwd, int_layernorm_fwd,
                                    int_rmsnorm_bwd, int_rmsnorm_fwd)

#: MXU lane width: the last block dimension must be a multiple of this.
_LANE = 128

#: VPU sublane width: the second-to-last block dimension's multiple.
_SUBLANE = 8

#: VMEM budget for one quantize grid step (blocks double-buffered) — half of
#: the 16 MiB scoped VMEM a v5e kernel gets by default, so the compiler
#: keeps headroom for in-kernel temporaries.
_VMEM_BUDGET = 8 * 1024 * 1024

#: VMEM budget for one limb-matmul grid step (``matmul_vmem_bytes``).  The
#: kernel asks Mosaic for a scoped limit derived from the blocks it gets
#: (``bfp_matmul._vmem_limit``), so this only has to stay well inside
#: the 128 MiB of VMEM a v5e core has.
_MATMUL_VMEM_BUDGET = 48 * 1024 * 1024

#: most int8 multiply-accumulates one limb-matmul grid step may do, over all
#: its limb pairs.  Mosaic unrolls a step's dots and combine in full, so its
#: compile time grows with this; on a v5e, 3x3-limb steps of 2e9-8e9 MACs
#: ran within 3% of each other and steps of ~1e10 ran 15-20% slower
#: (the block sweep in PERF.md).
_STEP_MACS = 1 << 32

#: the quantize kernel's row block never exceeds this many rows.
_QUANT_ROWS = 256

#: scoped VMEM a Mosaic kernel gets when it asks for no limit.
_SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def split_limbs_stacked(m: jax.Array, bits: int) -> jax.Array:
    """Stacked balanced base-2⁷ limb planes of a logical integer mantissa.

    Returns an int8 array of shape ``(L,) + m.shape`` with
    ``m = sum_j planes[j] * 2**(7*j)`` — the same digit set the quantize
    kernel emits in its fused split (``dfx_quantize(limb_planes=True)``).
    XLA convenience/reference path only: the layer hot path gets its planes
    straight from the quantize kernel and never runs this.

    Non-final digits are the balanced remainder in [-64, 63]; the final
    plane keeps the raw carry (|carry| <= 64 for every b <= 16 — storing it
    unreduced fixes the b=14 corner where a final mod-extraction dropped a
    carry of ±1·2^14).
    """
    L = n_limbs(bits)
    if L == 1:
        return m.astype(jnp.int8)[None]
    m32 = m.astype(jnp.int32)
    base = 1 << _LIMB_BITS
    planes = []
    for _ in range(L - 1):
        # Balanced remainder in [-base/2, base/2): keeps digits centred so
        # the carry into the next plane is exact integer arithmetic.
        lo = ((m32 + base // 2) % base) - base // 2
        m32 = (m32 - lo) // base
        planes.append(lo.astype(jnp.int8))
    planes.append(m32.astype(jnp.int8))
    return jnp.stack(planes)


def _as_planes(m: jax.Array, bits: int, base_ndim: int) -> jax.Array:
    """Accept stacked limb planes or a logical mantissa (split on the fly)."""
    if m.ndim == base_ndim + 1:
        assert m.shape[0] == n_limbs(bits), (m.shape, bits)
        assert m.dtype == jnp.int8, m.dtype
        return m
    assert m.ndim == base_ndim, (m.shape, base_ndim)
    return split_limbs_stacked(m, bits)


def _round_up_multiple(x: int, mult: int) -> int:
    """Round ``x`` up to the next multiple of ``mult`` (at least ``mult``)."""
    r = ((x + mult - 1) // mult) * mult
    return max(r, mult)


def _padded(n: int, sublane: bool) -> int:
    """Extent a matmul operand dim is zero-padded to: a multiple of 128, or
    of 8 for a sublane dim shorter than 128 (small row counts, decode)."""
    if sublane and n < _LANE:
        return _round_up_multiple(n, _SUBLANE)
    return _round_up_multiple(n, _LANE)


def _tiles(extent: int, sublane: bool) -> list[int]:
    """Block sizes that tile a padded extent exactly, smallest first: the
    extent itself below 128, else every multiple of 128 that divides it —
    and, on a sublane dim, 8 to 64 rows for budgets 128 rows overflow."""
    if extent < _LANE:
        return [extent]
    small = [8, 16, 32, 64] if sublane else []
    return small + [t for t in range(_LANE, extent + 1, _LANE)
                    if extent % t == 0]


def _pick_blocks(M: int, N: int, K: int, lx: int = 1, lw: int = 1,
                 budget: int = _MATMUL_VMEM_BUDGET,
                 contract_rows: bool = False, accumulate: bool = False):
    """Blocks ``(bm, bn, bk)`` of an (M, N) output contracting K, with
    ``lx``×``lw`` limb planes.

    Every block divides its dim's padded extent (``_padded``), so no operand
    is padded further than to the next 128 rows or lanes — or 8 rows under
    128.  The operand rows are the sublane dim: the output rows ``M`` in NN
    and NT, the contraction ``K`` in TN (``contract_rows=True``), whose
    output tile is all lanes.  Among the blocks whose working set
    (``matmul_vmem_bytes``, limb planes and accumulators counted) fits the
    budget and whose step does at most ``_STEP_MACS`` limb-pair MACs, the
    chooser takes the whole contraction in one grid step if any block
    allows it, then the largest output tile, then the widest.
    """
    Mp, Np = _padded(M, not contract_rows), _padded(N, False)
    Kp = _padded(K, contract_rows)
    bms, bns = _tiles(Mp, not contract_rows), _tiles(Np, False)
    bks = _tiles(Kp, contract_rows)

    def fits(b):
        bm, bn, bk = b
        n_k = max(2, Kp // bk) if accumulate else Kp // bk
        return (matmul_vmem_bytes(bm, bn, bk, lx, lw, n_k) <= budget
                and lx * lw * bm * bn * bk <= _STEP_MACS)

    def rows_ok(b):
        # a row tile under 128 is for budgets 128 rows overflow: on a v5e
        # a (64, 3072, 2304) 3x3 step needed 73.9 MiB of scoped VMEM
        # against the 44.3 MiB modelled, while (128, 1536, 2304) fit
        rows, extent = (b[2], Kp) if contract_rows else (b[0], Mp)
        return rows >= min(_LANE, extent)

    fitting = [b for b in itertools.product(bms, bns, bks) if fits(b)]
    if not fitting:
        return bms[0], bns[0], bks[0]
    return max(fitting, key=lambda b: (b[2] == Kp, rows_ok(b), b[0] * b[1],
                                       b[1], b[2]))


def _pad_last2(a: jax.Array, r: int, c: int) -> jax.Array:
    """Pad the trailing two dims to (r, c) multiples; leading dims untouched.

    Zero padding is exact for every limb plane and every expert regardless
    of its scale exponent: zero mantissas contribute nothing to the integer
    accumulation, and a zero row quantizes to zero under any exponent.
    """
    *lead, M, N = a.shape
    pm = (-M) % r
    pn = (-N) % c
    if pm or pn:
        a = jnp.pad(a, [(0, 0)] * len(lead) + [(0, pm), (0, pn)])
    return a


def dfx_matmul_tiled(
    xm: jax.Array, x_exp: jax.Array, x_bits: int,
    wm: jax.Array, w_exp: jax.Array, w_bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """Integer DFX matmul via the fused single-dispatch Pallas kernel.

    xm: (Lx, M, K) int8 limb planes (or a logical (M, K) int mantissa, split
    here for convenience); wm: (Lw, K, N) / (K, N).  Returns FP32 ``(x·w)``
    dequantized.  One ``pallas_call`` regardless of bit-width.
    """
    if interpret is None:
        interpret = not on_tpu()
    out_exp = (x_exp + w_exp).astype(jnp.int32)

    def local(xm, wm, out_exp):
        _, M, K = xm.shape
        _, _, N = wm.shape
        bm, bn, bk = _pick_blocks(M, N, K, xm.shape[0], wm.shape[0])
        xm, wm = _pad_last2(xm, bm, bk), _pad_last2(wm, bk, bn)
        out = bfp_matmul(xm, wm, out_exp, bm=bm, bn=bn, bk=bk,
                         interpret=interpret)
        return out[:M, :N]

    return sharding.over_batch(
        local, (_as_planes(xm, x_bits, 2), _as_planes(wm, w_bits, 2), out_exp),
        (1, None, None), 0)


def dfx_matmul_tiled_nt(
    gm: jax.Array, g_exp: jax.Array, g_bits: int,
    wm: jax.Array, w_exp: jax.Array, w_bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """Backward dX product: ``q(G)·q(W)ᵀ`` with W in forward (K, N) layout.

    gm: (Lg, M, N) grad limb planes, wm: (Lw, K, N) weight limb planes
    (logical 2-D mantissas also accepted).  Returns FP32 (M, K).  The kernel
    contracts the shared N axis in place — no transpose is materialized.
    """
    if interpret is None:
        interpret = not on_tpu()
    out_exp = (g_exp + w_exp).astype(jnp.int32)

    def local(gm, wm, out_exp):
        _, M, N = gm.shape
        _, K, _ = wm.shape
        # out is (M, K): M is the sublane-flexible dim, K and N ride lanes.
        bm, bn, bk = _pick_blocks(M, K, N, gm.shape[0], wm.shape[0])
        gm, wm = _pad_last2(gm, bm, bk), _pad_last2(wm, bn, bk)
        out = bfp_matmul_nt(gm, wm, out_exp, bm=bm, bn=bn, bk=bk,
                            interpret=interpret)
        return out[:M, :K]

    return sharding.over_batch(
        local, (_as_planes(gm, g_bits, 2), _as_planes(wm, w_bits, 2), out_exp),
        (1, None, None), 0)


def dfx_matmul_tiled_tn(
    xm: jax.Array, x_exp: jax.Array, x_bits: int,
    gm: jax.Array, g_exp: jax.Array, g_bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """Backward dW product: ``q(X)ᵀ·q(G)`` with X in forward (M, K) layout.

    xm: (Lx, M, K) activation limb planes, gm: (Lg, M, N) grad limb planes
    (logical 2-D mantissas also accepted).  Returns FP32 (K, N).  The kernel
    contracts the shared M axis in place; across chips each contracts its
    own rows and the f32 partials are summed (``psum``).
    """
    if interpret is None:
        interpret = not on_tpu()
    out_exp = (x_exp + g_exp).astype(jnp.int32)

    def local(xm, gm, out_exp):
        _, M, K = xm.shape
        _, _, N = gm.shape
        # out is (K, N): K and N ride the lanes of the output tile; the
        # contracted M axis is the operands' sublane dim
        bm, bn, bk = _pick_blocks(K, N, M, xm.shape[0], gm.shape[0],
                                  contract_rows=True)
        xm, gm = _pad_last2(xm, bk, bm), _pad_last2(gm, bk, bn)
        out = bfp_matmul_tn(xm, gm, out_exp, bm=bm, bn=bn, bk=bk,
                            interpret=interpret)
        return out[:K, :N]

    return sharding.over_batch(
        local, (_as_planes(xm, x_bits, 2), _as_planes(gm, g_bits, 2), out_exp),
        (1, 1, None), "sum")


def dfx_matmul_tiled_batched(
    xm: jax.Array, x_exp: jax.Array, x_bits: int,
    wm: jax.Array, w_exp: jax.Array, w_bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """Batched NN: ``q(X[e])·q(W[e])`` for all experts AND limb pairs in one
    launch.

    xm: (Lx, E, M, K) limb planes (or logical (E, M, K)), wm: (Lw, E, K, N);
    x_exp/w_exp are (E,)-broadcastable scale exponents (the (E, 1, 1)
    keep-dims layout of the per-expert quantizers is accepted).  Returns
    FP32 (E, M, N).
    """
    if interpret is None:
        interpret = not on_tpu()
    xm = _as_planes(xm, x_bits, 3)
    E = xm.shape[1]
    out_exp = (jnp.reshape(x_exp, (E,)) + jnp.reshape(w_exp, (E,))).astype(jnp.int32)

    def local(xm, wm, out_exp):
        _, _, M, K = xm.shape
        _, _, _, N = wm.shape
        bm, bn, bk = _pick_blocks(M, N, K, xm.shape[0], wm.shape[0])
        xm, wm = _pad_last2(xm, bm, bk), _pad_last2(wm, bk, bn)
        out = bfp_matmul_batched(xm, wm, out_exp, bm=bm, bn=bn, bk=bk,
                                 interpret=interpret)
        return out[:, :M, :N]

    return sharding.over_batch(
        local, (xm, _as_planes(wm, w_bits, 3), out_exp), (2, None, None), 1)


def dfx_matmul_tiled_batched_nt(
    gm: jax.Array, g_exp: jax.Array, g_bits: int,
    wm: jax.Array, w_exp: jax.Array, w_bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """Batched NT: ``dX[e] = q(G[e])·q(W[e])ᵀ``, W in forward layout.

    gm: (Lg, E, M, N) limb planes (or logical (E, M, N)), wm: (Lw, E, K, N).
    Returns FP32 (E, M, K).
    """
    if interpret is None:
        interpret = not on_tpu()
    gm = _as_planes(gm, g_bits, 3)
    E = gm.shape[1]
    out_exp = (jnp.reshape(g_exp, (E,)) + jnp.reshape(w_exp, (E,))).astype(jnp.int32)

    def local(gm, wm, out_exp):
        _, _, M, N = gm.shape
        _, _, K, _ = wm.shape
        bm, bn, bk = _pick_blocks(M, K, N, gm.shape[0], wm.shape[0])
        gm, wm = _pad_last2(gm, bm, bk), _pad_last2(wm, bn, bk)
        out = bfp_matmul_batched_nt(gm, wm, out_exp, bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
        return out[:, :M, :K]

    return sharding.over_batch(
        local, (gm, _as_planes(wm, w_bits, 3), out_exp), (2, None, None), 1)


def dfx_matmul_tiled_batched_tn(
    xm: jax.Array, x_exp: jax.Array, x_bits: int,
    gm: jax.Array, g_exp: jax.Array, g_bits: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    """Batched TN: ``dW[e] = q(X[e])ᵀ·q(G[e])``, X in forward layout.

    xm: (Lx, E, M, K) limb planes (or logical (E, M, K)), gm: (Lg, E, M, N).
    Returns FP32 (E, K, N).
    """
    if interpret is None:
        interpret = not on_tpu()
    xm = _as_planes(xm, x_bits, 3)
    E = xm.shape[1]
    out_exp = (jnp.reshape(x_exp, (E,)) + jnp.reshape(g_exp, (E,))).astype(jnp.int32)

    def local(xm, gm, out_exp):
        _, _, M, K = xm.shape
        _, _, _, N = gm.shape
        bm, bn, bk = _pick_blocks(K, N, M, xm.shape[0], gm.shape[0],
                                  contract_rows=True)
        xm, gm = _pad_last2(xm, bk, bm), _pad_last2(gm, bk, bn)
        out = bfp_matmul_batched_tn(xm, gm, out_exp, bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
        return out[:, :K, :N]

    return sharding.over_batch(
        local, (xm, _as_planes(gm, g_bits, 3), out_exp), (2, 2, None), "sum")


#: largest row tile of the grouped (sorted-rows) matmul: every group of rows
#: is padded to a multiple of it.
_GROUP_ROWS = 256


def group_row_tile(mean_rows: int) -> int:
    """Row tile every group is padded to: the mean group's rows in sublane
    multiples up to 128, else ``_GROUP_ROWS`` — small enough that padding
    stays a fraction of a mean group, and a block size ``_pick_blocks`` can
    take whole."""
    if mean_rows > _LANE:
        return _GROUP_ROWS
    return _round_up_multiple(max(mean_rows, 1), _SUBLANE)


def _grouped_exp(a_exp, b_exp, G: int) -> jax.Array:
    return (jnp.reshape(a_exp, (G,)) + jnp.reshape(b_exp, (G,))).astype(
        jnp.int32)


def dfx_matmul_grouped(
    xm: jax.Array, x_exp: jax.Array, x_bits: int,
    wm: jax.Array, w_exp: jax.Array, w_bits: int,
    offsets: jax.Array, tm: int, *, interpret: bool | None = None,
) -> jax.Array:
    """Grouped NN: ``q(X[r])·q(W[g])`` for every row ``r`` of group ``g``.

    xm: (Lx, M, K) limb planes (or logical (M, K)) with rows sorted by
    group, group ``g`` in ``[offsets[g], offsets[g+1])``, every group a
    multiple of the row tile ``tm``; wm: (Lw, G, K, N); x_exp/w_exp one
    exponent per group.  Returns FP32 (M, N), zero past ``offsets[G]``.
    Every chip runs the whole call: the rows are one chip's expert share.
    """
    if interpret is None:
        interpret = not on_tpu()
    wm = _as_planes(wm, w_bits, 3)
    out_exp = _grouped_exp(x_exp, w_exp, wm.shape[1])

    def local(xm, wm, out_exp, offsets):
        _, M, K = xm.shape
        N = wm.shape[-1]
        bm, bn, bk = _pick_blocks(tm, N, K, xm.shape[0], wm.shape[0])
        xm, wm = _pad_last2(xm, bm, bk), _pad_last2(wm, bk, bn)
        out = bfp_matmul_grouped(xm, wm, out_exp, offsets, bm=bm, bn=bn,
                                 bk=bk, interpret=interpret)
        return out[:, :N]

    return sharding.over_batch(
        local, (_as_planes(xm, x_bits, 2), wm, out_exp, offsets),
        (None, None, None, None), None)


def dfx_matmul_grouped_nt(
    gm: jax.Array, g_exp: jax.Array, g_bits: int,
    wm: jax.Array, w_exp: jax.Array, w_bits: int,
    offsets: jax.Array, tm: int, *, interpret: bool | None = None,
) -> jax.Array:
    """Grouped NT (dX): ``q(G[r])·q(W[g])ᵀ``, W (Lw, G, K, N) in forward
    layout; gm (Lg, M, N) sorted rows.  Returns FP32 (M, K)."""
    if interpret is None:
        interpret = not on_tpu()
    wm = _as_planes(wm, w_bits, 3)
    out_exp = _grouped_exp(g_exp, w_exp, wm.shape[1])

    def local(gm, wm, out_exp, offsets):
        _, M, N = gm.shape
        K = wm.shape[2]
        bm, bn, bk = _pick_blocks(tm, K, N, gm.shape[0], wm.shape[0])
        gm, wm = _pad_last2(gm, bm, bk), _pad_last2(wm, bn, bk)
        out = bfp_matmul_grouped_nt(gm, wm, out_exp, offsets, bm=bm, bn=bn,
                                    bk=bk, interpret=interpret)
        return out[:, :K]

    return sharding.over_batch(
        local, (_as_planes(gm, g_bits, 2), wm, out_exp, offsets),
        (None, None, None, None), None)


def dfx_matmul_grouped_tn(
    xm: jax.Array, x_exp: jax.Array, x_bits: int,
    gm: jax.Array, g_exp: jax.Array, g_bits: int,
    offsets: jax.Array, tm: int, *, interpret: bool | None = None,
) -> jax.Array:
    """Grouped TN (dW): ``q(X[rows of g])ᵀ·q(G[rows of g])`` for every
    group; xm (Lx, M, K), gm (Lg, M, N) sorted rows.  Returns FP32
    (G, K, N); a group of padding rows only gets zeros."""
    if interpret is None:
        interpret = not on_tpu()
    G = offsets.shape[0] - 1
    out_exp = _grouped_exp(x_exp, g_exp, G)

    def local(xm, gm, out_exp, offsets):
        _, M, K = xm.shape
        N = gm.shape[-1]
        bm, bn, bk = _pick_blocks(K, N, tm, xm.shape[0], gm.shape[0],
                                  contract_rows=True, accumulate=True)
        xm, gm = _pad_last2(xm, bk, bm), _pad_last2(gm, bk, bn)
        out = bfp_matmul_grouped_tn(xm, gm, out_exp, offsets, bm=bm, bn=bn,
                                    bk=bk, interpret=interpret)
        return out[:, :K, :N]

    return sharding.over_batch(
        local, (_as_planes(xm, x_bits, 2), _as_planes(gm, g_bits, 2),
                out_exp, offsets),
        (None, None, None, None), None)


def quantize_vmem_bytes(br: int, n: int, out_bytes: int,
                        stochastic: bool) -> int:
    """VMEM bytes one grid step of the quantize kernel keeps resident.

    Every block is double-buffered: the f32 ``(br, n)`` input, the f32
    noise ``u`` when rounding is stochastic, and the output — ``out_bytes``
    per element, i.e. ``L`` for the stacked int8 limb planes or the logical
    mantissa's itemsize.
    """
    return 2 * br * n * (4 + (4 if stochastic else 0) + out_bytes)


def _quant_rows(M: int, N: int, bits: int, stochastic: bool,
                limb_planes: bool) -> int:
    """Row block of the quantize kernel: at most ``_QUANT_ROWS`` rows,
    halved (in sublane multiples) until a grid step fits the VMEM budget —
    the same rule ``_pick_blocks`` applies to the matmul's sublane dim."""
    out_bytes = (n_limbs(bits) if limb_planes
                 else jnp.dtype(_out_dtype(bits)).itemsize)
    br = min(_QUANT_ROWS, _round_up_multiple(M, _SUBLANE))
    while br > _SUBLANE and quantize_vmem_bytes(
            br, N, out_bytes, stochastic) > _VMEM_BUDGET:
        br = _round_up_multiple(br // 2, _SUBLANE)
    return br


def _quant_vmem_limit(br: int, N: int, bits: int, stochastic: bool,
                      limb_planes: bool) -> int | None:
    """Scoped VMEM for a quantize step whose blocks and in-kernel f32
    temporaries (the rounded mantissa and one per limb plane, about
    ``L + 2`` tiles) pass Mosaic's 16 MiB default: half again their size.
    None below it, where the call keeps the default (a v5e compile of a
    (256, 2304) 3-limb step used 19.1 MiB against a 8.3 MiB block model)."""
    L = n_limbs(bits) if limb_planes else 1
    out_bytes = L if limb_planes else jnp.dtype(_out_dtype(bits)).itemsize
    need = (quantize_vmem_bytes(br, N, out_bytes, stochastic)
            + br * N * 4 * (L + 2))
    return None if need <= _SCOPED_VMEM_DEFAULT else need + need // 2


def quantize_pallas(x: jax.Array, exp: jax.Array, bits: int,
                    u: jax.Array | None = None,
                    interpret: bool | None = None,
                    limb_planes: bool = False) -> jax.Array:
    """2-D wrapper over the quantize kernel with row padding.

    ``limb_planes=True`` returns the (L, M, N) int8 limb-plane stack the
    matmul kernels consume (split fused into the quantize launch); the
    default returns the logical (M, N) int8/int16 mantissa.
    """
    if interpret is None:
        interpret = not on_tpu()

    def local(x, exp, *u):
        u = u[0] if u else None
        M, N = x.shape
        br = _quant_rows(M, N, bits, u is not None, limb_planes)
        pm = (-M) % br
        if pm:
            x = jnp.pad(x, ((0, pm), (0, 0)))
            if u is not None:
                u = jnp.pad(u, ((0, pm), (0, 0)))
        out = dfx_quantize(x, exp, bits=bits, u=u, br=br,
                           interpret=interpret, limb_planes=limb_planes,
                           vmem_limit=_quant_vmem_limit(
                               br, N, bits, u is not None, limb_planes))
        return out[:, :M] if limb_planes else out[:M]

    args = (x, exp) + (() if u is None else (u,))
    return sharding.over_batch(local, args, (0, None, 0)[:len(args)],
                               1 if limb_planes else 0)


def quantize_pallas_batched(x: jax.Array, exp: jax.Array, bits: int,
                            u: jax.Array | None = None,
                            interpret: bool | None = None,
                            limb_planes: bool = False) -> jax.Array:
    """3-D (E, M, N) wrapper over the grouped-scale quantize kernel.

    ``exp`` holds one scale exponent per leading slice ((E,) or any
    (E,)-broadcastable keep-dims layout). Row padding is shared across
    experts (slices are uniform in shape); padded rows are zeros, which
    quantize to zero mantissas under every per-expert exponent, and the
    stochastic noise ``u`` is zero-padded identically (floor(0 + 0) = 0).
    ``limb_planes=True`` returns the plane-major (L, E, M, N) int8 stack.
    """
    if interpret is None:
        interpret = not on_tpu()

    def local(x, exp, *u):
        u = u[0] if u else None
        _, M, N = x.shape
        br = _quant_rows(M, N, bits, u is not None, limb_planes)
        pm = (-M) % br
        if pm:
            x = jnp.pad(x, ((0, 0), (0, pm), (0, 0)))
            if u is not None:
                u = jnp.pad(u, ((0, 0), (0, pm), (0, 0)))
        out = dfx_quantize_grouped(x, exp, bits=bits, u=u, br=br,
                                   interpret=interpret,
                                   limb_planes=limb_planes)
        return out[:, :, :M] if limb_planes else out[:, :M]

    args = (x, jnp.reshape(exp, (x.shape[0],))) + (() if u is None else (u,))
    return sharding.over_batch(local, args, (1, None, 1)[:len(args)],
                               2 if limb_planes else 1)


def _pad_rows(R: int, cap: int, *arrs):
    """Row padding shared by the norm wrappers.

    Picks ``br = min(cap, R rounded up to a sublane multiple)`` and zero-pads
    every array's rows to a ``br`` multiple.  Zero rows are exact: their
    statistics are computed but trimmed by the caller, and zero *gradient*
    mantissa rows contribute nothing to the parameter-gradient partials (so
    any fill value in padded mu/rstd rows is safe).  Returns ``(br, arrs)``.
    """
    br = min(cap, _round_up_multiple(R, _SUBLANE))
    pr = (-R) % br
    if pr:
        arrs = tuple(jnp.pad(a, ((0, pr), (0, 0))) for a in arrs)
    return br, arrs


def layernorm_pallas(xm: jax.Array, x_exp: jax.Array, gamma: jax.Array,
                     beta: jax.Array, eps: float = 1e-5,
                     interpret: bool | None = None,
                     integer_rsqrt: bool = False):
    """Fused LN forward with row padding. Returns ``(y, mu, rstd)``.

    ``mu``/``rstd`` (R, 1) are the value-domain statistics the kernel
    normalized with — the backward residuals.  ``integer_rsqrt`` swaps the
    in-kernel FP32 rsqrt for the iapprox form (kept_ops="integer").
    """
    if interpret is None:
        interpret = not on_tpu()

    def local(xm, x_exp, gamma, beta):
        R = xm.shape[0]
        br, (xm,) = _pad_rows(R, 8, xm)
        y, mu, rstd = int_layernorm_fwd(xm, x_exp, gamma, beta, br=br,
                                        eps=eps, interpret=interpret,
                                        integer_rsqrt=integer_rsqrt)
        return y[:R], mu[:R], rstd[:R]

    return sharding.over_batch(local, (xm, x_exp, gamma, beta),
                               (0, None, None, None), (0, 0, 0))


def layernorm_bwd_pallas(xm: jax.Array, x_exp: jax.Array, gm: jax.Array,
                         g_exp: jax.Array, gamma: jax.Array, mu: jax.Array,
                         rstd: jax.Array, interpret: bool | None = None):
    """Fused LN backward with row padding. Returns ``(dx, dgamma, dbeta)``.

    The kernel emits per-row-block dgamma/dbeta partials; the cross-block
    combine here is a small (R/br, 1, D) XLA tree-sum, then a ``psum``
    across chips.
    """
    if interpret is None:
        interpret = not on_tpu()

    def local(xm, gm, mu, rstd, x_exp, g_exp, gamma):
        R = xm.shape[0]
        br, (xm, gm, mu, rstd) = _pad_rows(R, 64, xm, gm, mu, rstd)
        dx, dgp, dbp = int_layernorm_bwd(xm, gm, x_exp, g_exp, gamma, mu,
                                         rstd, br=br, interpret=interpret)
        return dx[:R], jnp.sum(dgp, axis=(0, 1)), jnp.sum(dbp, axis=(0, 1))

    return sharding.over_batch(
        local, (xm, gm, mu, rstd, x_exp, g_exp, gamma),
        (0, 0, 0, 0, None, None, None), (0, "sum", "sum"))


def rmsnorm_pallas(xm: jax.Array, x_exp: jax.Array, gamma: jax.Array,
                   eps: float = 1e-6, interpret: bool | None = None,
                   integer_rsqrt: bool = False):
    """Fused RMS-norm forward with row padding. Returns ``(y, rstd)``.
    ``integer_rsqrt`` as in ``layernorm_pallas``."""
    if interpret is None:
        interpret = not on_tpu()

    def local(xm, x_exp, gamma):
        R = xm.shape[0]
        br, (xm,) = _pad_rows(R, 8, xm)
        y, rstd = int_rmsnorm_fwd(xm, x_exp, gamma, br=br, eps=eps,
                                  interpret=interpret,
                                  integer_rsqrt=integer_rsqrt)
        return y[:R], rstd[:R]

    return sharding.over_batch(local, (xm, x_exp, gamma), (0, None, None),
                               (0, 0))


def rmsnorm_bwd_pallas(xm: jax.Array, x_exp: jax.Array, gm: jax.Array,
                       g_exp: jax.Array, gamma: jax.Array, rstd: jax.Array,
                       interpret: bool | None = None):
    """Fused RMS-norm backward with row padding. Returns ``(dx, dgamma)``."""
    if interpret is None:
        interpret = not on_tpu()

    def local(xm, gm, rstd, x_exp, g_exp, gamma):
        R = xm.shape[0]
        br, (xm, gm, rstd) = _pad_rows(R, 64, xm, gm, rstd)
        dx, dgp = int_rmsnorm_bwd(xm, gm, x_exp, g_exp, gamma, rstd, br=br,
                                  interpret=interpret)
        return dx[:R], jnp.sum(dgp, axis=(0, 1))

    return sharding.over_batch(local, (xm, gm, rstd, x_exp, g_exp, gamma),
                               (0, 0, 0, None, None, None), (0, "sum"))


# =========================================================================
# Integer flash attention (kernels/int_attention.py)
# =========================================================================

def _attn_dims(Sq: int, Sk: int, hd: int):
    """Score tile / padded sizes of the rows layout.

    ``bq`` × ``bk`` is the score tile, the unit of the kernels' numerics
    (kernels/int_attention.py); ``bq`` shrinks for short query runs
    (decode: Sq=1 -> bq=8) but always divides ``sq_p``, so a tile never
    straddles two GQA groups.
    """
    bq = min(_LANE, _round_up_multiple(Sq, _SUBLANE))
    sq_p = _round_up_multiple(Sq, bq)
    bk = _LANE
    sk_p = _round_up_multiple(Sk, bk)
    hd_p = _round_up_multiple(hd, _LANE)
    return bq, sq_p, bk, sk_p, hd_p


#: modelled cost, in µs on a v5e, of one attention grid step and of one
#: score tile a step checks and skips: least-squares fits over the block
#: sweep in PERF.md (0.23-0.36 a step by kernel; 0.017 a skipped tile in
#: dkv, ~0 in the forward and dq).  A live tile costs the same in every
#: grid block, so it does not enter the pick.
_ATTN_STEP_US = 0.3
_ATTN_DEAD_US = 0.017

#: VMEM budget for one attention grid step (``attention_vmem_bytes``); the
#: kernel asks Mosaic for half again its modelled working set.
_ATTN_VMEM_BUDGET = 32 * 1024 * 1024


def _attn_counts(kernel: str, sq_p: int, sk_p: int, bq: int, bk: int,
                 blocks: tuple[int, int], causal: bool, window,
                 q_off: int = 0) -> tuple[int, int, int]:
    """``(grid steps, tiles checked, live tiles)`` of one query head of one
    batch row (one GQA group) in ``kernel`` at grid block ``blocks``, for
    query offset ``q_off``: the grid the kernels build and the tiles their
    walk visits (``int_attention._walk_keys`` / ``_walk_queries``)."""
    rows, keys = blocks
    nr, nt = rows // bq, keys // bk
    nq, nk = sq_p // rows, sk_p // keys
    if not causal and window is None:
        return nq * nk, nq * nk * nr * nt, nq * nk * nr * nt
    mask = dict(causal=causal, window=window, xp=np)
    if kernel == "dkv":
        lo, hi = _ia._q_band(q_off, np.arange(nk), bq=rows, bk=keys, nqb=nq,
                             **mask)
        tlo, thi = _ia._q_band(q_off, np.arange(sk_p // bk), bq=bq, bk=bk,
                               nqb=sq_p // bq, **mask)
        steps = nk * _ia._band_steps(nq, keys, rows, causal, window)
    else:
        lo, hi = _ia._k_band(q_off, np.arange(nq), bq=rows, bk=keys,
                             sq_p=sq_p, n_k=nk, **mask)
        tlo, thi = _ia._k_band(q_off, np.arange(sq_p // bq), bq=bq, bk=bk,
                               sq_p=sq_p, n_k=sk_p // bk, **mask)
        steps = nq * _ia._band_steps(nk, rows, keys, causal, window)
    checked = int(np.sum(np.maximum(hi - lo + 1, 0))) * nr * nt
    return steps, checked, int(np.sum(np.maximum(thi - tlo + 1, 0)))


def _attn_blocks(kernel: str, sq_p: int, sk_p: int, hd_p: int, limbs: int,
                 causal: bool, window, bq: int, bk: int) -> tuple[int, int]:
    """Grid block ``(rows, keys)`` of ``kernel`` (``"fwd"``, ``"dq"``,
    ``"dkv"``) at padded extents ``sq_p`` × ``sk_p``, score tile ``bq`` ×
    ``bk`` and ``limbs`` planes per operand (the most of any).

    Every block is a multiple of the tile along each axis and divides the
    padded extent, so no operand is padded further and a block of rows
    never straddles two GQA groups.  Among the blocks whose modelled VMEM
    (``attention_vmem_bytes``) fits the budget, the chooser takes the one
    of least modelled cost — grid steps × ``_ATTN_STEP_US`` + tiles checked
    and skipped × ``_ATTN_DEAD_US`` (at query offset 0) — then the
    smaller working set."""
    all_live = not causal and window is None

    def cost(b):
        steps, checked, live = _attn_counts(kernel, sq_p, sk_p, bq, bk, b,
                                            causal, window)
        return (steps * _ATTN_STEP_US + (checked - live) * _ATTN_DEAD_US,
                vmem(b))

    def vmem(b):
        return attention_vmem_bytes(kernel, *b, hd_p, limbs, (bq, bk),
                                    all_live)

    fitting = [b for b in itertools.product(
        range(bq, sq_p + 1, bq), range(bk, sk_p + 1, bk))
        if sq_p % b[0] == 0 and sk_p % b[1] == 0
        and vmem(b) <= _ATTN_VMEM_BUDGET]
    return min(fitting, key=cost) if fitting else (bq, bk)


def attention_grid_plan(Sq: int, Sk: int, hd: int, limbs: int, causal: bool,
                        window: int | None = None, q_off: int = 0) -> dict:
    """Each attention kernel's grid block, grid steps and live score tiles
    for one query head of one batch row: ``{kernel: {"blocks": (rows,
    keys), "steps": n, "tiles": n}}`` over ``"fwd"``, ``"dq"``, ``"dkv"``
    — the blocks the wrappers below pick, and the counts their grids
    run at query offset ``q_off``."""
    bq, sq_p, bk, sk_p, hd_p = _attn_dims(Sq, Sk, hd)
    plan = {}
    for kernel in ("fwd", "dq", "dkv"):
        blocks = _attn_blocks(kernel, sq_p, sk_p, hd_p, limbs, causal,
                              window, bq, bk)
        steps, _, live = _attn_counts(kernel, sq_p, sk_p, bq, bk, blocks,
                                      causal, window, q_off)
        plan[kernel] = {"blocks": blocks, "steps": steps, "tiles": live}
    return plan


def _q_rows(qm: jax.Array, sq_p: int, hd_p: int) -> jax.Array:
    """(L, B, Sq, KV, G, hd) planes -> rows layout (L, B·KV, G·Sq_p, hd_p)."""
    L, B, Sq, KV, G, hd = qm.shape
    qr = _pad_last2(qm.transpose(0, 1, 3, 4, 2, 5), sq_p, hd_p)
    return qr.reshape(L, B * KV, G * sq_p, hd_p)


def _kv_rows(km: jax.Array, sk_p: int, hd_p: int) -> jax.Array:
    """(L, B, Sk, KV, hd) planes -> rows layout (L, B·KV, Sk_p, hd_p)."""
    L, B, Sk, KV, hd = km.shape
    kr = _pad_last2(km.transpose(0, 1, 3, 2, 4), sk_p, hd_p)
    return kr.reshape(L, B * KV, sk_p, hd_p)


def _rows_q_out(o: jax.Array, B: int, KV: int, G: int, sq_p: int,
                Sq: int, hd: int) -> jax.Array:
    """Rows-layout (BH, R, hd_p) output -> model layout (B, Sq, KV, G, hd)."""
    return o.reshape(B, KV, G, sq_p, -1)[:, :, :, :Sq, :hd].transpose(
        0, 3, 1, 2, 4)


def attention_fwd(qm: jax.Array, q_exp: jax.Array,
                  km: jax.Array, k_exp: jax.Array,
                  vm: jax.Array, v_exp: jax.Array,
                  q_off: jax.Array, p_bits: int, *,
                  causal: bool, window: int | None = None,
                  interpret: bool | None = None,
                  integer_exp: bool = False):
    """Fused integer attention forward — ONE ``pallas_call``.

    qm: (Lq, B, Sq, KV, G, hd) int8 limb planes (the quantize kernel's
    stacked output reshaped to the model layout); km/vm: (L, B, Sk, KV, hd);
    ``q_off`` (B,) int32 query offsets (0 for training, the cache index for
    decode / chunked prefill).  Returns ``(o, lse)``: o (B, Sq, KV, G, hd)
    f32, lse (B, KV, G, Sq) f32 — the backward residual.
    """
    if interpret is None:
        interpret = not on_tpu()
    exps = jnp.stack([jnp.reshape(q_exp, ()), jnp.reshape(k_exp, ()),
                      jnp.reshape(v_exp, ())]).astype(jnp.int32)

    def local(qm, km, vm, q_off, exps):
        _, B, Sq, KV, G, hd = qm.shape
        Sk = km.shape[2]
        bq, sq_p, bk, sk_p, hd_p = _attn_dims(Sq, Sk, hd)
        limbs = max(qm.shape[0], km.shape[0], vm.shape[0])
        o, lse = int_attn_fwd(
            _q_rows(qm, sq_p, hd_p), _kv_rows(km, sk_p, hd_p),
            _kv_rows(vm, sk_p, hd_p), q_off, exps,
            p_bits=p_bits, sq_p=sq_p, kv_heads=KV, kv_len=Sk, causal=causal,
            window=window, sc=1.0 / float(hd) ** 0.5, bq=bq, bk=bk,
            blocks=_attn_blocks("fwd", sq_p, sk_p, hd_p, limbs, causal,
                                window, bq, bk),
            interpret=interpret, integer_exp=integer_exp)
        return (_rows_q_out(o, B, KV, G, sq_p, Sq, hd),
                lse.reshape(B, KV, G, sq_p)[..., :Sq])

    return sharding.over_batch(local, (qm, km, vm, q_off, exps),
                               (1, 1, 1, 0, None), (0, 0))


def attention_bwd(qm: jax.Array, q_exp: jax.Array,
                  km: jax.Array, k_exp: jax.Array,
                  vm: jax.Array, v_exp: jax.Array,
                  gm: jax.Array, g_exp: jax.Array,
                  lse: jax.Array, delta: jax.Array, ds_exp: jax.Array,
                  q_off: jax.Array, p_bits: int, ds_bits: int, *,
                  causal: bool, window: int | None = None,
                  interpret: bool | None = None,
                  integer_exp: bool = False):
    """Fused integer attention backward — TWO ``pallas_call``s (dq; dk+dv).

    ``gm`` is the quantized upstream-grad limb stack in q layout; ``lse``
    (B, KV, G, Sq) and ``delta`` (B, Sq, KV, G) the forward-saved rows;
    ``ds_exp`` the bound-derived dS scale exponent (traced int32) of a call
    that is neither causal nor windowed, and None for one that is: that
    call scales dS per tile (kernels/int_attention.py).  Returns
    ``(dq, dk, dv)`` in model layout.  Padded lse rows are filled with
    +1e30 so the recomputed ``p`` vanishes there exactly.
    """
    if (ds_exp is None) != (causal or window is not None):
        raise ValueError("a causal or windowed call scales dS per tile and "
                         "takes no ds_exp; any other call needs one")
    if interpret is None:
        interpret = not on_tpu()
    exps = jnp.stack([jnp.reshape(e, ()) for e in (
        q_exp, k_exp, v_exp, g_exp, ds_exp) if e is not None]
    ).astype(jnp.int32)

    def local(qm, km, vm, gm, lse, delta, q_off, exps):
        _, B, Sq, KV, G, hd = qm.shape
        Sk = km.shape[2]
        bq, sq_p, bk, sk_p, hd_p = _attn_dims(Sq, Sk, hd)
        qr = _q_rows(qm, sq_p, hd_p)
        kr = _kv_rows(km, sk_p, hd_p)
        vr = _kv_rows(vm, sk_p, hd_p)
        gr = _q_rows(gm, sq_p, hd_p)
        lse_r = jnp.pad(lse, [(0, 0)] * 3 + [(0, sq_p - Sq)],
                        constant_values=1e30).reshape(B * KV, G * sq_p, 1)
        d_r = jnp.pad(delta.transpose(0, 2, 3, 1),
                      [(0, 0)] * 3 + [(0, sq_p - Sq)]
                      ).reshape(B * KV, G * sq_p, 1)
        sc = 1.0 / float(hd) ** 0.5
        common = dict(sq_p=sq_p, kv_heads=KV, kv_len=Sk, causal=causal,
                      window=window, sc=sc, bq=bq, bk=bk,
                      interpret=interpret, integer_exp=integer_exp)
        limbs = max(qm.shape[0], km.shape[0], vm.shape[0], gm.shape[0])

        def blocks(kernel):
            return _attn_blocks(kernel, sq_p, sk_p, hd_p, limbs, causal,
                                window, bq, bk)
        dq = int_attn_bwd_dq(qr, kr, vr, gr, lse_r, d_r, q_off, exps,
                             ds_bits=ds_bits, blocks=blocks("dq"), **common)
        dk, dv = int_attn_bwd_dkv(qr, kr, vr, gr, lse_r, d_r, q_off, exps,
                                  p_bits=p_bits, ds_bits=ds_bits,
                                  blocks=blocks("dkv"), **common)
        dq = _rows_q_out(dq, B, KV, G, sq_p, Sq, hd)
        dk = dk.reshape(B, KV, sk_p, hd_p)[:, :, :Sk, :hd].transpose(
            0, 2, 1, 3)
        dv = dv.reshape(B, KV, sk_p, hd_p)[:, :, :Sk, :hd].transpose(
            0, 2, 1, 3)
        return dq, dk, dv

    return sharding.over_batch(
        local, (qm, km, vm, gm, lse, delta, q_off, exps),
        (1, 1, 1, 1, 0, 0, 0, None), (0, 0, 0))
