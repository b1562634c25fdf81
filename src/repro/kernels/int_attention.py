"""Pallas TPU kernels: fused integer flash-attention, forward and backward.

Attention is the quadratic cost center the paper's recipe leaves untouched —
this module closes it with the same kept-ops contract as the linear / norm
kernels (DESIGN.md §6): the two big contractions (QKᵀ and PV, and all four
backward products) run on **DFX-quantized int8 limb planes** with int32 MXU
accumulation, while the softmax itself — exp / running max / the 1/l
normalizer — stays in f32 *inside the kernel* (a kept op, like the norm
rsqrt).  Nothing about the quantized value semantics depends on the backend:
the sim path in ``core.int_ops`` and the f64 oracles in ``kernels/ref.py``
compute the same quantize → integer-dot → f32-softmax pipeline.

Layout ("rows" form, produced by kernels/ops.py wrappers):

* Q / dO limb planes  ``(L, BH, R, hd_p)``   with ``BH = B·KV`` (batch ×
  kv-head) and ``R = G·Sq_p`` (GQA group-major rows: group ``g`` owns rows
  ``[g·Sq_p, (g+1)·Sq_p)``) — so one grid axis covers batch and head, and
  every q block of ``bq`` rows lies inside a single group (``bq | Sq_p``),
* K / V limb planes   ``(L, BH, Sk_p, hd_p)``,
* O                   ``(BH, R, hd_p)`` f32,
* lse / delta         ``(BH, R, 1)``   f32.

Online softmax (forward): per 128-wide K block the kernel keeps the running
row max ``m``, normalizer ``l`` and f32 accumulator in VMEM scratch across
the innermost ("arbitrary") grid axis:

    s      = sc · Σ_pairs (q_limb · k_limbᵀ)    int32 MXU, f32 combine
    m_new  = max(m, rowmax(s));   p = where(ok, exp(s - m_new), 0)
    l      = l·α + rowsum(p),     α = exp(m - m_new)
    acc    = acc·α + Σ_pairs (quant(p) · v_limb) · 2^{-(p_bits-1)}

``p ≤ 1`` by construction (``m_new`` dominates the in-block row max), so P
quantizes with the *static* exponent ``-(p_bits-1)`` — no extra max pass.
``l`` accumulates the **unquantized** ``p`` (the normalizer is a kept op);
only the PV contraction sees the quantized mantissa.  The ``where``-guard on
``exp`` is essential: a fully masked block has ``s == m_new == -1e30`` and
a bare ``exp(0) = 1`` would poison ``l``.

Backward (flash-attention-2 style, two kernels): ``dq`` iterates K blocks
innermost accumulating one q-row block; ``dk/dv`` iterates q blocks
innermost accumulating one k-row block.  Both recompute ``p`` from the saved
row ``lse`` (no S×S residual), quantize ``p`` and ``dS = p·(dp − δ)`` to
limb planes **in-register** (the digit split of kernels/dfx_quant.py), and
run every contraction on the integer MXU path.  ``dS``'s scale exponent is
a *bound-derived* static-per-trace int32 operand (see core.int_ops) — no
max pass over dS either.

Masking: ``qpos = q_offset[b] + i_local`` (per-row offsets for KV-cache
decode / chunked prefill / continuous-batching slots), ``kpos`` the global
K column; validity is ``kpos < kv_len`` (ragged tail) ∧ causal
(``kpos ≤ qpos``) ∧ sliding window (``kpos > qpos − window``).

Accumulator budget (quantlint QL006): every integer dot is digit×digit —
|limb| ≤ 64 — so the int32 partials are bounded by ``64²·K`` with
``K ≤ max(hd_p, bq, bk)``: ≤ 2^19 at the default 128 blocks, five orders of
magnitude inside int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single source of the limb radix + digit split: quantized P / dS planes cut
# in-kernel MUST match the shifts the quantize kernel uses for Q/K/V.
from repro.core import iapprox
from repro.kernels.dfx_quant import (  # noqa: E402
    LIMB_BITS, _round_clip, _split_planes, n_limbs)

_BIG_NEG = -1e30

#: the per-row query offsets and the scale exponents are scalars read by
#: index: Mosaic loads those only from SMEM.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _limb_dot(a_ref, b_ref, la: int, lb: int, dims, exp_f32, shift: int):
    """Σ over limb pairs of ``dot(a[ja], b[jb])`` with the ordered f32
    combine of kernels/bfp_matmul.py.

    ``a_ref``/``b_ref`` are ``(L, 1, rows, cols)`` int8 plane blocks; the
    scale is applied as ``exp2(exp) * 2^(7(ja+jb)+shift)`` — ``exp2`` once
    on the raw (traced) exponent, then a power-of-two *literal* multiply —
    never folded into the exp2 argument (not correctly rounded at every
    integer arg; same contract as the matmul combine).
    """
    lc, rc = dims
    scale0 = jnp.exp2(exp_f32)
    out = None
    for ja in range(la):
        for jb in range(lb):
            part = jax.lax.dot_general(
                a_ref[ja, 0], b_ref[jb, 0],
                (((lc,), (rc,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            part = (part.astype(jnp.float32) * scale0) * (
                2.0 ** (LIMB_BITS * (ja + jb) + shift))
            out = part if out is None else out + part
    return out


def _plane_dot(planes, b_ref, lb: int, dims, exp_f32, shift: int):
    """Like ``_limb_dot`` but the lhs limbs are in-register f32 digit planes
    (the just-quantized P or dS), cast to int8 at the MXU boundary — every
    digit lies in [-64, 64]."""
    lc, rc = dims
    scale0 = jnp.exp2(exp_f32)
    out = None
    for ja, plane in enumerate(planes):
        for jb in range(lb):
            part = jax.lax.dot_general(
                plane.astype(jnp.int8), b_ref[jb, 0],
                (((lc,), (rc,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            part = (part.astype(jnp.float32) * scale0) * (
                2.0 ** (LIMB_BITS * (ja + jb) + shift))
            out = part if out is None else out + part
    return out


def _valid_mask(off, qi, kj, *, bq: int, bk: int, sq_p: int, kv_len: int,
                causal: bool, window):
    """(bq, bk) bool validity of score block (qi, kj).

    ``off`` is the scalar per-batch-row query offset; the row index inside
    the group is recovered from the group-major R axis — ``bq | sq_p`` so a
    q block never straddles two GQA groups and the group id is the scalar
    ``(qi·bq) // sq_p``.
    """
    g_blk = (qi * bq) // sq_p
    i_local = (qi * bq - g_blk * sq_p
               + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    qpos = off + i_local
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kpos < kv_len
    if causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)
    return ok


def _p_exp(x, integer_exp: bool):
    """In-kernel softmax exp: FP32 (the paper's kept op) or the iapprox
    fixed-point form under ``kept_ops="integer"``.  Static flag — the swap
    is in-kernel, the dispatch count is unchanged either way.  i_exp clamps
    at exp(-30) ~ 9e-14, which rounds to a zero P mantissa at every
    supported p_bits, so the tail behaves like the exact exp's underflow."""
    if integer_exp:
        return iapprox.i_exp(x)
    return jnp.exp(x)


# =========================================================================
# Forward
# =========================================================================

def _int_attn_fwd_kernel(q_ref, k_ref, v_ref, off_ref, exp_ref,
                         o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                         n_k: int, lq: int, lk: int, lv: int, p_bits: int,
                         sq_p: int, kv_heads: int, kv_len: int, causal: bool,
                         window, sc: float, bq: int, bk: int,
                         integer_exp: bool):
    h = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _BIG_NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qe = exp_ref[0].astype(jnp.float32)
    ke = exp_ref[1].astype(jnp.float32)
    ve = exp_ref[2].astype(jnp.float32)
    off = off_ref[h // kv_heads]

    ok = _valid_mask(off, qi, kj, bq=bq, bk=bk, sq_p=sq_p, kv_len=kv_len,
                     causal=causal, window=window)
    s = _limb_dot(q_ref, k_ref, lq, lk, (1, 1), qe + ke, 0) * sc
    s = jnp.where(ok, s, _BIG_NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # the where-guard is load-bearing: a fully masked block has
    # s == m_new == _BIG_NEG and exp(0) = 1 would corrupt l
    p = jnp.where(ok, _p_exp(s - m_new, integer_exp), 0.0)
    alpha = _p_exp(m_prev - m_new, integer_exp)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new

    # P quantizes at the static exponent -(p_bits-1): p <= 1 by construction
    pm = _round_clip(jnp.round(p * (2.0 ** (p_bits - 1))), p_bits)
    pv = _plane_dot(_split_planes(pm, n_limbs(p_bits)), v_ref, lv,
                    (1, 0), ve, -(p_bits - 1))
    acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(kj == n_k - 1)
    def _epilogue():
        l = l_scr[...]
        if integer_exp:
            # fixed-point reciprocal normalizer (kept_ops="integer")
            o_ref[0] = acc_scr[...] * iapprox.i_recip(jnp.maximum(l, 1e-20))
        else:
            o_ref[0] = acc_scr[...] / jnp.maximum(l, 1e-20)
        lse_ref[0] = m_scr[...] + jnp.log(jnp.maximum(l, 1e-37))


@functools.partial(jax.jit, static_argnames=(
    "p_bits", "sq_p", "kv_heads", "kv_len", "causal", "window", "sc",
    "bq", "bk", "interpret", "integer_exp"))
def int_attn_fwd(
    qm: jax.Array,          # (Lq, BH, R, hd_p) int8 limb planes
    km: jax.Array,          # (Lk, BH, Sk_p, hd_p) int8 limb planes
    vm: jax.Array,          # (Lv, BH, Sk_p, hd_p) int8 limb planes
    q_off: jax.Array,       # (B,) int32 per-batch-row query offsets
    exps: jax.Array,        # (3,) int32 [q_exp, k_exp, v_exp]
    *,
    p_bits: int,
    sq_p: int,
    kv_heads: int,
    kv_len: int,
    causal: bool,
    window: int | None,
    sc: float,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
    integer_exp: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused forward: ``(o, lse)`` — (BH, R, hd_p) and (BH, R, 1) f32.

    ``integer_exp=True`` swaps the in-kernel online softmax's FP32 exp for
    the iapprox fixed-point form (kept_ops="integer"); the running-max /
    normalizer recurrence is unchanged."""
    Lq, BH, R, hd_p = qm.shape
    Lk, BH2, Skp, hd2 = km.shape
    Lv = vm.shape[0]
    assert BH == BH2 and hd_p == hd2 and vm.shape[1:] == km.shape[1:], (
        qm.shape, km.shape, vm.shape)
    assert R % bq == 0 and Skp % bk == 0 and sq_p % bq == 0, (
        R, Skp, sq_p, bq, bk)
    n_k = Skp // bk
    return pl.pallas_call(
        functools.partial(
            _int_attn_fwd_kernel, n_k=n_k, lq=Lq, lk=Lk, lv=Lv,
            p_bits=p_bits, sq_p=sq_p, kv_heads=kv_heads, kv_len=kv_len,
            causal=causal, window=window, sc=sc, bq=bq, bk=bk,
            integer_exp=integer_exp),
        grid=(BH, R // bq, n_k),
        in_specs=[
            pl.BlockSpec((Lq, 1, bq, hd_p), lambda h, i, j: (0, h, i, 0)),
            pl.BlockSpec((Lk, 1, bk, hd_p), lambda h, i, j: (0, h, j, 0)),
            pl.BlockSpec((Lv, 1, bk, hd_p), lambda h, i, j: (0, h, j, 0)),
            _SMEM,                               # (B,) query offsets
            _SMEM,                               # (3,) exps
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd_p), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, R, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((BH, R, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running row max
            pltpu.VMEM((bq, 1), jnp.float32),      # running normalizer
            pltpu.VMEM((bq, hd_p), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qm, km, vm, q_off.astype(jnp.int32), exps.astype(jnp.int32))


# =========================================================================
# Backward — dQ (K blocks innermost, one q-row block accumulated)
# =========================================================================

def _int_attn_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                            off_ref, exp_ref, dq_ref, dq_scr, *,
                            n_k: int, lq: int, lk: int, lv: int, lg: int,
                            ds_bits: int, sq_p: int, kv_heads: int,
                            kv_len: int, causal: bool, window, sc: float,
                            bq: int, bk: int, integer_exp: bool):
    h = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qe = exp_ref[0].astype(jnp.float32)
    ke = exp_ref[1].astype(jnp.float32)
    ve = exp_ref[2].astype(jnp.float32)
    ge = exp_ref[3].astype(jnp.float32)
    dse = exp_ref[4].astype(jnp.float32)
    off = off_ref[h // kv_heads]

    ok = _valid_mask(off, qi, kj, bq=bq, bk=bk, sq_p=sq_p, kv_len=kv_len,
                     causal=causal, window=window)
    s = _limb_dot(q_ref, k_ref, lq, lk, (1, 1), qe + ke, 0) * sc
    s = jnp.where(ok, s, _BIG_NEG)
    # padded q rows carry lse = +1e30, so p vanishes there exactly
    p = jnp.where(ok, _p_exp(s - lse_ref[0], integer_exp), 0.0)

    dp = _limb_dot(g_ref, v_ref, lg, lv, (1, 1), ge + ve, 0)
    ds = p * (dp - d_ref[0])
    dsm = _round_clip(jnp.round(ds * jnp.exp2(-dse)), ds_bits)
    dq_scr[...] += _plane_dot(_split_planes(dsm, n_limbs(ds_bits)), k_ref,
                              lk, (1, 0), dse + ke, 0)

    @pl.when(kj == n_k - 1)
    def _epilogue():
        dq_ref[0] = dq_scr[...] * sc


@functools.partial(jax.jit, static_argnames=(
    "ds_bits", "sq_p", "kv_heads", "kv_len", "causal", "window", "sc",
    "bq", "bk", "interpret", "integer_exp"))
def int_attn_bwd_dq(
    qm: jax.Array,          # (Lq, BH, R, hd_p) int8 limb planes
    km: jax.Array,          # (Lk, BH, Sk_p, hd_p)
    vm: jax.Array,          # (Lv, BH, Sk_p, hd_p)
    gm: jax.Array,          # (Lg, BH, R, hd_p) quantized dO planes
    lse: jax.Array,         # (BH, R, 1) f32 (+1e30 on padded rows)
    delta: jax.Array,       # (BH, R, 1) f32 rowsum(dO * O)
    q_off: jax.Array,       # (B,) int32
    exps: jax.Array,        # (5,) int32 [q, k, v, g, dS] exponents
    *,
    ds_bits: int,
    sq_p: int,
    kv_heads: int,
    kv_len: int,
    causal: bool,
    window: int | None,
    sc: float,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
    integer_exp: bool = False,
) -> jax.Array:
    """Fused dQ: (BH, R, hd_p) f32.  ``integer_exp`` must match the
    forward's flag — the FA2 recompute ``p = exp(s - lse)`` has to rebuild
    the same P the forward contracted."""
    Lq, BH, R, hd_p = qm.shape
    Lk, _, Skp, _ = km.shape
    Lv, Lg = vm.shape[0], gm.shape[0]
    assert gm.shape[1:] == qm.shape[1:] and lse.shape == (BH, R, 1), (
        qm.shape, gm.shape, lse.shape)
    n_k = Skp // bk
    return pl.pallas_call(
        functools.partial(
            _int_attn_bwd_dq_kernel, n_k=n_k, lq=Lq, lk=Lk, lv=Lv, lg=Lg,
            ds_bits=ds_bits, sq_p=sq_p, kv_heads=kv_heads, kv_len=kv_len,
            causal=causal, window=window, sc=sc, bq=bq, bk=bk,
            integer_exp=integer_exp),
        grid=(BH, R // bq, n_k),
        in_specs=[
            pl.BlockSpec((Lq, 1, bq, hd_p), lambda h, i, j: (0, h, i, 0)),
            pl.BlockSpec((Lk, 1, bk, hd_p), lambda h, i, j: (0, h, j, 0)),
            pl.BlockSpec((Lv, 1, bk, hd_p), lambda h, i, j: (0, h, j, 0)),
            pl.BlockSpec((Lg, 1, bq, hd_p), lambda h, i, j: (0, h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
            _SMEM,                               # (B,) query offsets
            _SMEM,                               # (5,) exps
        ],
        out_specs=pl.BlockSpec((1, bq, hd_p), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, R, hd_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qm, km, vm, gm, lse, delta,
      q_off.astype(jnp.int32), exps.astype(jnp.int32))


# =========================================================================
# Backward — dK / dV (q blocks innermost, one k-row block accumulated)
# =========================================================================

def _int_attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                             off_ref, exp_ref, dk_ref, dv_ref,
                             dk_scr, dv_scr, *,
                             n_q: int, lq: int, lk: int, lv: int, lg: int,
                             p_bits: int, ds_bits: int, sq_p: int,
                             kv_heads: int, kv_len: int, causal: bool,
                             window, sc: float, bq: int, bk: int,
                             integer_exp: bool):
    h = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qe = exp_ref[0].astype(jnp.float32)
    ke = exp_ref[1].astype(jnp.float32)
    ve = exp_ref[2].astype(jnp.float32)
    ge = exp_ref[3].astype(jnp.float32)
    dse = exp_ref[4].astype(jnp.float32)
    off = off_ref[h // kv_heads]

    ok = _valid_mask(off, qi, kj, bq=bq, bk=bk, sq_p=sq_p, kv_len=kv_len,
                     causal=causal, window=window)
    s = _limb_dot(q_ref, k_ref, lq, lk, (1, 1), qe + ke, 0) * sc
    s = jnp.where(ok, s, _BIG_NEG)
    p = jnp.where(ok, _p_exp(s - lse_ref[0], integer_exp), 0.0)

    # dV: quantized-Pᵀ · dO — the same static-exponent P mantissa the
    # forward contracted against V (straight-through at the quantizer)
    pm = _round_clip(jnp.round(p * (2.0 ** (p_bits - 1))), p_bits)
    dv_scr[...] += _plane_dot(_split_planes(pm, n_limbs(p_bits)), g_ref, lg,
                              (0, 0), ge, -(p_bits - 1))

    dp = _limb_dot(g_ref, v_ref, lg, lv, (1, 1), ge + ve, 0)
    ds = p * (dp - d_ref[0])
    dsm = _round_clip(jnp.round(ds * jnp.exp2(-dse)), ds_bits)
    dk_scr[...] += _plane_dot(_split_planes(dsm, n_limbs(ds_bits)), q_ref,
                              lq, (0, 0), dse + qe, 0)

    @pl.when(qi == n_q - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[...] * sc
        dv_ref[0] = dv_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "p_bits", "ds_bits", "sq_p", "kv_heads", "kv_len", "causal", "window",
    "sc", "bq", "bk", "interpret", "integer_exp"))
def int_attn_bwd_dkv(
    qm: jax.Array,          # (Lq, BH, R, hd_p) int8 limb planes
    km: jax.Array,          # (Lk, BH, Sk_p, hd_p)
    vm: jax.Array,          # (Lv, BH, Sk_p, hd_p)
    gm: jax.Array,          # (Lg, BH, R, hd_p) quantized dO planes
    lse: jax.Array,         # (BH, R, 1) f32 (+1e30 on padded rows)
    delta: jax.Array,       # (BH, R, 1) f32 rowsum(dO * O)
    q_off: jax.Array,       # (B,) int32
    exps: jax.Array,        # (5,) int32 [q, k, v, g, dS] exponents
    *,
    p_bits: int,
    ds_bits: int,
    sq_p: int,
    kv_heads: int,
    kv_len: int,
    causal: bool,
    window: int | None,
    sc: float,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
    integer_exp: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused dK, dV: each (BH, Sk_p, hd_p) f32.  ``integer_exp`` as in
    ``int_attn_bwd_dq``."""
    Lq, BH, R, hd_p = qm.shape
    Lk, _, Skp, _ = km.shape
    Lv, Lg = vm.shape[0], gm.shape[0]
    assert gm.shape[1:] == qm.shape[1:] and lse.shape == (BH, R, 1), (
        qm.shape, gm.shape, lse.shape)
    n_q = R // bq
    return pl.pallas_call(
        functools.partial(
            _int_attn_bwd_dkv_kernel, n_q=n_q, lq=Lq, lk=Lk, lv=Lv, lg=Lg,
            p_bits=p_bits, ds_bits=ds_bits, sq_p=sq_p, kv_heads=kv_heads,
            kv_len=kv_len, causal=causal, window=window, sc=sc,
            bq=bq, bk=bk, integer_exp=integer_exp),
        grid=(BH, Skp // bk, n_q),
        in_specs=[
            pl.BlockSpec((Lq, 1, bq, hd_p), lambda h, j, i: (0, h, i, 0)),
            pl.BlockSpec((Lk, 1, bk, hd_p), lambda h, j, i: (0, h, j, 0)),
            pl.BlockSpec((Lv, 1, bk, hd_p), lambda h, j, i: (0, h, j, 0)),
            pl.BlockSpec((Lg, 1, bq, hd_p), lambda h, j, i: (0, h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            _SMEM,                               # (B,) query offsets
            _SMEM,                               # (5,) exps
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd_p), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bk, hd_p), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Skp, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((BH, Skp, hd_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd_p), jnp.float32),
            pltpu.VMEM((bk, hd_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qm, km, vm, gm, lse, delta,
      q_off.astype(jnp.int32), exps.astype(jnp.int32))
