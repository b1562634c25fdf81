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
  every block of query rows lies inside a single group (it divides
  ``Sq_p``),
* K / V limb planes   ``(L, BH, Sk_p, hd_p)``,
* O                   ``(BH, R, hd_p)`` f32,
* lse / delta         ``(BH, R, 1)``   f32.

Score tiles and grid blocks.  The numerics are defined on ``(bq, bk)``
*score tiles* (128 × 128, or ``bq`` × 128 for short query runs): the
online-softmax update runs once per key tile, a causal or windowed call
takes each tile's own dS exponent, and every product that contracts over
keys (PV, dQ) or over queries (dV, dK) is summed in f32 one tile at a
time.  A grid step covers a *grid block* of ``nr × nt`` such tiles
(``blocks=(rows, keys)``, multiples of the tile chosen by
``ops._attn_blocks``) and walks them in the one order that reproduces the
one-tile-a-step grid bit for bit: key tiles ascending for each row tile
(forward, dq), query tiles ascending — group by group — for each key tile
(dkv).  Different row tiles (forward, dq) or key tiles (dkv) are
independent, so a call whose tiles are all live (neither causal nor
windowed) computes them together, as one wide dot along the dimension that
is not contracted: every output element is still the same exact int32 dot
and the same f32 arithmetic.  Fewer, larger grid steps pay the fixed cost
of a Pallas grid step less often; nothing else changes.

Online softmax (forward), per key tile, with the running row max ``m``,
normalizer ``l`` and f32 accumulator in VMEM scratch across the innermost
("arbitrary") grid axis:

    s      = sc · Σ_pairs (q_limb · k_limbᵀ)    int32 MXU, f32 combine
    m_new  = max(m, rowmax(s));   p = where(ok, exp(s - m_new), 0)
    l      = l·α + rowsum(p),     α = exp(m - m_new)
    acc    = acc·α + Σ_pairs (quant(p) · v_limb) · 2^{-(p_bits-1)}

``p ≤ 1`` by construction (``m_new`` dominates the in-tile row max), so P
quantizes with the *static* exponent ``-(p_bits-1)`` — no extra max pass.
``l`` accumulates the **unquantized** ``p`` (the normalizer is a kept op);
only the PV contraction sees the quantized mantissa.  The ``where``-guard on
``exp`` is essential: a fully masked tile has ``s == m_new == -1e30`` and
a bare ``exp(0) = 1`` would poison ``l``.

Backward (flash-attention-2 style, two kernels): ``dq`` walks key tiles
accumulating each row tile; ``dk/dv`` walks query tiles accumulating each
key tile.  Both recompute ``p`` from the saved row ``lse`` (no S×S
residual), quantize ``p`` and ``dS = p·(dp − δ)`` to limb planes
**in-register** (the digit split of kernels/dfx_quant.py), and run every
contraction on the integer MXU path.  ``dS``'s scale exponent is, in a
call that is neither causal nor windowed, a *bound-derived* int32 operand
(see core.int_ops); a causal or windowed call takes each (bq, bk) tile's
own, the DFX exponent of the tile's largest magnitude (``_tile_ds_exp``),
since there a row's P spreads over up to Sk keys and the bound's
``p <= 1`` leaves ~log2(Sk) of its bits unused.  No max pass over dS
either way: a tile's maximum is taken where the tile is made, and each
tile's product is scaled on its own before the f32 accumulation.

Masking: ``qpos = q_offset[b] + i_local`` (per-row offsets for KV-cache
decode / chunked prefill / continuous-batching slots), ``kpos`` the global
K column; validity is ``kpos < kv_len`` (ragged tail) ∧ causal
(``kpos ≤ qpos``) ∧ sliding window (``kpos > qpos − window``).  A causal
or windowed call visits only the grid blocks that validity can reach (the
band calls at the end of this file) and, inside a block, only the tiles it
can reach (``pl.when`` on each tile's band); a call that is neither runs
the full grid.

Accumulator budget (quantlint QL006): every integer dot is digit×digit —
|limb| ≤ 64 — so the int32 partials are bounded by ``64²·K`` with
``K ≤ max(hd_p, bq, bk)``: ≤ 2^19 at 128-wide tiles, five orders of
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
from repro.kernels.bfp_matmul import _SCOPED_VMEM_DEFAULT
from repro.kernels.dfx_quant import (  # noqa: E402
    LIMB_BITS, _round_clip, _split_planes, n_limbs)

_BIG_NEG = -1e30

#: the per-row query offsets and the scale exponents are scalars read by
#: index: Mosaic loads those only from SMEM.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

#: lanes a (rows, 1) f32 block or scratch occupies in VMEM.
_LANES = 128


def attention_vmem_bytes(kernel: str, rows: int, keys: int, hd_p: int,
                         limbs: int, tile: tuple[int, int],
                         all_live: bool) -> int:
    """VMEM bytes one grid step of ``kernel`` (``"fwd"``, ``"dq"`` or
    ``"dkv"``) keeps resident over a ``rows`` × ``keys`` grid block.

    Counted: the double-buffered blocks (int8 limb planes, ``limbs`` of
    each operand; f32 outputs; a (rows, 1) f32 column takes 128 lanes), the
    f32 scratch, and the in-step f32 temporaries of one visit — S, P, dP,
    dS, their limb planes, the mask and a pair product, about
    ``8 + 2·limbs`` values over the scores one visit covers (a tile; all
    rows of the block against a key tile, or a query tile against all its
    keys, when every tile is live) — plus two f32 partials of its output.
    """
    col = rows * _LANES * 4
    q_side = limbs * rows * hd_p
    k_side = limbs * keys * hd_p
    if kernel == "fwd":
        io = q_side + 2 * k_side + rows * hd_p * 4 + col
        scratch = 2 * col + rows * hd_p * 4
    elif kernel == "dq":
        io = 2 * q_side + 2 * k_side + 2 * col + rows * hd_p * 4
        scratch = rows * hd_p * 4
    else:
        io = 2 * q_side + 2 * k_side + 2 * col + 2 * keys * hd_p * 4
        scratch = 2 * keys * hd_p * 4
    bq, bk = tile
    if kernel == "dkv":                # a query tile against keys
        visit_rows, visit_cols = bq, (keys if all_live else bk)
        out = visit_cols
    else:                              # rows against a key tile
        visit_rows, visit_cols = (rows if all_live else bq), bk
        out = visit_rows
    temps = (8 + 2 * limbs) * visit_rows * visit_cols * 4 + 2 * out * hd_p * 4
    return 2 * io + scratch + temps


def _vmem_limit(kernel, blocks, hd_p, limbs, tile, all_live):
    """Scoped VMEM to ask Mosaic for: the modelled working set plus half
    again, or None (the default) where that fits the default."""
    need = attention_vmem_bytes(kernel, *blocks, hd_p, limbs, tile, all_live)
    need += need // 2
    return None if need <= _SCOPED_VMEM_DEFAULT else need


def _planes(ref, n: int, rows):
    """The ``n`` int8 limb planes of an ``(L, 1, R, C)`` block over
    ``rows``."""
    return [ref[j, 0, rows, :] for j in range(n)]


def _pair_dot(a, b, dims, exp_f32, shift: int):
    """Σ over limb pairs of ``dot(a[ja], b[jb])`` with the ordered f32
    combine of kernels/bfp_matmul.py.

    ``a`` and ``b`` are lists of 2-D digit planes: int8 limb planes, or
    the just-quantized P / dS as f32 digits, cast to int8 at the MXU
    boundary (every digit lies in [-64, 64]).  The scale is applied as
    ``exp2(exp) * 2^(7(ja+jb)+shift)`` — ``exp2`` once on the raw (traced)
    exponent, then a power-of-two *literal* multiply — never folded into
    the exp2 argument (not correctly rounded at every integer arg; same
    contract as the matmul combine).
    """
    lc, rc = dims
    scale0 = jnp.exp2(exp_f32)
    out = None
    for ja, pa in enumerate(a):
        for jb, pb in enumerate(b):
            part = jax.lax.dot_general(
                pa.astype(jnp.int8), pb,
                (((lc,), (rc,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            part = (part.astype(jnp.float32) * scale0) * (
                2.0 ** (LIMB_BITS * (ja + jb) + shift))
            out = part if out is None else out + part
    return out


def _tile_ds_exp(ds, ds_bits: int):
    """(1, 1) f32 scale exponent of one dS tile, as the DFX quantizer picks
    a tensor's (core/dfx.py): the frexp exponent of the tile's largest
    magnitude, less ``ds_bits - 1``.  Read exactly from the float's
    exponent field; an all-zero tile (floored at 2^-100) gets a finite
    one, and its mantissas are zero whatever it is."""
    amax = jnp.max(jnp.max(jnp.abs(ds), axis=1, keepdims=True), axis=0,
                   keepdims=True)
    field = jax.lax.bitcast_convert_type(jnp.maximum(amax, 2.0 ** -100),
                                         jnp.int32) >> 23
    return (field - 126 - (ds_bits - 1)).astype(jnp.float32)


def _valid_mask(off, row0, col0, shape, *, sq_p: int, kv_len: int,
                causal: bool, window):
    """Bool validity of the ``shape`` scores whose first row is R-axis row
    ``row0`` and whose first column is key ``col0``.

    ``off`` is the scalar per-batch-row query offset; the row index inside
    the group is recovered from the group-major R axis — the rows lie in
    one GQA group (every block of rows divides ``sq_p``), whose id is the
    scalar ``row0 // sq_p``.
    """
    g_blk = row0 // sq_p
    i_local = (row0 - g_blk * sq_p
               + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    qpos = off + i_local
    kpos = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = kpos < kv_len
    if causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    if window is not None:
        ok = jnp.logical_and(ok, kpos > qpos - window)
    return ok


def _k_band(off, qi, *, bq: int, bk: int, sq_p: int, n_k: int, causal: bool,
            window, xp=jnp):
    """First and last K block of width ``bk`` that query block ``qi`` of
    ``bq`` rows can see under the causal and window masks
    (``_valid_mask``); a scalar computation, run by the index maps (on grid
    blocks) and by the kernels (on score tiles) alike, and over arrays of
    blocks by the block chooser (``xp=numpy``)."""
    g_blk = (qi * bq) // sq_p
    r0 = qi * bq - g_blk * sq_p
    lo, hi = 0, n_k - 1
    if causal:
        hi = xp.minimum(hi, (off + r0 + bq - 1) // bk)
    if window is not None:
        lo = xp.maximum(off + r0 - window + 1, 0) // bk
    return lo, hi


def _q_band(off, kj, *, bq: int, bk: int, nqb: int, causal: bool, window,
            xp=jnp):
    """First and last query block of ``bq`` rows, inside every GQA group of
    ``nqb`` blocks, that can see K block ``kj`` of width ``bk``; ``hi <
    lo`` when none can."""
    lo, hi = 0, nqb - 1
    if causal:
        lo = xp.maximum(kj * bk - off, 0) // bq
    if window is not None:
        last = kj * bk + bk + window - 2 - off
        hi = xp.minimum(hi, xp.where(last < 0, -1, last // bq))
    return lo, hi


def _k_visits(n_k: int, *, bq: int, bk: int, rows: int, keys: int,
              sq_p: int, kv_heads: int, causal: bool, window):
    """``(block band, tile band, grid steps, k-block index map)`` of a
    call over ``n_k`` key tiles that steps over key blocks of ``keys``
    innermost (forward, dq): every block in turn, or, when causal or
    windowed, the band ``_k_band`` gives a block of ``rows`` queries,
    clamped at its end; the tile band is ``_k_band``'s for one ``bq`` ×
    ``bk`` score tile."""
    n_blocks = n_k * bk // keys
    if not causal and window is None:
        return None, None, n_blocks, lambda h, i, j, off: j
    mask = dict(sq_p=sq_p, causal=causal, window=window)
    band = functools.partial(_k_band, bq=rows, bk=keys, n_k=n_blocks, **mask)
    tile_band = functools.partial(_k_band, bq=bq, bk=bk, n_k=n_k, **mask)

    def kblk(h, i, j, off):
        lo, hi = band(off[h // kv_heads], i)
        return jnp.minimum(lo + j, hi)
    return (band, tile_band, _band_steps(n_blocks, rows, keys, causal, window),
            kblk)


def _band_steps(n: int, rows: int, cols: int, causal: bool, window) -> int:
    """Grid steps along the visited axis: the widest band a block of
    ``rows`` can see over blocks of ``cols`` when causal and windowed, else
    every block (the skipped ones re-point at a visited block)."""
    if causal and window is not None:
        return min(n, (rows + window - 2) // cols + 2)
    return n


def _visit_band(visit, band, off, blk, step):
    """Run ``visit`` on the block a grid step points at: the step itself
    on the full grid (``band`` None), else the step-th block of the band
    that ``band(off, blk)`` gives, skipped past the band's end."""
    if band is None:
        visit(step)
        return
    lo, hi = band(off, blk)
    pl.when(lo + step <= hi)(lambda: visit(jnp.minimum(lo + step, hi)))


def _walk_keys(tile, tile_band, off, qb, kb, *, bq: int, bk: int, nr: int,
               nt: int):
    """Run ``tile(r0, rows, k0, cols)`` (block-local offsets and sizes)
    over the score tiles of query block ``qb`` × key block ``kb``, key
    tiles ascending for every row tile (forward, dq).  With every tile
    live (``tile_band`` None) all rows go at once; else each row tile in
    turn, skipping the key tiles its band ``tile_band(off, qi)`` leaves
    out.  Each key tile is one loop iteration, as it was one grid step."""
    def key_tile(r0, rows, lo=None, hi=None):
        def body(t, carry):
            k0 = pl.multiple_of(t * bk, bk)
            if lo is None:
                tile(r0, rows, k0, bk)
            else:
                kj = kb * nt + t
                pl.when(jnp.logical_and(lo <= kj, kj <= hi))(
                    lambda: tile(r0, rows, k0, bk))
            return carry
        jax.lax.fori_loop(0, nt, body, 0)

    if tile_band is None:
        key_tile(0, nr * bq)
        return

    def row_tile(c, carry):
        key_tile(pl.multiple_of(c * bq, bq), bq, *tile_band(off, qb * nr + c))
        return carry
    jax.lax.fori_loop(0, nr, row_tile, 0)


def _walk_queries(tile, tile_band, off, qg, kb, *, bq: int, bk: int,
                  nr: int, nt: int):
    """Run ``tile(r0, rows, k0, cols)`` over the score tiles of key block
    ``kb`` × the query block ``qg`` (counted inside its GQA group), query
    tiles ascending for every key tile (dkv).  With every tile live all
    keys go at once; else each key tile on its own, skipping the query
    tiles its band ``tile_band(off, kj)`` leaves out.  Each query tile is
    one loop iteration, as it was one grid step."""
    def q_tile(c, carry):
        r0 = pl.multiple_of(c * bq, bq)
        if tile_band is None:
            tile(r0, bq, 0, nt * bk)
            return carry
        qi = qg * nr + c

        def key_tile(t, carry):
            lo, hi = tile_band(off, kb * nt + t)
            k0 = pl.multiple_of(t * bk, bk)
            pl.when(jnp.logical_and(lo <= qi, qi <= hi))(
                lambda: tile(r0, bq, k0, bk))
            return carry
        return jax.lax.fori_loop(0, nt, key_tile, carry)
    jax.lax.fori_loop(0, nr, q_tile, 0)


def _attn_call(kernel, *, banded: bool, grid, in_blocks, out_blocks,
               out_shape, scratch, name: str, interpret: bool,
               vmem_limit: int | None):
    """One attention ``pallas_call``, returned as ``f(*blocked, q_off=,
    exps=)``.  ``in_blocks``/``out_blocks`` pair each block shape with its
    index map ``(h, i, j, off)``; the kernel takes the blocked refs, then
    the (B,) query offsets and the exponents, both in SMEM.

    A banded (causal or windowed) call moves the query offsets to the
    scalar prefetch, where its index maps read them: a grid step past the
    last block its row block can see re-points at that block, which Pallas
    does not fetch again, and the kernel skips its work.  Every block
    skipped is wholly masked, and a wholly masked block changes none of
    the running sums, so the results are the full grid's bit for bit.  Any
    other call (the encoders) runs the full grid with no scalar prefetch,
    and its index maps never read ``off``."""
    def spec(shape, index):
        if banded:
            return pl.BlockSpec(shape, index)
        return pl.BlockSpec(shape, lambda h, i, j: index(h, i, j, None))

    n = len(in_blocks)
    if banded:
        inner = kernel

        def kernel(off_ref, *refs):
            return inner(*refs[:n], off_ref, *refs[n:])

    in_specs = [spec(*b) for b in in_blocks] + [_SMEM] * (1 if banded else 2)
    out_specs = (spec(*out_blocks) if isinstance(out_blocks, tuple)
                 else [spec(*b) for b in out_blocks])
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(banded), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        name=name,
        interpret=interpret,
    )

    def run(*blocked, q_off, exps):
        q_off, exps = q_off.astype(jnp.int32), exps.astype(jnp.int32)
        if banded:
            return call(q_off, *blocked, exps)
        return call(*blocked, q_off, exps)
    return run


def _p_exp(x, integer_exp: bool):
    """In-kernel softmax exp: FP32 (the paper's kept op) or the iapprox
    fixed-point form under ``kept_ops="integer"``.  Static flag — the swap
    is in-kernel, the dispatch count is unchanged either way.  i_exp clamps
    at exp(-30) ~ 9e-14, which rounds to a zero P mantissa at every
    supported p_bits, so the tail behaves like the exact exp's underflow."""
    if integer_exp:
        return iapprox.i_exp(x)
    return jnp.exp(x)


def _grid_block(blocks, bq: int, bk: int) -> tuple[int, int, int, int]:
    """``(rows, keys, nr, nt)`` of a grid block: ``blocks`` (None = one
    score tile) and its row and key tiles."""
    rows, keys = blocks or (bq, bk)
    assert rows % bq == 0 and keys % bk == 0, (blocks, bq, bk)
    return rows, keys, rows // bq, keys // bk


# =========================================================================
# Forward
# =========================================================================

def _int_attn_fwd_kernel(q_ref, k_ref, v_ref, off_ref, exp_ref,
                         o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                         n_k: int, lq: int, lk: int, lv: int, p_bits: int,
                         sq_p: int, kv_heads: int, kv_len: int, causal: bool,
                         window, sc: float, bq: int, bk: int, nr: int,
                         nt: int, integer_exp: bool, band=None,
                         tile_band=None):
    h = pl.program_id(0)
    qb = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _BIG_NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qe = exp_ref[0].astype(jnp.float32)
    ke = exp_ref[1].astype(jnp.float32)
    ve = exp_ref[2].astype(jnp.float32)
    off = off_ref[h // kv_heads]

    def visit(kb):
        def tile(r0, rows, k0, cols):
            rs, ks = pl.ds(r0, rows), pl.ds(k0, cols)
            ok = _valid_mask(off, qb * nr * bq + r0, kb * nt * bk + k0,
                             (rows, cols), sq_p=sq_p, kv_len=kv_len,
                             causal=causal, window=window)
            s = _pair_dot(_planes(q_ref, lq, rs), _planes(k_ref, lk, ks),
                          (1, 1), qe + ke, 0) * sc
            s = jnp.where(ok, s, _BIG_NEG)

            m_prev = m_scr[rs, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # the where-guard is load-bearing: a fully masked tile has
            # s == m_new == _BIG_NEG and exp(0) = 1 would corrupt l
            p = jnp.where(ok, _p_exp(s - m_new, integer_exp), 0.0)
            alpha = _p_exp(m_prev - m_new, integer_exp)
            l_scr[rs, :] = (l_scr[rs, :] * alpha
                            + jnp.sum(p, axis=1, keepdims=True))
            m_scr[rs, :] = m_new

            # P quantizes at the static exponent -(p_bits-1): p <= 1 by
            # construction
            pm = _round_clip(jnp.round(p * (2.0 ** (p_bits - 1))), p_bits)
            pv = _pair_dot(_split_planes(pm, n_limbs(p_bits)),
                           _planes(v_ref, lv, ks), (1, 0), ve, -(p_bits - 1))
            acc_scr[rs, :] = acc_scr[rs, :] * alpha + pv

        _walk_keys(tile, tile_band, off, qb, kb, bq=bq, bk=bk, nr=nr, nt=nt)

    _visit_band(visit, band, off, qb, step)

    @pl.when(step == n_k - 1)
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
    "bq", "bk", "blocks", "interpret", "integer_exp"))
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
    blocks: tuple[int, int] | None = None,
    interpret: bool = False,
    integer_exp: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused forward: ``(o, lse)`` — (BH, R, hd_p) and (BH, R, 1) f32.

    ``bq`` × ``bk`` is the score tile, ``blocks`` the grid block (rows,
    keys; None for one tile a step).  ``integer_exp=True`` swaps the
    in-kernel online softmax's FP32 exp for the iapprox fixed-point form
    (kept_ops="integer"); the running-max / normalizer recurrence is
    unchanged."""
    Lq, BH, R, hd_p = qm.shape
    Lk, BH2, Skp, hd2 = km.shape
    Lv = vm.shape[0]
    assert BH == BH2 and hd_p == hd2 and vm.shape[1:] == km.shape[1:], (
        qm.shape, km.shape, vm.shape)
    rows, keys, nr, nt = _grid_block(blocks, bq, bk)
    band, tile_band, steps, kblk = _k_visits(
        Skp // bk, bq=bq, bk=bk, rows=rows, keys=keys, sq_p=sq_p,
        kv_heads=kv_heads, causal=causal, window=window)
    assert R % rows == 0 and Skp % keys == 0 and sq_p % rows == 0, (
        R, Skp, sq_p, rows, keys)

    def q_rows(h, i, j, off):
        return (0, h, i, 0)

    def k_rows(h, i, j, off):
        return (0, h, kblk(h, i, j, off), 0)

    def out_rows(h, i, j, off):
        return (h, i, 0)

    kernel = functools.partial(
        _int_attn_fwd_kernel, n_k=steps, lq=Lq, lk=Lk, lv=Lv,
        p_bits=p_bits, sq_p=sq_p, kv_heads=kv_heads, kv_len=kv_len,
        causal=causal, window=window, sc=sc, bq=bq, bk=bk, nr=nr, nt=nt,
        integer_exp=integer_exp, band=band, tile_band=tile_band)
    return _attn_call(
        kernel, banded=band is not None, grid=(BH, R // rows, steps),
        in_blocks=[((Lq, 1, rows, hd_p), q_rows),
                   ((Lk, 1, keys, hd_p), k_rows),
                   ((Lv, 1, keys, hd_p), k_rows)],
        out_blocks=[((1, rows, hd_p), out_rows), ((1, rows, 1), out_rows)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, R, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((BH, R, 1), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((rows, 1), jnp.float32),      # running row max
            pltpu.VMEM((rows, 1), jnp.float32),      # running normalizer
            pltpu.VMEM((rows, hd_p), jnp.float32),   # output accumulator
        ],
        name="int_attn_fwd", interpret=interpret,
        vmem_limit=_vmem_limit("fwd", (rows, keys), hd_p, max(Lq, Lk, Lv),
                               (bq, bk), band is None),
    )(qm, km, vm, q_off=q_off, exps=exps)


# =========================================================================
# Backward — dQ (key tiles walked, each row tile accumulated)
# =========================================================================

def _int_attn_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                            off_ref, exp_ref, dq_ref, dq_scr, *,
                            n_k: int, lq: int, lk: int, lv: int, lg: int,
                            ds_bits: int, sq_p: int, kv_heads: int,
                            kv_len: int, causal: bool, window, sc: float,
                            bq: int, bk: int, nr: int, nt: int,
                            integer_exp: bool, band=None, tile_band=None):
    h = pl.program_id(0)
    qb = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qe = exp_ref[0].astype(jnp.float32)
    ke = exp_ref[1].astype(jnp.float32)
    ve = exp_ref[2].astype(jnp.float32)
    ge = exp_ref[3].astype(jnp.float32)
    # a causal or windowed call scales dS per tile (module docstring)
    tile_ds = causal or window is not None
    dse = None if tile_ds else exp_ref[4].astype(jnp.float32)
    off = off_ref[h // kv_heads]

    def visit(kb):
        def tile(r0, rows, k0, cols):
            rs, ks = pl.ds(r0, rows), pl.ds(k0, cols)
            ok = _valid_mask(off, qb * nr * bq + r0, kb * nt * bk + k0,
                             (rows, cols), sq_p=sq_p, kv_len=kv_len,
                             causal=causal, window=window)
            s = _pair_dot(_planes(q_ref, lq, rs), _planes(k_ref, lk, ks),
                          (1, 1), qe + ke, 0) * sc
            s = jnp.where(ok, s, _BIG_NEG)
            # padded q rows carry lse = +1e30, so p vanishes there exactly
            p = jnp.where(ok, _p_exp(s - lse_ref[0, rs, :], integer_exp),
                          0.0)

            dp = _pair_dot(_planes(g_ref, lg, rs), _planes(v_ref, lv, ks),
                           (1, 1), ge + ve, 0)
            ds = p * (dp - d_ref[0, rs, :])
            e = _tile_ds_exp(ds, ds_bits) if tile_ds else dse
            dsm = _round_clip(jnp.round(ds * jnp.exp2(-e)), ds_bits)
            dq_scr[rs, :] += _pair_dot(_split_planes(dsm, n_limbs(ds_bits)),
                                       _planes(k_ref, lk, ks), (1, 0),
                                       e + ke, 0)

        _walk_keys(tile, tile_band, off, qb, kb, bq=bq, bk=bk, nr=nr, nt=nt)

    _visit_band(visit, band, off, qb, step)

    @pl.when(step == n_k - 1)
    def _epilogue():
        dq_ref[0] = dq_scr[...] * sc


@functools.partial(jax.jit, static_argnames=(
    "ds_bits", "sq_p", "kv_heads", "kv_len", "causal", "window", "sc",
    "bq", "bk", "blocks", "interpret", "integer_exp"))
def int_attn_bwd_dq(
    qm: jax.Array,          # (Lq, BH, R, hd_p) int8 limb planes
    km: jax.Array,          # (Lk, BH, Sk_p, hd_p)
    vm: jax.Array,          # (Lv, BH, Sk_p, hd_p)
    gm: jax.Array,          # (Lg, BH, R, hd_p) quantized dO planes
    lse: jax.Array,         # (BH, R, 1) f32 (+1e30 on padded rows)
    delta: jax.Array,       # (BH, R, 1) f32 rowsum(dO * O)
    q_off: jax.Array,       # (B,) int32
    exps: jax.Array,        # (5,) int32 [q, k, v, g, dS] exponents; no dS
                            # in a causal or windowed call
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
    blocks: tuple[int, int] | None = None,
    interpret: bool = False,
    integer_exp: bool = False,
) -> jax.Array:
    """Fused dQ: (BH, R, hd_p) f32.  Tile and block as in ``int_attn_fwd``;
    ``integer_exp`` must match the forward's flag — the FA2 recompute
    ``p = exp(s - lse)`` has to rebuild the same P the forward
    contracted."""
    Lq, BH, R, hd_p = qm.shape
    Lk, _, Skp, _ = km.shape
    Lv, Lg = vm.shape[0], gm.shape[0]
    assert gm.shape[1:] == qm.shape[1:] and lse.shape == (BH, R, 1), (
        qm.shape, gm.shape, lse.shape)
    rows, keys, nr, nt = _grid_block(blocks, bq, bk)
    band, tile_band, steps, kblk = _k_visits(
        Skp // bk, bq=bq, bk=bk, rows=rows, keys=keys, sq_p=sq_p,
        kv_heads=kv_heads, causal=causal, window=window)

    def q_rows(h, i, j, off):
        return (0, h, i, 0)

    def k_rows(h, i, j, off):
        return (0, h, kblk(h, i, j, off), 0)

    def row(h, i, j, off):
        return (h, i, 0)

    kernel = functools.partial(
        _int_attn_bwd_dq_kernel, n_k=steps, lq=Lq, lk=Lk, lv=Lv, lg=Lg,
        ds_bits=ds_bits, sq_p=sq_p, kv_heads=kv_heads, kv_len=kv_len,
        causal=causal, window=window, sc=sc, bq=bq, bk=bk, nr=nr, nt=nt,
        integer_exp=integer_exp, band=band, tile_band=tile_band)
    return _attn_call(
        kernel, banded=band is not None, grid=(BH, R // rows, steps),
        in_blocks=[((Lq, 1, rows, hd_p), q_rows),
                   ((Lk, 1, keys, hd_p), k_rows),
                   ((Lv, 1, keys, hd_p), k_rows),
                   ((Lg, 1, rows, hd_p), q_rows),
                   ((1, rows, 1), row), ((1, rows, 1), row)],
        out_blocks=((1, rows, hd_p), row),
        out_shape=jax.ShapeDtypeStruct((BH, R, hd_p), jnp.float32),
        scratch=[pltpu.VMEM((rows, hd_p), jnp.float32)],
        name="int_attn_bwd_dq", interpret=interpret,
        vmem_limit=_vmem_limit("dq", (rows, keys), hd_p,
                               max(Lq, Lk, Lv, Lg), (bq, bk), band is None),
    )(qm, km, vm, gm, lse, delta, q_off=q_off, exps=exps)


# =========================================================================
# Backward — dK / dV (query tiles walked, each key tile accumulated)
# =========================================================================

def _int_attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                             off_ref, exp_ref, dk_ref, dv_ref,
                             dk_scr, dv_scr, *,
                             n_q: int, lq: int, lk: int, lv: int, lg: int,
                             p_bits: int, ds_bits: int, sq_p: int,
                             kv_heads: int, kv_len: int, causal: bool,
                             window, sc: float, bq: int, bk: int, nr: int,
                             nt: int, integer_exp: bool, q_block=None,
                             tile_band=None):
    h = pl.program_id(0)
    kb = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qe = exp_ref[0].astype(jnp.float32)
    ke = exp_ref[1].astype(jnp.float32)
    ve = exp_ref[2].astype(jnp.float32)
    ge = exp_ref[3].astype(jnp.float32)
    tile_ds = causal or window is not None
    dse = None if tile_ds else exp_ref[4].astype(jnp.float32)
    off = off_ref[h // kv_heads]
    nqb = sq_p // (nr * bq)                 # query blocks in one GQA group

    def visit(qb):
        def tile(r0, rows, k0, cols):
            rs, ks = pl.ds(r0, rows), pl.ds(k0, cols)
            ok = _valid_mask(off, qb * nr * bq + r0, kb * nt * bk + k0,
                             (rows, cols), sq_p=sq_p, kv_len=kv_len,
                             causal=causal, window=window)
            s = _pair_dot(_planes(q_ref, lq, rs), _planes(k_ref, lk, ks),
                          (1, 1), qe + ke, 0) * sc
            s = jnp.where(ok, s, _BIG_NEG)
            p = jnp.where(ok, _p_exp(s - lse_ref[0, rs, :], integer_exp),
                          0.0)

            # dV: quantized-Pᵀ · dO — the same static-exponent P mantissa
            # the forward contracted against V (straight-through at the
            # quantizer)
            pm = _round_clip(jnp.round(p * (2.0 ** (p_bits - 1))), p_bits)
            dv_scr[ks, :] += _pair_dot(_split_planes(pm, n_limbs(p_bits)),
                                       _planes(g_ref, lg, rs), (0, 0), ge,
                                       -(p_bits - 1))

            dp = _pair_dot(_planes(g_ref, lg, rs), _planes(v_ref, lv, ks),
                           (1, 1), ge + ve, 0)
            ds = p * (dp - d_ref[0, rs, :])
            e = _tile_ds_exp(ds, ds_bits) if tile_ds else dse
            dsm = _round_clip(jnp.round(ds * jnp.exp2(-e)), ds_bits)
            dk_scr[ks, :] += _pair_dot(_split_planes(dsm, n_limbs(ds_bits)),
                                       _planes(q_ref, lq, rs), (0, 0),
                                       e + qe, 0)

        _walk_queries(tile, tile_band, off, qb % nqb, kb, bq=bq, bk=bk,
                      nr=nr, nt=nt)

    if q_block is None:
        visit(step)
    else:
        qb, live = q_block(off, kb, step)
        pl.when(live)(lambda: visit(qb))

    @pl.when(step == n_q - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[...] * sc
        dv_ref[0] = dv_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "p_bits", "ds_bits", "sq_p", "kv_heads", "kv_len", "causal", "window",
    "sc", "bq", "bk", "blocks", "interpret", "integer_exp"))
def int_attn_bwd_dkv(
    qm: jax.Array,          # (Lq, BH, R, hd_p) int8 limb planes
    km: jax.Array,          # (Lk, BH, Sk_p, hd_p)
    vm: jax.Array,          # (Lv, BH, Sk_p, hd_p)
    gm: jax.Array,          # (Lg, BH, R, hd_p) quantized dO planes
    lse: jax.Array,         # (BH, R, 1) f32 (+1e30 on padded rows)
    delta: jax.Array,       # (BH, R, 1) f32 rowsum(dO * O)
    q_off: jax.Array,       # (B,) int32
    exps: jax.Array,        # (5,) int32 [q, k, v, g, dS] exponents; no dS
                            # in a causal or windowed call
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
    blocks: tuple[int, int] | None = None,
    interpret: bool = False,
    integer_exp: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused dK, dV: each (BH, Sk_p, hd_p) f32.  Tile and block as in
    ``int_attn_fwd``; ``integer_exp`` as in ``int_attn_bwd_dq``."""
    Lq, BH, R, hd_p = qm.shape
    Lk, _, Skp, _ = km.shape
    Lv, Lg = vm.shape[0], gm.shape[0]
    assert gm.shape[1:] == qm.shape[1:] and lse.shape == (BH, R, 1), (
        qm.shape, gm.shape, lse.shape)
    rows, keys, nr, nt = _grid_block(blocks, bq, bk)
    masked = causal or window is not None
    q_block, tile_band, steps = None, None, R // rows

    def qblk(h, j, i, off):
        return i

    if masked:
        nqb = sq_p // rows                 # query blocks in one GQA group
        band = functools.partial(_q_band, bq=rows, bk=keys, nqb=nqb,
                                 causal=causal, window=window)
        tile_band = functools.partial(_q_band, bq=bq, bk=bk,
                                      nqb=sq_p // bq, causal=causal,
                                      window=window)
        per_group = _band_steps(nqb, keys, rows, causal, window)
        steps = (R // sq_p) * per_group

        def q_block(off, kj, step):
            """(query block, whether it is visited) of grid step ``step``:
            the group's band, group by group."""
            g = step // per_group
            j = step - g * per_group
            lo, hi = band(off, kj)
            li = jnp.clip(jnp.minimum(lo + j, hi), 0, nqb - 1)
            return g * nqb + li, lo + j <= hi

        def qblk(h, j, i, off):
            return q_block(off[h // kv_heads], j, i)[0]

    def q_rows(h, j, i, off):
        return (0, h, qblk(h, j, i, off), 0)

    def row(h, j, i, off):
        return (h, qblk(h, j, i, off), 0)

    def k_rows(h, j, i, off):
        return (0, h, j, 0)

    def out_rows(h, j, i, off):
        return (h, j, 0)

    kernel = functools.partial(
        _int_attn_bwd_dkv_kernel, n_q=steps, lq=Lq, lk=Lk, lv=Lv, lg=Lg,
        p_bits=p_bits, ds_bits=ds_bits, sq_p=sq_p, kv_heads=kv_heads,
        kv_len=kv_len, causal=causal, window=window, sc=sc, bq=bq, bk=bk,
        nr=nr, nt=nt, integer_exp=integer_exp, q_block=q_block,
        tile_band=tile_band)
    return _attn_call(
        kernel, banded=masked, grid=(BH, Skp // keys, steps),
        in_blocks=[((Lq, 1, rows, hd_p), q_rows),
                   ((Lk, 1, keys, hd_p), k_rows),
                   ((Lv, 1, keys, hd_p), k_rows),
                   ((Lg, 1, rows, hd_p), q_rows),
                   ((1, rows, 1), row), ((1, rows, 1), row)],
        out_blocks=[((1, keys, hd_p), out_rows), ((1, keys, hd_p), out_rows)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Skp, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((BH, Skp, hd_p), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((keys, hd_p), jnp.float32),
            pltpu.VMEM((keys, hd_p), jnp.float32),
        ],
        name="int_attn_bwd_dkv", interpret=interpret,
        vmem_limit=_vmem_limit("dkv", (rows, keys), hd_p,
                               max(Lq, Lk, Lv, Lg), (bq, bk), not masked),
    )(qm, km, vm, gm, lse, delta, q_off=q_off, exps=exps)
