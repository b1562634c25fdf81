"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dfx
from repro.kernels import ops, ref
from repro.kernels.bfp_matmul import bfp_matmul
from repro.kernels.dfx_quant import dfx_quantize

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 512)])
@pytest.mark.parametrize("dtype", [jnp.int8])
def test_bfp_matmul_exact(M, K, N, dtype):
    xm = jax.random.randint(KEY, (M, K), -127, 128, jnp.int32).astype(dtype)
    wm = jax.random.randint(jax.random.fold_in(KEY, 1), (K, N), -127, 128,
                            jnp.int32).astype(dtype)
    for e in (-7, 0, 3):
        # single-limb planes: the kernel takes (L, M, K) stacks
        y = bfp_matmul(xm[None], wm[None], jnp.int32(e), interpret=True)
        yr = ref.bfp_matmul_ref(xm, wm, jnp.int32(e))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("blocks", [(128, 128, 128), (256, 128, 128)])
def test_bfp_matmul_block_shapes(blocks):
    bm, bn, bk = blocks
    M, K, N = 2 * bm, 2 * bk, 2 * bn
    xm = jax.random.randint(KEY, (M, K), -127, 128, jnp.int32).astype(jnp.int8)
    wm = jax.random.randint(KEY, (K, N), -127, 128, jnp.int32).astype(jnp.int8)
    y = bfp_matmul(xm[None], wm[None], jnp.int32(-2), bm=bm, bn=bn, bk=bk,
                   interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.bfp_matmul_ref(xm, wm, jnp.int32(-2))))


@pytest.mark.parametrize("bits", [8, 10, 12, 14, 16])
def test_limb_decomposition_roundtrip(bits):
    """Stacked limb planes reconstruct the logical mantissa exactly.

    b=14 is the regression width: the old mod-extracting final limb dropped
    a carry of ±1·2^14 at the extreme mantissa ±8191 (the raw-carry final
    plane keeps it)."""
    lim = 2 ** (bits - 1) - 1
    m = jax.random.randint(KEY, (64, 64), -lim, lim + 1, jnp.int32)
    m = m.at[0, 0].set(lim).at[0, 1].set(-lim)     # force the carry corners
    planes = ops.split_limbs_stacked(m, bits)
    rec = sum(planes[j].astype(jnp.int32) * (2 ** (7 * j))
              for j in range(planes.shape[0]))
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(m))
    assert planes.dtype == jnp.int8
    assert planes.shape[0] == {8: 1, 10: 2, 12: 2, 14: 2, 16: 3}[bits]
    # every non-final digit balanced in [-64, 63]; final carry within int8
    pl_np = np.asarray(planes, np.int32)
    if pl_np.shape[0] > 1:
        assert pl_np[:-1].min() >= -64 and pl_np[:-1].max() <= 63
        assert pl_np[-1].min() >= -64 and pl_np[-1].max() <= 64


@pytest.mark.parametrize("xb,wb", [(8, 8), (12, 8), (12, 12), (16, 16)])
@pytest.mark.parametrize("shape", [(100, 200, 60), (32, 128, 128)])
def test_dfx_matmul_tiled_vs_oracle(xb, wb, shape):
    M, K, N = shape
    x = jax.random.normal(KEY, (M, K)) * 2.0
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (K, N)) * 0.3
    qx, qw = dfx.quantize(x, xb), dfx.quantize(w, wb)
    y = ops.dfx_matmul_tiled(qx.m, qx.exp, xb, qw.m, qw.exp, wb,
                             interpret=True)
    # exact integer oracle in numpy int64 (the limb path is bit-exact; jnp
    # float64 would silently truncate to f32 under the default x64=off)
    acc = np.asarray(qx.m, np.int64) @ np.asarray(qw.m, np.int64)
    yr = acc.astype(np.float64) * 2.0 ** float(qx.exp + qw.exp)
    # each limb partial is bit-exact int32; the cross-limb combine happens in
    # f32 (epilogue), so tolerance = f32 ulp of the largest partial magnitude
    np.testing.assert_allclose(np.asarray(y, np.float64), yr,
                               atol=abs(yr).max() * 2e-6 + 1e-12)


@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("shape", [(64, 128), (100, 37)])
def test_quantize_kernel_matches_core(bits, shape):
    x = jax.random.normal(KEY, shape) * 3
    t = dfx.quantize(x, bits)
    m = ops.quantize_pallas(x, t.exp, bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(t.m))


@pytest.mark.parametrize("bits", [8, 12])
def test_quantize_kernel_stochastic_matches_oracle(bits):
    x = jax.random.normal(KEY, (64, 96)) * 2
    t = dfx.quantize(x, bits)
    u = jax.random.uniform(jax.random.fold_in(KEY, 2), x.shape)
    m = ops.quantize_pallas(x, t.exp, bits, u=u, interpret=True)
    mr = ref.dfx_quantize_ref(x, t.exp, bits, u=u)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))


@pytest.mark.parametrize("R,D", [(16, 128), (8, 256), (24, 64), (10, 96)])
@pytest.mark.parametrize("bits", [12, 16])
def test_layernorm_kernel(R, D, bits):
    """Multi-output fused LN fwd vs the exact-f64 oracle: y AND the
    (mu, rstd) statistics the kernel normalized with (the non-multiple-of-8
    row count exercises the padding path)."""
    x = jax.random.normal(KEY, (R, D)) * 2
    t = dfx.quantize(x, bits)
    gm = jax.random.normal(jax.random.fold_in(KEY, 3), (D,))
    bt = jax.random.normal(jax.random.fold_in(KEY, 4), (D,))
    y, mu, rstd = ops.layernorm_pallas(t.m, t.exp, gm, bt, interpret=True)
    yr, mur, rstdr = ref.int_layernorm_fwd_ref(t.m, t.exp, gm, bt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mur),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rstd), np.asarray(rstdr),
                               rtol=1e-6, atol=0)


@pytest.mark.parametrize("E", [1, 3])
def test_bfp_matmul_batched_k_grid(E):
    """Batched NN/NT/TN kernels whose contraction spans two grid steps (the
    int32 scratch path, k on the fourth grid axis), 2 x 3 limb planes and a
    per-expert exponent, bit-equal to the per-pair limb loop."""
    from repro.kernels.bfp_matmul import (bfp_matmul_batched,
                                          bfp_matmul_batched_nt,
                                          bfp_matmul_batched_tn)
    exps = jnp.arange(E, dtype=jnp.int32) - 2

    def planes(key, n, shape):
        return jax.random.randint(jax.random.fold_in(KEY, key), (n, E) + shape,
                                  -64, 64, jnp.int32).astype(jnp.int8)

    a, b = planes(1, 2, (128, 256)), planes(2, 3, (256, 128))
    bt, at = planes(3, 3, (128, 256)), planes(4, 2, (256, 128))
    cases = [
        (bfp_matmul_batched(a, b, exps, bk=128, interpret=True),
         a, b, (((2,), (1,)), ((0,), (0,)))),
        (bfp_matmul_batched_nt(a, bt, exps, bk=128, interpret=True),
         a, bt, (((2,), (2,)), ((0,), (0,)))),
        (bfp_matmul_batched_tn(at, b, exps, bk=128, interpret=True),
         at, b, (((1,), (1,)), ((0,), (0,)))),
    ]
    for y, xm, wm, dn in cases:
        loop = ref.limb_loop_matmul_ref(xm, wm, exps.reshape(E, 1, 1),
                                        dimension_numbers=dn)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(loop))


@pytest.mark.parametrize("E", [1, 4])
@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (96, 200, 72)])
def test_bfp_matmul_batched_exact(E, M, K, N):
    """Batched NN/NT/TN kernels vs batched int32 oracles: per-expert
    exponent vectors, one pallas_call per layout."""
    from repro.kernels.bfp_matmul import (bfp_matmul_batched,
                                          bfp_matmul_batched_nt,
                                          bfp_matmul_batched_tn)
    exps = jnp.arange(E, dtype=jnp.int32) - 3
    # NN: (E, M, K) @ (E, K, N) — kernels take plane-major (L, E, ...) stacks
    xm = jax.random.randint(KEY, (E, 128, 128), -127, 128,
                            jnp.int32).astype(jnp.int8)
    wm = jax.random.randint(jax.random.fold_in(KEY, 1), (E, 128, 128),
                            -127, 128, jnp.int32).astype(jnp.int8)
    y = bfp_matmul_batched(xm[None], wm[None], exps, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.bfp_matmul_batched_ref(xm, wm, exps)))
    ynt = bfp_matmul_batched_nt(xm[None], wm[None], exps, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(ynt),
        np.asarray(ref.bfp_matmul_batched_nt_ref(xm, wm, exps)))
    ytn = bfp_matmul_batched_tn(xm[None], wm[None], exps, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(ytn),
        np.asarray(ref.bfp_matmul_batched_tn_ref(xm, wm, exps)))
    # padded/ragged shapes through the tiled wrappers, vs int64 numpy
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (E, M, K)) * 2.0
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (E, K, N)) * 0.3
    qx = dfx.quantize(x, 12, reduce_axes=(1, 2))
    qw = dfx.quantize(w, 12, reduce_axes=(1, 2))
    yt = ops.dfx_matmul_tiled_batched(qx.m, qx.exp, 12, qw.m, qw.exp, 12,
                                      interpret=True)
    acc = np.einsum("eck,ekn->ecn", np.asarray(qx.m, np.int64),
                    np.asarray(qw.m, np.int64))
    yr = acc.astype(np.float64) * 2.0 ** np.asarray(
        qx.exp + qw.exp, np.float64)
    np.testing.assert_allclose(np.asarray(yt, np.float64), yr,
                               atol=np.abs(yr).max() * 2e-6 + 1e-12)


@pytest.mark.parametrize("bits", [8, 12, 16])
def test_batched_backward_wrappers_vs_oracle(bits):
    """Batched NT (dX) and TN (dW) tiled wrappers against int64 numpy, with
    ragged shapes exercising the per-expert zero padding."""
    E, M, K, N = 3, 40, 60, 37
    x = jax.random.normal(KEY, (E, M, K)) * 1.5
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (E, K, N)) * 0.4
    g = jax.random.normal(jax.random.fold_in(KEY, 2), (E, M, N))
    qx = dfx.quantize(x, bits, reduce_axes=(1, 2))
    qw = dfx.quantize(w, bits, reduce_axes=(1, 2))
    qg = dfx.quantize(g, bits, reduce_axes=(1, 2))
    dx = ops.dfx_matmul_tiled_batched_nt(qg.m, qg.exp, bits,
                                         qw.m, qw.exp, bits, interpret=True)
    acc = np.einsum("ecn,ekn->eck", np.asarray(qg.m, np.int64),
                    np.asarray(qw.m, np.int64))
    dxr = acc.astype(np.float64) * 2.0 ** np.asarray(
        qg.exp + qw.exp, np.float64)
    np.testing.assert_allclose(np.asarray(dx, np.float64), dxr,
                               atol=np.abs(dxr).max() * 2e-6 + 1e-12)
    dw = ops.dfx_matmul_tiled_batched_tn(qx.m, qx.exp, bits,
                                         qg.m, qg.exp, bits, interpret=True)
    accw = np.einsum("eck,ecn->ekn", np.asarray(qx.m, np.int64),
                     np.asarray(qg.m, np.int64))
    dwr = accw.astype(np.float64) * 2.0 ** np.asarray(
        qx.exp + qg.exp, np.float64)
    np.testing.assert_allclose(np.asarray(dw, np.float64), dwr,
                               atol=np.abs(dwr).max() * 2e-6 + 1e-12)


@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("shape", [(3, 64, 96), (2, 100, 37)])
def test_quantize_grouped_matches_per_slice(bits, shape):
    """One grouped-scale kernel launch == E per-slice quantizations."""
    E = shape[0]
    x = jax.random.normal(KEY, shape) * jnp.exp2(
        jnp.arange(E, dtype=jnp.float32) * 2 - 2).reshape(E, 1, 1)
    per = [dfx.quantize(x[e], bits) for e in range(E)]
    exp = jnp.stack([p.exp for p in per])
    m = ops.quantize_pallas_batched(x, exp, bits, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(m), np.stack([np.asarray(p.m) for p in per]))
    # stochastic path vs the grouped oracle. b=16 is excluded (as in the
    # unbatched stochastic test): at |y| ~ 2^15 the f32 `y + u` can straddle
    # an integer boundary differently when XLA fuses the shift-multiply and
    # the noise add into an FMA, so jitted-kernel vs eager-oracle is not
    # bit-stable there.
    if bits < 16:
        u = jax.random.uniform(jax.random.fold_in(KEY, 4), x.shape)
        ms = ops.quantize_pallas_batched(x, exp, bits, u=u, interpret=True)
        mr = ref.dfx_quantize_grouped_ref(x, exp, bits, u=u)
        np.testing.assert_array_equal(np.asarray(ms), np.asarray(mr))


def test_round_up_multiple():
    assert ops._round_up_multiple(1, 8) == 8
    assert ops._round_up_multiple(8, 8) == 8
    assert ops._round_up_multiple(9, 8) == 16
    assert ops._round_up_multiple(127, 128) == 128
    assert ops._round_up_multiple(129, 128) == 256


def _assert_tiles(blocks, M, N, K, contract_rows=False):
    """Each block divides its dim's padded extent: the next multiple of 128,
    or of 8 for an operand row dim under 128 rows (no extra padding)."""
    bm, bn, bk = blocks
    rows, rb = (K, bk) if contract_rows else (M, bm)
    for n, b, sublane in ((M, bm, not contract_rows), (N, bn, False),
                          (K, bk, contract_rows)):
        ext = ops._padded(n, sublane)
        assert ext == (ops._round_up_multiple(n, 8) if sublane and n < 128
                       else ops._round_up_multiple(n, 128)), (n, ext)
        assert ext % b == 0, (n, b, ext)
        assert b % (8 if sublane else 128) == 0 or b == ext, (n, b)
    if rows < 128:          # small row counts keep their 8-rounding
        assert rb == ops._round_up_multiple(rows, 8)


@pytest.mark.parametrize("M,N,K", [(1, 1, 1), (4, 7, 100), (8, 128, 128),
                                   (100, 37, 60), (128, 256, 512),
                                   (200, 130, 70)])
def test_pick_blocks_small_and_ragged(M, N, K):
    """Lane dims take multiples of 128 and the operand row dim multiples of
    8, each dividing its padded extent exactly, so no operand is padded
    further than before; small row counts are padded to the next 8 rows,
    not to 128 (decode keeps its row block), and the working set fits the
    budget — with the rows on the output (NN, NT) or contracted (TN)."""
    for contract_rows in (False, True):
        blocks = ops._pick_blocks(M, N, K, 3, 3, contract_rows=contract_rows)
        _assert_tiles(blocks, M, N, K, contract_rows)
        n_k = ops._padded(K, contract_rows) // blocks[2]
        assert ops.matmul_vmem_bytes(*blocks, 3, 3, n_k) \
            <= ops._MATMUL_VMEM_BUDGET


@pytest.mark.parametrize("lx,lw", [(1, 1), (2, 2), (3, 3), (3, 1)])
def test_pick_blocks_vmem_budget(lx, lw):
    """The chooser counts limb planes and per-pair accumulators: at any limb
    count the chosen blocks fit the budget, and under a tight budget the
    3×3-limb blocks are no larger than the 1-limb ones (regression: the old
    chooser sized blocks for the 1-limb case only) — in both the output-row
    and the contracted-row (TN) layout."""
    for contract_rows in (False, True):
        blocks = ops._pick_blocks(4096, 4096, 4096, lx, lw,
                                  contract_rows=contract_rows)
        _assert_tiles(blocks, 4096, 4096, 4096, contract_rows)
        assert ops.matmul_vmem_bytes(*blocks, lx, lw, 4096 // blocks[2]) \
            <= ops._MATMUL_VMEM_BUDGET
        # tight: the 1-limb working set of a 128^3 k-grid step
        tight = ops.matmul_vmem_bytes(128, 128, 128, 1, 1, 2)
        b1 = ops._pick_blocks(4096, 4096, 4096, 1, 1, budget=tight,
                              contract_rows=contract_rows)
        bl = ops._pick_blocks(4096, 4096, 4096, lx, lw, budget=tight,
                              contract_rows=contract_rows)
        assert ops.matmul_vmem_bytes(*b1, 1, 1, 4096 // b1[2]) <= tight
        fits = ops.matmul_vmem_bytes(*bl, lx, lw, 4096 // bl[2]) <= tight
        smallest = min(bl) == 8     # nothing fits: the smallest tiles
        assert fits or smallest, (bl, lx, lw)
        assert bl[0] * bl[1] * bl[2] <= b1[0] * b1[1] * b1[2]
        if (lx, lw) == (3, 3):
            assert bl[0] * bl[1] * bl[2] < b1[0] * b1[1] * b1[2]


def test_matmul_vmem_bytes_model():
    """9 limb pairs cost 9x the accumulator scratch and 3x the operand
    stacks of the 1-limb case; a contraction in one grid step (n_k = 1)
    keeps no accumulators at all."""
    one = ops.matmul_vmem_bytes(128, 128, 128, 1, 1, n_k=2)
    nine = ops.matmul_vmem_bytes(128, 128, 128, 3, 3, n_k=2)
    assert nine - one == (2 * 4 * 128 * 128       # 4 more operand planes
                          + 8 * 128 * 128 * 4)    # 8 more accumulators
    assert nine == (2 * (3 + 3) * 128 * 128        # int8 operand stacks x2
                    + 2 * 128 * 128 * 4            # f32 out block x2
                    + 2 * 128 * 128 * 4            # pair product, sum
                    + 9 * 128 * 128 * 4)           # per-pair int32 acc
    assert ops.matmul_vmem_bytes(128, 128, 128, 3, 3, n_k=1) \
        == nine - 9 * 128 * 128 * 4


#: bert-base (B=32, S=384) and vit-base (64 x 197) rows; the linears of one
#: encoder layer as (K, N): q/k/v/o, the FFN up- and down-projection.
PAPER_ROWS = (32 * 384, 64 * 197)
PAPER_LINEARS = {"qkvo": (768, 768), "w1": (768, 3072), "w2": (3072, 768)}


@pytest.mark.parametrize("rows", PAPER_ROWS, ids=["bert", "vit"])
@pytest.mark.parametrize("linear", sorted(PAPER_LINEARS))
def test_pick_blocks_paper_shapes_full_contraction(rows, linear):
    """At every bert and vit int16 product the forward (NN) and dX (NT)
    blocks hold the whole contraction in one grid step, the budget allows
    it, and the blocks tile the padded extents exactly (vit's 12608 rows
    pad to 12672 = 128 x 99, not to a multiple of a larger block)."""
    K, N = PAPER_LINEARS[linear]
    for M, Nout, Kc in ((rows, N, K), (rows, K, N)):        # NN, NT
        bm, bn, bk = ops._pick_blocks(M, Nout, Kc, 3, 3)
        _assert_tiles((bm, bn, bk), M, Nout, Kc)
        assert bk == Kc
        assert ops.matmul_vmem_bytes(bm, bn, bk, 3, 3, 1) \
            <= ops._MATMUL_VMEM_BUDGET
        assert ops._padded(M, True) == ops._round_up_multiple(M, 128)
    tn = ops._pick_blocks(K, N, rows, 3, 3, contract_rows=True)  # dW
    _assert_tiles(tn, K, N, rows, contract_rows=True)
    assert ops.matmul_vmem_bytes(
        *tn, 3, 3, ops._padded(rows, True) // tn[2]) \
        <= ops._MATMUL_VMEM_BUDGET


@pytest.mark.parametrize("M,N,K", [(3, 5, 2), (100, 37, 60), (130, 128, 250)])
def test_dfx_matmul_tiled_ragged_shapes(M, N, K):
    x = jax.random.normal(KEY, (M, K)) * 1.5
    w = jax.random.normal(jax.random.fold_in(KEY, 7), (K, N)) * 0.4
    qx, qw = dfx.quantize(x, 12), dfx.quantize(w, 12)
    y = ops.dfx_matmul_tiled(qx.m, qx.exp, 12, qw.m, qw.exp, 12,
                             interpret=True)
    acc = np.asarray(qx.m, np.int64) @ np.asarray(qw.m, np.int64)
    yr = acc.astype(np.float64) * 2.0 ** float(qx.exp + qw.exp)
    np.testing.assert_allclose(np.asarray(y, np.float64), yr,
                               atol=abs(yr).max() * 2e-6 + 1e-12)


@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("M,K,N", [(64, 48, 80), (100, 60, 37)])
def test_backward_transpose_contractions_vs_oracle(bits, M, K, N):
    """NT (dX = G·Wᵀ) and TN (dW = Xᵀ·G) kernel paths against the exact
    int64 numpy oracle, across the limb-decomposition bit-widths."""
    x = jax.random.normal(KEY, (M, K)) * 2.0
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (K, N)) * 0.3
    g = jax.random.normal(jax.random.fold_in(KEY, 2), (M, N))
    qx, qw, qg = (dfx.quantize(x, bits), dfx.quantize(w, bits),
                  dfx.quantize(g, bits))

    dx = ops.dfx_matmul_tiled_nt(qg.m, qg.exp, bits, qw.m, qw.exp, bits,
                                 interpret=True)
    acc = np.asarray(qg.m, np.int64) @ np.asarray(qw.m, np.int64).T
    dxr = acc.astype(np.float64) * 2.0 ** float(qg.exp + qw.exp)
    np.testing.assert_allclose(np.asarray(dx, np.float64), dxr,
                               atol=abs(dxr).max() * 2e-6 + 1e-12)

    dw = ops.dfx_matmul_tiled_tn(qx.m, qx.exp, bits, qg.m, qg.exp, bits,
                                 interpret=True)
    accw = np.asarray(qx.m, np.int64).T @ np.asarray(qg.m, np.int64)
    dwr = accw.astype(np.float64) * 2.0 ** float(qx.exp + qg.exp)
    np.testing.assert_allclose(np.asarray(dw, np.float64), dwr,
                               atol=abs(dwr).max() * 2e-6 + 1e-12)


@pytest.mark.parametrize("blocks", [(128, 128, 128), (256, 128, 128)])
def test_bfp_matmul_nt_tn_block_shapes(blocks):
    from repro.kernels.bfp_matmul import bfp_matmul_nt, bfp_matmul_tn
    bm, bn, bk = blocks
    M, N, K = 2 * bm, 2 * bk, 2 * bn
    gm = jax.random.randint(KEY, (M, N), -127, 128, jnp.int32).astype(jnp.int8)
    wm = jax.random.randint(jax.random.fold_in(KEY, 1), (K, N), -127, 128,
                            jnp.int32).astype(jnp.int8)
    y = bfp_matmul_nt(gm[None], wm[None], jnp.int32(-1), bm=bm, bn=bn, bk=bk,
                      interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.bfp_matmul_nt_ref(gm, wm, jnp.int32(-1))))
    xm = jax.random.randint(jax.random.fold_in(KEY, 2), (N, M), -127, 128,
                            jnp.int32).astype(jnp.int8)
    gm2 = jax.random.randint(jax.random.fold_in(KEY, 3), (N, K), -127, 128,
                             jnp.int32).astype(jnp.int8)
    y2 = bfp_matmul_tn(xm[None], gm2[None], jnp.int32(2), bm=bm, bn=bn, bk=bk,
                       interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y2),
        np.asarray(ref.bfp_matmul_tn_ref(xm, gm2, jnp.int32(2))))


def test_grad_pallas_backend_matches_sim():
    """jax.grad end-to-end: backend='pallas' gradients equal backend='sim'
    up to f32 accumulation rounding (RN rounding for determinism)."""
    import dataclasses
    from repro.core import int_ops
    from repro.core.qconfig import QuantConfig

    cfg_s = dataclasses.replace(QuantConfig.int12(), stochastic_grad=False,
                                backend="sim")
    cfg_p = dataclasses.replace(cfg_s, backend="pallas")
    x = jax.random.normal(KEY, (4, 16, 48))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (48, 24)) * 0.1
    b = jnp.zeros((24,))
    r = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 16, 24))

    def loss(x, w, b, c):
        return jnp.sum(int_ops.int_linear(x, w, b, None, c) * r)

    gs = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, cfg_s)
    gp = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, cfg_p)
    for a, bb in zip(gs, gp):
        scale = float(jnp.abs(a).max()) + 1e-12
        assert float(jnp.abs(a - bb).max()) / scale < 1e-5


def test_kernel_end_to_end_linear_close_to_fp32():
    """quantize kernel -> matmul kernel pipeline ~ fp32 matmul."""
    x = jax.random.normal(KEY, (128, 256))
    w = jax.random.normal(jax.random.fold_in(KEY, 5), (256, 128)) * 0.1
    qx, qw = dfx.quantize(x, 12), dfx.quantize(w, 12)
    xm = ops.quantize_pallas(x, qx.exp, 12, interpret=True)
    wm = ops.quantize_pallas(w, qw.exp, 12, interpret=True)
    y = ops.dfx_matmul_tiled(xm, qx.exp, 12, wm, qw.exp, 12, interpret=True)
    y0 = x @ w
    relerr = float(jnp.linalg.norm(y - y0) / jnp.linalg.norm(y0))
    assert relerr < 2e-2, relerr
