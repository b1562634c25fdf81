"""Single-dispatch multi-limb matmul: acceptance + parity sweeps.

The PR's acceptance properties (ISSUE 4):

* ONE traced ``pallas_call`` per matmul direction at every bit-width, both
  unbatched and batched (it was ``Lx·Lw`` ≤ 9);
* the quantize kernel emits the stacked limb planes directly — no
  ``_split_limbs`` shift/round chain (int ``rem``/``div`` arithmetic) in the
  traced layer jaxpr, forward or backward;
* results are BIT-EXACT against the removed per-limb-pair dispatch loop
  (``ref.limb_loop_matmul_ref`` reproduces its exact int32-partial +
  ordered-f32-combine semantics) on oracle sweeps, and within the f32
  cross-limb combine bound of the exact int64 oracle;
* ``jax.grad`` end-to-end through the fused path tracks FP32 at every
  preset.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import count_eqns, count_pallas_calls, rules
from repro.core import dfx, int_ops
from repro.core.qconfig import PRESETS, QuantConfig
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)

#: bit-width -> limb-plane count (ops.split_limbs_stacked / dfx_quant.n_limbs)
LIMBS = {8: 1, 12: 2, 16: 3}

#: deliberately non-multiple-of-8/128 shapes (odd M/K/N) — padding sweeps
ODD_SHAPES = ((97, 131, 59), (100, 200, 60), (33, 257, 129))


def _quant(shape_key, shape, bits, scale=1.0):
    x = jax.random.normal(jax.random.fold_in(KEY, shape_key), shape) * scale
    return dfx.quantize(x, bits)


# -------------------------------------------------------------------------
# one pallas_call per direction, at every bit-width
# -------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 12, 16])
def test_single_dispatch_per_direction(bits):
    qx = _quant(1, (40, 72), bits)
    qw = _quant(2, (72, 24), bits, 0.3)
    qg = _quant(3, (40, 24), bits)

    def nn():
        return ops.dfx_matmul_tiled(qx.m, qx.exp, bits, qw.m, qw.exp, bits,
                                    interpret=True)

    def nt():
        return ops.dfx_matmul_tiled_nt(qg.m, qg.exp, bits, qw.m, qw.exp,
                                       bits, interpret=True)

    def tn():
        return ops.dfx_matmul_tiled_tn(qx.m, qx.exp, bits, qg.m, qg.exp,
                                       bits, interpret=True)

    for name, fn in (("nn", nn), ("nt", nt), ("tn", tn)):
        n = count_pallas_calls(jax.make_jaxpr(fn)())
        assert n == 1, (name, bits, n)


@pytest.mark.parametrize("bits", [8, 16])
def test_single_dispatch_per_direction_batched(bits):
    E = 4
    qx = dfx.quantize(jax.random.normal(KEY, (E, 24, 40)), bits,
                      reduce_axes=(1, 2))
    qw = dfx.quantize(jax.random.normal(jax.random.fold_in(KEY, 1),
                                        (E, 40, 16)), bits, reduce_axes=(1, 2))
    qg = dfx.quantize(jax.random.normal(jax.random.fold_in(KEY, 2),
                                        (E, 24, 16)), bits, reduce_axes=(1, 2))
    fns = {
        "nn": lambda: ops.dfx_matmul_tiled_batched(
            qx.m, qx.exp, bits, qw.m, qw.exp, bits, interpret=True),
        "nt": lambda: ops.dfx_matmul_tiled_batched_nt(
            qg.m, qg.exp, bits, qw.m, qw.exp, bits, interpret=True),
        "tn": lambda: ops.dfx_matmul_tiled_batched_tn(
            qx.m, qx.exp, bits, qg.m, qg.exp, bits, interpret=True),
    }
    for name, fn in fns.items():
        n = count_pallas_calls(jax.make_jaxpr(fn)())
        assert n == 1, (name, bits, n)


def test_layer_dispatch_counts_and_no_split_chain():
    """int_linear on pallas at b=16: 3 pallas_calls forward (quantize x,
    quantize w, ONE matmul) and 6 forward+backward (+ quantize g, NT, TN) —
    and the traced jaxpr contains no limb-split arithmetic (the int
    ``rem``/``div`` chain of the removed XLA ``_split_limbs``) outside the
    kernels."""
    pal = dataclasses.replace(QuantConfig.int16(), backend="pallas",
                              stochastic_grad=False)
    x = jax.random.normal(KEY, (4, 16, 48))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (48, 24)) * 0.1

    def fwd(x, w):
        return int_ops.int_linear(x, w, None, None, pal)

    def loss(x, w):
        return jnp.sum(fwd(x, w) ** 2)

    jf = jax.make_jaxpr(fwd)(x, w)
    jb = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)
    assert count_pallas_calls(jf) == 3
    assert count_pallas_calls(jb) == 6
    for j in (jf, jb):
        assert count_eqns(j, "rem", recurse_pallas=False) == 0
        assert count_eqns(j, "div", recurse_pallas=False) == 0
        # the analyzer's integer-closure rule subsumes the rem/div counts:
        # no limb-split chains, no XLA mantissa dots, no rsqrt leaks
        assert not rules.check_integer_closure(j)


# -------------------------------------------------------------------------
# bit-exact vs the removed limb-loop path; oracle sweeps on odd shapes
# -------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("M,K,N", ODD_SHAPES)
def test_fused_bit_exact_vs_limb_loop_and_oracle(bits, M, K, N):
    """All three directions: the fused kernel must be bit-equal to the
    removed per-pair dispatch loop (same int32 partials, same ordered f32
    combine) and within the ~1 ulp f32 combine bound of the exact int64
    oracle."""
    qx = _quant(10, (M, K), bits, 2.0)
    qw = _quant(11, (K, N), bits, 0.3)
    qg = _quant(12, (M, N), bits)

    cases = [
        ("nn", ops.dfx_matmul_tiled(qx.m, qx.exp, bits, qw.m, qw.exp, bits,
                                    interpret=True),
         (qx, qw), (((1,), (0,)), ((), ())),
         np.asarray(qx.m, np.int64) @ np.asarray(qw.m, np.int64)),
        ("nt", ops.dfx_matmul_tiled_nt(qg.m, qg.exp, bits, qw.m, qw.exp,
                                       bits, interpret=True),
         (qg, qw), (((1,), (1,)), ((), ())),
         np.asarray(qg.m, np.int64) @ np.asarray(qw.m, np.int64).T),
        ("tn", ops.dfx_matmul_tiled_tn(qx.m, qx.exp, bits, qg.m, qg.exp,
                                       bits, interpret=True),
         (qx, qg), (((0,), (0,)), ((), ())),
         np.asarray(qx.m, np.int64).T @ np.asarray(qg.m, np.int64)),
    ]
    for name, y, (qa, qb), dn, acc in cases:
        out_exp = (qa.exp + qb.exp).astype(jnp.int32)
        loop = ref.limb_loop_matmul_ref(
            ops.split_limbs_stacked(qa.m, bits),
            ops.split_limbs_stacked(qb.m, bits), out_exp,
            dimension_numbers=dn)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(loop),
                                      err_msg=f"{name} b={bits}")
        yr = acc.astype(np.float64) * 2.0 ** float(out_exp)
        np.testing.assert_allclose(np.asarray(y, np.float64), yr,
                                   atol=np.abs(yr).max() * 2e-6 + 1e-12,
                                   err_msg=f"{name} b={bits}")


@pytest.mark.parametrize("bits", [8, 12, 16])
def test_fused_bit_exact_vs_limb_loop_batched(bits):
    """Batched NN/NT/TN (ragged E=3 stack) bit-equal to the removed loop."""
    E, M, K, N = 3, 41, 67, 29
    qx = dfx.quantize(jax.random.normal(KEY, (E, M, K)) * 1.5, bits,
                      reduce_axes=(1, 2))
    qw = dfx.quantize(jax.random.normal(jax.random.fold_in(KEY, 1),
                                        (E, K, N)) * 0.4, bits,
                      reduce_axes=(1, 2))
    qg = dfx.quantize(jax.random.normal(jax.random.fold_in(KEY, 2),
                                        (E, M, N)), bits, reduce_axes=(1, 2))

    def bexp(qa, qb):
        return (qa.exp + qb.exp).astype(jnp.int32).reshape(E, 1, 1)

    cases = [
        ("nn", ops.dfx_matmul_tiled_batched(
            qx.m, qx.exp, bits, qw.m, qw.exp, bits, interpret=True),
         (qx, qw), (((2,), (1,)), ((0,), (0,)))),
        ("nt", ops.dfx_matmul_tiled_batched_nt(
            qg.m, qg.exp, bits, qw.m, qw.exp, bits, interpret=True),
         (qg, qw), (((2,), (2,)), ((0,), (0,)))),
        ("tn", ops.dfx_matmul_tiled_batched_tn(
            qx.m, qx.exp, bits, qg.m, qg.exp, bits, interpret=True),
         (qx, qg), (((1,), (1,)), ((0,), (0,)))),
    ]
    for name, y, (qa, qb), dn in cases:
        loop = ref.limb_loop_matmul_ref(
            ops.split_limbs_stacked(qa.m, bits),
            ops.split_limbs_stacked(qb.m, bits), bexp(qa, qb),
            dimension_numbers=dn)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(loop),
                                      err_msg=f"{name} b={bits}")


#: rows a multiple of vit's 197 positions; every dim spans several 128
#: blocks, so the 128^3 tiling runs a k-grid over int32 scratch planes
BLOCK_M, BLOCK_K, BLOCK_N = 2 * 197, 384, 256


@pytest.mark.parametrize("bits", [(8, 8), (12, 8), (16, 16)],
                         ids=["1x1", "2x1", "3x3"])
def test_chosen_blocks_bit_identical_to_128_blocks(bits, monkeypatch):
    """NN, NT and TN with the chooser's blocks (the whole contraction in one
    grid step, pair products straight into the combine) give exactly the
    bits of forced 128^3 blocks: integer accumulation is exact at any
    tiling and the f32 combine runs in the same order."""
    ba, bb = bits
    la, lb = LIMBS[ba], LIMBS[bb]
    M, K, N = BLOCK_M, BLOCK_K, BLOCK_N
    qx = _quant(20, (M, K), ba, 2.0)
    qw = _quant(21, (K, N), bb, 0.3)
    qg = _quant(22, (M, N), ba)
    qgb = _quant(22, (M, N), bb)
    picks = {"nn": (ops._pick_blocks(M, N, K, la, lb), K),
             "nt": (ops._pick_blocks(M, K, N, la, lb), N),
             "tn": (ops._pick_blocks(K, N, M, la, lb, contract_rows=True),
                    ops._padded(M, True))}
    for name, (blocks, contraction) in picks.items():
        assert blocks[2] == contraction, (name, blocks)   # one k step

    def run():
        return {
            "nn": ops.dfx_matmul_tiled(qx.m, qx.exp, ba, qw.m, qw.exp, bb,
                                       interpret=True),
            "nt": ops.dfx_matmul_tiled_nt(qg.m, qg.exp, ba, qw.m, qw.exp,
                                          bb, interpret=True),
            "tn": ops.dfx_matmul_tiled_tn(qx.m, qx.exp, ba, qgb.m, qgb.exp,
                                          bb, interpret=True),
        }

    chosen = run()
    monkeypatch.setattr(ops, "_pick_blocks",
                        lambda *a, **k: (128, 128, 128))
    forced = run()
    for name in chosen:
        np.testing.assert_array_equal(np.asarray(chosen[name]),
                                      np.asarray(forced[name]),
                                      err_msg=f"{name} {bits}")


# -------------------------------------------------------------------------
# fused quantize: limb planes straight from the kernel
# -------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("shape", [(64, 96), (97, 37)])
def test_quantize_emits_limb_planes(bits, shape):
    """One quantize launch == logical quantize + XLA split, bit-equal —
    including the stochastic-rounding path."""
    x = jax.random.normal(KEY, shape) * 3
    t = dfx.quantize(x, bits)
    planes = ops.quantize_pallas(x, t.exp, bits, interpret=True,
                                 limb_planes=True)
    want = ops.split_limbs_stacked(t.m, bits)
    assert planes.dtype == jnp.int8 and planes.shape[0] == LIMBS[bits]
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(want))
    if bits < 16:    # b=16 stochastic is FMA-unstable (see grouped test)
        u = jax.random.uniform(jax.random.fold_in(KEY, 2), x.shape)
        ms = ops.quantize_pallas(x, t.exp, bits, u=u, interpret=True,
                                 limb_planes=True)
        mr = ops.split_limbs_stacked(
            ref.dfx_quantize_ref(x, t.exp, bits, u=u), bits)
        np.testing.assert_array_equal(np.asarray(ms), np.asarray(mr))


@pytest.mark.parametrize("bits", [8, 12, 16])
def test_quantize_grouped_emits_limb_planes(bits):
    E, M, N = 3, 50, 37
    x = jax.random.normal(KEY, (E, M, N)) * jnp.exp2(
        jnp.arange(E, dtype=jnp.float32) * 2 - 2).reshape(E, 1, 1)
    per = [dfx.quantize(x[e], bits) for e in range(E)]
    exp = jnp.stack([p.exp for p in per])
    planes = ops.quantize_pallas_batched(x, exp, bits, interpret=True,
                                         limb_planes=True)
    want = ops.split_limbs_stacked(jnp.stack([p.m for p in per]), bits)
    assert planes.shape == (LIMBS[bits], E, M, N)
    np.testing.assert_array_equal(np.asarray(planes), np.asarray(want))


# -------------------------------------------------------------------------
# jax.grad end-to-end vs FP32, every preset
# -------------------------------------------------------------------------

@pytest.mark.parametrize("preset", PRESETS)
def test_grad_e2e_vs_fp32_every_preset(preset):
    """The fused pallas path's gradients track exact FP32 gradients at every
    preset (quantization error only — the mapping step dominates, so looser
    thresholds at narrower widths)."""
    cfg = dataclasses.replace(QuantConfig.preset(preset), backend="pallas",
                              stochastic_grad=False)
    x = jax.random.normal(KEY, (4, 16, 48))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (48, 24)) * 0.1
    r = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 16, 24))

    g0 = jax.grad(lambda x, w: jnp.sum(
        jnp.einsum("bsk,kn->bsn", x, w) * r), argnums=(0, 1))(x, w)
    g = jax.grad(lambda x, w: jnp.sum(
        int_ops.int_linear(x, w, None, None, cfg) * r), argnums=(0, 1))(x, w)
    if not cfg.enabled:
        for a, b in zip(g, g0):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    tol = {16: 2e-3, 12: 2e-2, 10: 8e-2, 8: 0.3}[min(
        cfg.act_bits, cfg.weight_bits, cfg.grad_bits)]
    for a, b in zip(g, g0):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))
        assert rel < tol, (preset, rel, tol)
