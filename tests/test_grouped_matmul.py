"""The grouped limb matmul (rows sorted by expert, group sizes known only at
run time) against the float64 product, in interpret mode: NN, NT and TN,
with an empty group and a group that holds every row, and the integer
layer built on it (``int_ops.int_grouped_linear``) on both backends.

Tolerance: the kernels sum exact int32 limb-pair products and combine them
in float32 with the group's scale, so they agree with the float64 product
to a few float32 ulps of the largest term (2e-6 relative to the largest
output); rows past the used ones come back zero.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import int_ops
from repro.core.qconfig import QuantConfig
from repro.kernels import ops as kops

TM = 16
K, N = 40, 136                 # neither a multiple of 128: padded in-call


def _layout(sizes):
    """(offsets, rows, group of each row) for groups of ``sizes`` rows, each
    padded to the row tile and at least one tile long, plus two unused
    tiles."""
    padded = [max(TM, -(-s // TM) * TM) for s in sizes]
    off = np.concatenate([[0], np.cumsum(padded)]).astype(np.int32)
    rows = int(off[-1]) + 2 * TM
    return off, rows


def _mantissas(rng, sizes, off, rows, cols, bits):
    lim = 2 ** (bits - 1) - 1
    m = np.zeros((rows, cols), np.int64)
    for g, s in enumerate(sizes):
        m[off[g]:off[g] + s] = rng.integers(-lim, lim + 1, (s, cols))
    return m


LAYOUTS = {"empty_group": [0, 37, 5], "one_group_all_rows": [0, 53, 0]}
BITS = {"3x3": (16, 16), "2x1": (12, 8)}


@pytest.mark.parametrize("bits", BITS.values(), ids=BITS.keys())
@pytest.mark.parametrize("sizes", LAYOUTS.values(), ids=LAYOUTS.keys())
def test_grouped_products_match_float64(sizes, bits):
    ba, bw = bits
    rng = np.random.default_rng(len(sizes) + ba)
    off, rows = _layout(sizes)
    G = len(sizes)
    x = _mantissas(rng, sizes, off, rows, K, ba)
    gr = _mantissas(rng, sizes, off, rows, N, ba)
    lim = 2 ** (bw - 1) - 1
    w = rng.integers(-lim, lim + 1, (G, K, N))
    xe = np.array([-20, -18, -21], np.int32)[:G]
    we = np.array([-15, -16, -14], np.int32)[:G]
    j = jnp.asarray
    y = np.asarray(kops.dfx_matmul_grouped(j(x), j(xe), ba, j(w), j(we), bw,
                                           j(off), TM))
    dx = np.asarray(kops.dfx_matmul_grouped_nt(j(gr), j(xe), ba, j(w), j(we),
                                               bw, j(off), TM))
    dw = np.asarray(kops.dfx_matmul_grouped_tn(j(x), j(xe), ba, j(gr), j(we),
                                               ba, j(off), TM))
    ry, rdx = np.zeros((rows, N)), np.zeros((rows, K))
    rdw = np.zeros((G, K, N))
    for g in range(G):
        r = slice(off[g], off[g + 1])
        s = 2.0 ** (xe[g] + we[g])
        ry[r] = x[r].astype(np.float64) @ w[g] * s
        rdx[r] = gr[r].astype(np.float64) @ w[g].T * s
        rdw[g] = x[r].T.astype(np.float64) @ gr[r] * s
    for got, want in ((y, ry), (dx, rdx), (dw, rdw)):
        scale = max(np.max(np.abs(want)), 1e-30)
        assert np.max(np.abs(got - want)) <= 2e-6 * scale
    assert not y[off[-1]:].any() and not dx[off[-1]:].any()
    for g, s in enumerate(sizes):
        if s == 0:
            assert not dw[g].any()


def test_grouped_linear_backends_agree():
    """``int_grouped_linear`` forward and both gradients: the pallas kernels
    and the sim path quantize at the same per-group exponents, so they
    differ only by float32 accumulation order (1e-5 of each output's
    largest entry)."""
    sizes = [9, 0, 30, 17]
    off, rows = _layout(sizes)
    rng = np.random.default_rng(3)
    x = np.zeros((rows, K), np.float32)
    for g, s in enumerate(sizes):
        x[off[g]:off[g] + s] = rng.normal(size=(s, K)) * (g + 1)
    w = (rng.normal(size=(len(sizes), K, N)) * 0.02).astype(np.float32)
    ct = jnp.asarray(rng.normal(size=(rows, N)), jnp.float32)
    gid = int_ops.row_groups(jnp.asarray(off), rows)
    ct = jnp.where((gid < len(sizes))[:, None], ct, 0.0)
    outs = []
    for backend in ("sim", "pallas"):
        cfg = dataclasses.replace(QuantConfig.int16(), backend=backend,
                                  stochastic_grad=False)
        y, vjp = jax.vjp(lambda x, w: int_ops.int_grouped_linear(
            x, w, jnp.asarray(off), None, cfg, TM), jnp.asarray(x),
            jnp.asarray(w))
        outs.append((y, *vjp(ct)))
    for a, b in zip(*outs):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-5 * max(np.max(np.abs(b)), 1e-30)


def test_row_tile_follows_the_mean_group():
    assert kops.group_row_tile(2048) == 256
    assert kops.group_row_tile(129) == 256
    assert kops.group_row_tile(100) == 104
    assert kops.group_row_tile(1) == 8
