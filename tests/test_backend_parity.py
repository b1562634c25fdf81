"""sim-vs-pallas backend parity for the integer layers, all presets.

The two backends quantize identically (RN mantissas are bit-equal); the
contraction differs — XLA float accumulation (sim) vs bit-exact int32 limb
accumulation with an f32 cross-limb combine (pallas). Agreement is therefore
bounded by f32 accumulation rounding, far inside the Proposition 1 mapping
error ``2^(e_scale - b + 1)`` — both bounds are asserted.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dfx, int_ops
from repro.core.qconfig import PRESETS, QuantConfig
from repro.analysis import count_pallas_calls

KEY = jax.random.PRNGKey(0)


def _pair(preset):
    # backend pinned explicitly: the suite may run under REPRO_BACKEND=pallas
    # (the CI backend matrix), which changes the *default* backend.
    sim = dataclasses.replace(QuantConfig.preset(preset),
                              stochastic_grad=False, backend="sim")
    return sim, dataclasses.replace(sim, backend="pallas")


def _assert_close(a, b, bits, context):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    diff = np.abs(a - b).max()
    scale = np.abs(a).max() + 1e-12
    assert diff / scale < 1e-4, (context, diff, scale)
    # Proposition 1: the per-element mapping step of the reference output at
    # the layer's bit-width upper-bounds any acceptable backend divergence.
    bound = float(dfx.error_bound(jnp.asarray(a, jnp.float32), bits))
    assert diff <= max(bound, scale * 1e-4), (context, diff, bound)


@pytest.mark.parametrize("preset", PRESETS)
def test_linear_fwd_parity(preset):
    sim, pal = _pair(preset)
    x = jax.random.normal(KEY, (4, 16, 64))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 32)) * 0.1
    b = jnp.ones((32,)) * 0.01
    ys = int_ops.int_linear(x, w, b, None, sim)
    yp = int_ops.int_linear(x, w, b, None, pal)
    if not sim.enabled:
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))
        return
    _assert_close(ys, yp, min(sim.act_bits, sim.weight_bits), preset)


@pytest.mark.parametrize("preset", PRESETS)
def test_linear_bwd_parity(preset):
    sim, pal = _pair(preset)
    x = jax.random.normal(KEY, (3, 8, 64))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 48)) * 0.1
    b = jnp.zeros((48,))
    r = jax.random.normal(jax.random.fold_in(KEY, 2), (3, 8, 48))

    def loss(x, w, b, c):
        return jnp.sum(int_ops.int_linear(x, w, b, None, c) * r)

    gs = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, sim)
    gp = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, pal)
    if not sim.enabled:
        for a, bb in zip(gs, gp):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
        return
    bits = min(sim.grad_bits, sim.weight_bits, sim.act_bits)
    for a, bb in zip(gs, gp):
        _assert_close(a, bb, bits, preset)


@pytest.mark.parametrize("E", [1, 8])
@pytest.mark.parametrize("preset", PRESETS)
def test_batched_linear_parity(preset, E):
    """Every preset at E in {1, 8}: the batched pallas path (one kernel per
    limb pair, expert axis on the grid) matches sim inside the Prop. 1 bound
    for forward and both backward products."""
    sim, pal = _pair(preset)
    mags = jnp.exp2(jnp.linspace(-3.0, 3.0, E)).reshape(E, 1, 1)
    x = jax.random.normal(KEY, (E, 8, 32)) * mags
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (E, 32, 16)) * 0.2
    ys = int_ops.int_batched_linear(x, w, None, sim)
    yp = int_ops.int_batched_linear(x, w, None, pal)
    if not sim.enabled:
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))
        return
    _assert_close(ys, yp, min(sim.act_bits, sim.weight_bits), (preset, E))

    def loss(x, w, c):
        return jnp.sum(int_ops.int_batched_linear(x, w, None, c) ** 2)

    gs = jax.grad(loss, argnums=(0, 1))(x, w, sim)
    gp = jax.grad(loss, argnums=(0, 1))(x, w, pal)
    bits = min(sim.grad_bits, sim.weight_bits, sim.act_bits)
    for a, bb in zip(gs, gp):
        _assert_close(a, bb, bits, (preset, E))


def test_batched_linear_grad_e2e_vs_fp32():
    """jax.grad end-to-end through int_batched_linear at E > 1: both
    backends' gradients track the exact FP32 einsum gradients (the batched
    analogue of the unbatched grad-level backend check)."""
    E, C, K, N = 4, 8, 32, 16
    x = jax.random.normal(KEY, (E, C, K))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (E, K, N)) * 0.2
    r = jax.random.normal(jax.random.fold_in(KEY, 4), (E, C, N))

    g0 = jax.grad(lambda x, w: jnp.sum(
        jnp.einsum("eck,ekn->ecn", x, w) * r), argnums=(0, 1))(x, w)
    sim, pal = _pair("int16")
    for cfg in (sim, pal):
        g = jax.grad(lambda x, w, c=cfg: jnp.sum(
            int_ops.int_batched_linear(x, w, None, c) * r),
            argnums=(0, 1))(x, w)
        for a, b in zip(g, g0):
            rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))
            assert rel < 1e-3, (cfg.backend, rel)


@pytest.mark.parametrize("preset", ["int8", "int16"])
def test_batched_dispatch_count_independent_of_experts(preset):
    """The acceptance property of the batched kernels: the number of
    pallas_call dispatches traced for int_batched_linear is the same at
    E=1 and E=8 (one batched launch per direction covering every expert AND
    limb pair, plus the grouped quantizations) — no Python loop over the
    expert axis, and no per-limb-pair dispatch loop."""
    _, pal = _pair(preset)

    def counts(E):
        x = jax.random.normal(KEY, (E, 8, 32))
        w = jax.random.normal(jax.random.fold_in(KEY, 1), (E, 32, 16))
        fwd = lambda x, w: int_ops.int_batched_linear(x, w, None, pal)
        loss = lambda x, w: jnp.sum(fwd(x, w) ** 2)
        return (count_pallas_calls(jax.make_jaxpr(fwd)(x, w)),
                count_pallas_calls(
                    jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)))

    assert counts(1) == counts(8)
    nf, nb = counts(8)
    # quantize x, quantize w, ONE fused matmul launch — at every bit-width
    assert nf == 3
    # + quantize g, one NT launch (dX), one TN launch (dW)
    assert nb == 6


@pytest.mark.parametrize("preset", ["int16", "int8"])
def test_embedding_parity(preset):
    """Bugfix regression: int_embedding used to bypass cfg.backend and
    always quantize through the sim path."""
    sim, pal = _pair(preset)
    tbl = jax.random.normal(KEY, (100, 32))
    ids = jnp.array([[1, 2, 3], [4, 5, 1]])
    ys = int_ops.int_embedding(tbl, ids, None, sim)
    yp = int_ops.int_embedding(tbl, ids, None, pal)
    # both backends use RN quantization of the same table: bit-equal
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))
    gs = jax.grad(lambda t: jnp.sum(
        int_ops.int_embedding(t, ids, None, sim) ** 2))(tbl)
    gp = jax.grad(lambda t: jnp.sum(
        int_ops.int_embedding(t, ids, None, pal) ** 2))(tbl)
    _assert_close(gs, gp, min(sim.grad_bits, sim.weight_bits), preset)


@pytest.mark.parametrize("preset", ["int16", "int8"])
def test_dwconv_parity(preset):
    """Bugfix regression: int_conv1d_depthwise used to bypass cfg.backend."""
    sim, pal = _pair(preset)
    x = jax.random.normal(KEY, (2, 10, 8))
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (4, 8))
    ys = int_ops.int_conv1d_depthwise(x, w, None, sim)
    yp = int_ops.int_conv1d_depthwise(x, w, None, pal)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))

    def loss(x, w, c):
        return jnp.sum(int_ops.int_conv1d_depthwise(x, w, None, c) ** 2)

    gs = jax.grad(loss, argnums=(0, 1))(x, w, sim)
    gp = jax.grad(loss, argnums=(0, 1))(x, w, pal)
    for a, bb in zip(gs, gp):
        _assert_close(a, bb, min(sim.grad_bits, sim.act_bits), preset)


@pytest.mark.parametrize("backend", ["sim", "pallas"])
def test_batched_linear_stochastic_fwd(backend):
    """Bugfix regression: int_batched_linear used to ignore
    cfg.stochastic_fwd (no key split, RN activations on both backends)."""
    cfg = dataclasses.replace(QuantConfig.int8(), backend=backend,
                              stochastic_fwd=True, stochastic_grad=False)
    x = jax.random.normal(KEY, (2, 8, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 32, 16)) * 0.2
    y1 = int_ops.int_batched_linear(x, w, jax.random.fold_in(KEY, 10), cfg)
    y2 = int_ops.int_batched_linear(x, w, jax.random.fold_in(KEY, 11), cfg)
    y1b = int_ops.int_batched_linear(x, w, jax.random.fold_in(KEY, 10), cfg)
    assert float(jnp.abs(y1 - y2).max()) > 0.0       # noise actually applied
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y1b))
    # without a key the forward stays deterministic RN (serve-time contract)
    rn = dataclasses.replace(cfg, stochastic_fwd=False)
    np.testing.assert_array_equal(
        np.asarray(int_ops.int_batched_linear(x, w, None, cfg)),
        np.asarray(int_ops.int_batched_linear(x, w, None, rn)))


def test_batched_linear_stochastic_fwd_cross_backend():
    """Same key => both backends draw the identical activation noise; the
    outputs differ only by accumulation rounding."""
    k = jax.random.fold_in(KEY, 12)
    x = jax.random.normal(KEY, (2, 8, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 32, 16)) * 0.2
    outs = []
    for backend in ("sim", "pallas"):
        cfg = dataclasses.replace(QuantConfig.int8(), backend=backend,
                                  stochastic_fwd=True, stochastic_grad=False)
        outs.append(int_ops.int_batched_linear(x, w, k, cfg))
    _assert_close(outs[0], outs[1], 8, "stochastic_fwd")


@pytest.mark.parametrize("preset", PRESETS)
def test_layernorm_parity(preset):
    sim, pal = _pair(preset)
    x = jax.random.normal(KEY, (4, 8, 64)) * 2.0
    gm = jnp.ones((64,)) * 1.3
    bt = jnp.zeros((64,)) + 0.2
    r = jax.random.normal(jax.random.fold_in(KEY, 9), x.shape)
    ys = int_ops.int_layernorm(x, gm, bt, None, sim)
    yp = int_ops.int_layernorm(x, gm, bt, None, pal)
    if not sim.enabled:
        np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))
        return
    # kernel uses the one-pass E[x²]-E[x]² variance; slightly looser than
    # the matmul parity but still far below the Prop. 1 step
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yp),
                               rtol=2e-4, atol=2e-4)

    def loss(x, gm, c):
        return jnp.sum(int_ops.int_layernorm(x, gm, bt, None, c) * r)

    gs = jax.grad(loss, argnums=(0, 1))(x, gm, sim)
    gp = jax.grad(loss, argnums=(0, 1))(x, gm, pal)
    for a, bb in zip(gs, gp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-3, atol=2e-3)


def test_stochastic_grad_unbiased_on_pallas():
    """Assumption 2 plumbing: the pallas backend draws the stochastic-
    rounding noise from the layer key — different keys give different
    gradients, same key gives identical gradients."""
    cfg = dataclasses.replace(QuantConfig.int8(), backend="pallas",
                              stochastic_grad=True)
    x = jax.random.normal(KEY, (16, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 6), (32, 8))

    def g(k):
        return jax.grad(lambda w: jnp.sum(jnp.tanh(
            int_ops.int_linear(x, w, None, k, cfg))))(w)

    g1 = g(jax.random.fold_in(KEY, 7))
    g2 = g(jax.random.fold_in(KEY, 8))
    g1b = g(jax.random.fold_in(KEY, 7))
    assert float(jnp.abs(g1 - g2).max()) > 0.0
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g1b))


def test_backend_validation():
    with pytest.raises(ValueError):
        QuantConfig(backend="cuda")


def test_acc_dtype_escalation():
    """The dead-branch fix: inexact sim configurations must not silently
    report f32-exactness."""
    assert dfx.sim_accum_exact(8, 8, 128)            # 21 bits: exact
    assert not dfx.sim_accum_exact(16, 16, 128)      # 37 bits: inexact
    assert dfx.acc_dtype(8, 8, 128) == jnp.float32
    with pytest.warns(RuntimeWarning, match="accumulator bits"):
        dfx._INEXACT_WARNED.clear()
        assert dfx.acc_dtype(16, 16, 1 << 20) == jnp.float32
