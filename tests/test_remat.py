"""The layer stacks' remat (``utils.checkpoint``): the rule that picks the
saving policy or full remat from a stack's residual bytes and the device's
memory, and the saving policy giving the numbers full remat gives."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import utils
from repro.core.qconfig import QuantConfig
from repro.models import paper_models as pm

KEY = jax.random.PRNGKey(0)
#: a device limit far above any tiny model's residuals (policy mode)
ROOMY = 1 << 40


@pytest.mark.parametrize("saved, depth, limit, mode", [
    (100, 12, 2400, "policy"),          # 1200 bytes: exactly half the limit
    (100, 12, 10_000, "policy"),        # well under half
    (100, 12, 2399, "full"),            # just over half
    (588_269_848, 12, 8_000_000_000, "full"),
    (588_269_848, 12, 16_000_000_000, "policy"),
    (100, 12, None, "full"),            # no memory reported (the CPU)
    (None, 12, None, "full"),           # nothing measured
])
def test_remat_mode_rule(saved, depth, limit, mode):
    assert utils.remat_mode(saved, depth, limit) == mode


def _bert(d=128, s=128, layers=2):
    cfg = pm.bert_config(n_layers=layers, d_model=d, n_heads=2, d_ff=4 * d,
                         vocab=256)
    params = pm.bert_init(KEY, cfg, span_head=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {"tokens": jax.random.randint(k1, (2, s), 0, cfg.vocab),
             "span_start": jax.random.randint(k2, (2,), 0, s // 2),
             "span_end": jax.random.randint(k2, (2,), s // 2, s)}
    return cfg, params, batch, pm.bert_span_loss


def _vit(d=128, layers=2):
    # 16 patches and the class token: 17 positions, unaligned like 197
    cfg = pm.vit_config(n_layers=layers, d_model=d, n_heads=2, d_ff=4 * d,
                        img=32, patch=8)
    params = pm.vit_init(KEY, cfg, num_classes=5, img=32, patch=8)
    batch = {"images": jax.random.normal(jax.random.PRNGKey(1),
                                         (2, 32, 32, 3)),
             "labels": jnp.array([1, 4])}

    def loss(p, b, c, q, k):
        return pm.vit_cls_loss(p, b, c, q, k, patch=8)
    return cfg, params, batch, loss


MODELS = {"bert": _bert, "vit": _vit}


def _loss_and_grads(monkeypatch, limit, model, backend):
    monkeypatch.setattr(utils, "device_bytes_limit", lambda: limit)
    cfg, params, batch, loss = MODELS[model]()
    q = dataclasses.replace(QuantConfig.int16(), backend=backend)
    (value, _), grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, batch, cfg, q, KEY), has_aux=True))(params)
    return value, grads, utils.remat_decisions()["blocks"]


@pytest.mark.parametrize("backend", ["sim", "pallas"])
@pytest.mark.parametrize("model", ["bert", "vit"])
def test_saving_policy_matches_full_remat_bitwise(monkeypatch, model,
                                                  backend):
    """Policy mode reads in the backward the very values full remat
    recomputes: loss and every gradient leaf are equal, bit for bit."""
    l_full, g_full, d_full = _loss_and_grads(monkeypatch, None, model,
                                             backend)
    l_pol, g_pol, d_pol = _loss_and_grads(monkeypatch, ROOMY, model,
                                          backend)
    assert (d_full.mode, d_pol.mode) == ("full", "policy")
    assert d_full.saved_bytes is None and d_pol.saved_bytes > 0
    assert d_pol.depth == 2 and d_pol.budget == ROOMY // 2
    np.testing.assert_array_equal(np.asarray(l_pol), np.asarray(l_full))
    leaves_full = jax.tree.leaves(g_full)
    assert len(leaves_full) == len(jax.tree.leaves(g_pol)) > 0
    for a, b in zip(jax.tree.leaves(g_pol), leaves_full):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _loss_residuals(monkeypatch, limit, d=128, s=128, layers=2):
    monkeypatch.setattr(utils, "device_bytes_limit", lambda: limit)
    cfg, params, batch, loss = _bert(d, s, layers)
    q = dataclasses.replace(QuantConfig.int16(), backend="pallas")
    res = jax.eval_shape(
        lambda p: jax.vjp(lambda p: loss(p, batch, cfg, q, KEY)[0], p)[1],
        params)
    return cfg, jax.tree.leaves(res)


def test_policy_keeps_one_float_tensor_of_the_mlp_width(monkeypatch):
    """Under the policy the bert stack keeps the w1 product (float32, one
    a layer) and none of the tanh GeLU's float32 intermediates of that
    width, which would hold five more."""
    cfg, res = _loss_residuals(monkeypatch, ROOMY)
    assert utils.remat_decisions()["blocks"].mode == "policy"
    wide = [r for r in res if r.dtype == jnp.float32
            and r.shape[-1:] == (cfg.d_ff,)
            and r.size == cfg.n_layers * 2 * 128 * cfg.d_ff]
    assert [r.shape for r in wide] == [(cfg.n_layers, 2, 128, cfg.d_ff)]


def test_decision_measures_one_layer_times_depth(monkeypatch):
    """The logged saved bytes are one layer's residuals: a stack twice as
    deep keeps the same bytes a layer, and the whole loss's residuals hold
    ``depth`` of them, but for a few bytes a layer of loop-invariant
    values (the key) that one layer counts and a scan does not stack."""
    _, res2 = _loss_residuals(monkeypatch, ROOMY, layers=2)
    d2 = utils.remat_decisions()["blocks"]
    _, res4 = _loss_residuals(monkeypatch, ROOMY, layers=4)
    d4 = utils.remat_decisions()["blocks"]
    assert d2.saved_bytes == d4.saved_bytes
    total = [sum(r.size * r.dtype.itemsize for r in res)
             for res in (res2, res4)]
    assert abs(total[1] - total[0] - 2 * d2.saved_bytes) <= 2 * 16


@pytest.mark.parametrize("limit", [None, 1 << 10])
def test_full_remat_traces_the_plain_checkpoint(monkeypatch, limit):
    """Full remat, whether no memory is reported or the residuals do not
    fit, traces the program a bare ``jax.checkpoint`` of each layer gives:
    the measurement leaves no trace in it."""
    cfg, params, batch, loss = _bert(d=64, s=32)
    q = QuantConfig.int16()

    def text():
        return str(jax.make_jaxpr(jax.grad(
            lambda p: loss(p, batch, cfg, q, KEY)[0]))(params))

    monkeypatch.setattr(utils, "device_bytes_limit", lambda: limit)
    got = text()
    assert utils.remat_decisions()["blocks"].mode == "full"
    monkeypatch.setattr(
        utils, "checkpoint", lambda f, stack, depth: jax.checkpoint(f))
    assert got == text()
