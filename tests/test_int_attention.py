"""Fused integer flash attention: backend parity, f64 oracle, e2e grads.

Both backends share every quantization point (q/k/v mantissas, P at the
static ``-(p_bits-1)`` exponent against the running max, dS at the
norm-derived exponent), so sim-vs-pallas divergence is bounded only by f32
accumulation rounding.  The f64 oracle (kernels/ref.py) uses the GLOBAL row
max, which agrees with the online running max whenever Sk fits one 128-wide
KV block — the oracle sweeps therefore stay at Sk <= 128 and assert tight
agreement on deliberately odd shapes (GQA G > 1, sliding window, per-row
offsets, ragged extents).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dfx, int_ops
from repro.core.qconfig import PRESETS, QuantConfig
from repro.core.qpolicy import QuantPolicy, ensure_scope, rule
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import blocks

KEY = jax.random.PRNGKey(7)


def _pair(preset):
    sim = dataclasses.replace(QuantConfig.preset(preset),
                              stochastic_grad=False, backend="sim")
    return sim, dataclasses.replace(sim, backend="pallas")


def _qkv(B=2, Sq=24, Sk=None, KV=2, G=2, hd=32, key=KEY):
    Sk = Sq if Sk is None else Sk
    q = jax.random.normal(key, (B, Sq, KV, G, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sk, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, KV, hd))
    return q, k, v


def _run(cfg, q, k, v, off=0, causal=True, window=None):
    def f(q, k, v):
        o = int_ops.int_attention(q, k, v, jnp.asarray(off), None,
                                  cfg, cfg, causal, window)
        return jnp.sum(o * o), o
    (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    return o, grads


# =========================================================================
# sim vs pallas parity, every preset
# =========================================================================

@pytest.mark.parametrize("preset", PRESETS)
def test_fwd_bwd_parity(preset):
    sim, pal = _pair(preset)
    if not sim.enabled:
        pytest.skip("fp32 preset never reaches int_attention (callers gate "
                    "on leaf.enabled)")
    q, k, v = _qkv()
    o_s, g_s = _run(sim, q, k, v)
    o_p, g_p = _run(pal, q, k, v)
    scale = float(jnp.abs(o_s).max()) + 1e-12
    assert float(jnp.abs(o_s - o_p).max()) / scale < 1e-4, preset
    # grads tolerate one ULP of the dS integer grid: the backends order the
    # f32 p/ds accumulations differently, which can flip a round-to-nearest
    for name, a, b in zip("qkv", g_s, g_p):
        gs = float(jnp.abs(a).max()) + 1e-12
        assert float(jnp.abs(a - b).max()) / gs < 2e-3, (preset, name)


@pytest.mark.parametrize("preset", ("int8", "int16"))
def test_parity_multiblock_and_window(preset):
    """Sk spanning several 128-wide KV blocks + a sliding window: the sim
    path must mirror the kernel's per-block running-max P quantization."""
    sim, pal = _pair(preset)
    q, k, v = _qkv(B=1, Sq=16, Sk=300, KV=2, G=1, hd=16)
    for window in (None, 64):
        o_s, _ = _run(sim, q, k, v, off=284, window=window)
        o_p, _ = _run(pal, q, k, v, off=284, window=window)
        scale = float(jnp.abs(o_s).max()) + 1e-12
        assert float(jnp.abs(o_s - o_p).max()) / scale < 1e-4, window


# =========================================================================
# kernel vs f64 oracle, odd shapes
# =========================================================================

_ORACLE_CASES = [
    # (B, Sq, Sk, KV, G, hd, causal, window, off)
    (2, 13, 77, 2, 3, 24, True, None, 64),        # GQA G=3, ragged extents
    (1, 32, 32, 2, 1, 16, True, 9, 0),            # sliding window
    (3, 5, 40, 1, 2, 8, True, None, (0, 7, 19)),  # per-row offsets (prefill)
    (1, 9, 33, 2, 2, 128, False, None, 0),        # bidirectional, full hd
]


@pytest.mark.parametrize("case", _ORACLE_CASES)
def test_fwd_matches_f64_oracle(case):
    B, Sq, Sk, KV, G, hd, causal, window, off = case
    cfg = dataclasses.replace(QuantConfig.preset("int8"),
                              stochastic_grad=False, backend="pallas",
                              warn_stability=False)
    q, k, v = _qkv(B=B, Sq=Sq, Sk=Sk, KV=KV, G=G, hd=hd)
    off_v = np.broadcast_to(np.asarray(off, np.int64), (B,))
    o = int_ops.int_attention(q, k, v, jnp.asarray(np.asarray(off)), None,
                              cfg, cfg, causal, window)
    qq, qk, qv = (dfx.quantize(t, b) for t, b in
                  ((q, cfg.act_bits), (k, cfg.act_bits), (v, cfg.act_bits)))
    o_ref, _ = kref.int_attention_fwd_ref(
        np.asarray(qq.m, np.float64), float(qq.exp),
        np.asarray(qk.m, np.float64), float(qk.exp),
        np.asarray(qv.m, np.float64), float(qv.exp),
        cfg.act_bits, off_v, causal=causal, window=window)
    scale = float(np.abs(o_ref).max()) + 1e-12
    assert float(np.abs(np.asarray(o, np.float64) - o_ref).max()) / scale \
        < 1e-5, case


def _bwd_vs_oracle(B, Sq, Sk, KV, G, hd, off, causal):
    """The kernels' (dq, dk, dv) and the f64 oracle's: a causal call with
    each (bq, bk) tile's dS exponent, any other with the norm bound's."""
    cfg = dataclasses.replace(QuantConfig.preset("int8"),
                              stochastic_grad=False, backend="pallas",
                              warn_stability=False)
    q, k, v = _qkv(B=B, Sq=Sq, Sk=Sk, KV=KV, G=G, hd=hd)

    def f(q, k, v):
        return int_ops.int_attention(q, k, v, jnp.asarray(off), None,
                                     cfg, cfg, causal, None)

    o, vjp = jax.vjp(f, q, k, v)
    g = jax.random.normal(jax.random.fold_in(KEY, 9), o.shape)
    dq, dk, dv = vjp(g)

    bits = cfg.act_bits
    qq, qk, qv = (dfx.quantize(t, bits) for t in (q, k, v))
    qg = dfx.quantize(g, cfg.grad_bits)
    off_v = np.full((B,), off, np.int64)
    _, lse = kref.int_attention_fwd_ref(
        np.asarray(qq.m, np.float64), float(qq.exp),
        np.asarray(qk.m, np.float64), float(qk.exp),
        np.asarray(qv.m, np.float64), float(qv.exp),
        bits, off_v, causal=causal)
    delta = np.sum(np.asarray(g, np.float64) * np.asarray(o, np.float64),
                   axis=-1)
    ds_exp, tile = None, None
    if causal:
        bq, _, bk, _, _ = kops._attn_dims(Sq, Sk, hd)
        tile = (bq, bk)
    else:
        ds_exp = int(int_ops._ds_exp(int_ops._max_row_norm(g),
                                     int_ops._max_row_norm(v),
                                     cfg.grad_bits))
    ref = kref.int_attention_bwd_ref(
        np.asarray(qq.m, np.float64), float(qq.exp),
        np.asarray(qk.m, np.float64), float(qk.exp),
        np.asarray(qv.m, np.float64), float(qv.exp),
        np.asarray(qg.m, np.float64), float(qg.exp),
        lse, delta, ds_exp, bits, cfg.grad_bits, off_v, causal=causal,
        tile=tile)
    return (dq, dk, dv), ref


def test_bwd_matches_f64_oracle():
    (dq, dk, dv), (dq_r, dk_r, dv_r) = _bwd_vs_oracle(
        2, 13, 48, 2, 3, 24, 32, True)
    for name, got, ref in (("dq", dq, dq_r), ("dk", dk, dk_r),
                           ("dv", dv, dv_r)):
        scale = float(np.abs(ref).max()) + 1e-12
        assert float(np.abs(np.asarray(got, np.float64) - ref).max()) \
            / scale < 1e-4, name


def test_bwd_ds_tiles_match_f64_oracle():
    """dQ and dK of a causal call over three q tiles (Sq = 300: 128-row
    tiles in the kernels' layout, the last ragged), each tile's dS at its
    own exponent.  dV does not use dS; over 300 rows an 8-bit P mantissa
    can round across a half between the oracle's f64 and the kernel's f32
    ``lse``, so it is held to the single-tile case above."""
    (dq, dk, _), (dq_r, dk_r, _) = _bwd_vs_oracle(
        2, 300, 48, 2, 3, 24, 32, True)
    for name, got, ref in (("dq", dq, dq_r), ("dk", dk, dk_r)):
        scale = float(np.abs(ref).max()) + 1e-12
        assert float(np.abs(np.asarray(got, np.float64) - ref).max()) \
            / scale < 1e-4, name


def test_bwd_matches_f64_oracle_bidirectional():
    """Neither causal nor windowed (the encoders): the norm bound's one
    dS exponent."""
    got, ref = _bwd_vs_oracle(2, 13, 48, 2, 3, 24, 0, False)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        scale = float(np.abs(b).max()) + 1e-12
        assert float(np.abs(np.asarray(a, np.float64) - b).max()) \
            / scale < 1e-4, name


# =========================================================================
# end-to-end gradients vs the FP32 flash reference
# =========================================================================

def test_grad_e2e_vs_fp32_flash():
    q, k, v = _qkv(B=2, Sq=20, KV=2, G=2, hd=24)

    def ref_loss(q, k, v):
        return jnp.sum(blocks.flash_attention(q, k, v, causal=True) ** 2)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    prev = None
    for preset in ("int8", "int12", "int16"):
        sim, _ = _pair(preset)
        _, g = _run(sim, q, k, v)
        rels = [float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-12)
                for a, b in zip(g, g_ref)]
        if prev is not None:       # quantization error shrinks with width
            assert max(rels) < max(prev), (preset, rels, prev)
        prev = rels
    assert max(prev) < 5e-3        # int16 lands close to the FP32 grads


# =========================================================================
# decode (Sq=1) through the same entry point
# =========================================================================

def test_decode_matches_training_row():
    """Sq=1 with a padded cache and q_offset must reproduce the last row of
    the training-shape call — one entry point, three shapes."""
    B, S, KV, G, hd, Smax = 2, 17, 2, 2, 16, 40
    cfg = dataclasses.replace(QuantConfig.preset("int8"),
                              stochastic_grad=False, backend="pallas",
                              warn_stability=False)
    q, k, v = _qkv(B=B, Sq=S, KV=KV, G=G, hd=hd)
    # pin the global max-abs of q into the last row so the decode-step
    # quantization (which only sees that row) picks the same exponent
    q = q.at[:, -1, 0, 0, 0].set(float(jnp.abs(q).max()) * 1.5)
    o_full = int_ops.int_attention(q, k, v, jnp.asarray(0), None,
                                   cfg, cfg, True, None)
    kc = jnp.zeros((B, Smax, KV, hd)).at[:, :S].set(k)
    vc = jnp.zeros((B, Smax, KV, hd)).at[:, :S].set(v)
    o_dec = int_ops.int_attention(q[:, -1:], kc, vc, jnp.asarray(S - 1),
                                  None, cfg, cfg, True, None)
    np.testing.assert_allclose(np.asarray(o_dec[:, 0]),
                               np.asarray(o_full[:, -1]), atol=1e-5)


# =========================================================================
# policy scoping: attn.qk / attn.pv leaves
# =========================================================================

def test_attention_bits_tunable_per_scope():
    """The attn.qk leaf resolves per call site: overriding it changes the
    attention output; disabling it routes the module to the FP32 path."""
    from repro.models.config import ArchConfig
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    params = blocks.attention_init(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 8, 32))
    base = dataclasses.replace(QuantConfig.preset("int8"),
                               stochastic_grad=False, backend="sim",
                               warn_stability=False)

    def apply(policy):
        sc = ensure_scope(policy).child("blocks").child("0").child("attn")
        return blocks.attention_apply(params, x, cfg, sc, None)[0]

    y8 = apply(QuantPolicy(base=base))
    y16 = apply(QuantPolicy(base=base,
                            rules=(rule("*.attn.qk", act_bits=16),)))
    yfp = apply(QuantPolicy(base=base,
                            rules=(rule("*.attn.qk", enabled=False),)))
    assert float(jnp.abs(y8 - y16).max()) > 0
    assert float(jnp.abs(y8 - yfp).max()) > 0
    # the fp-attention variant still quantizes the projections
    assert float(jnp.abs(y16 - yfp).max()) > 0


# =========================================================================
# satellite: ragged final KV chunk in the XLA flash path
# =========================================================================

@pytest.mark.parametrize("Sk", (1500, 130))
def test_flash_attention_ragged_sk(Sk):
    """flash_attention used to assert Sk % chunk == 0; ragged key lengths
    (e.g. Sk=1500 against the 1024-wide chunk) must pad and mask."""
    B, Sq, Hkv, G, hd = 1, 8, 2, 1, 16
    key = jax.random.fold_in(KEY, Sk)
    q = jax.random.normal(key, (B, Sq, Hkv, G, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sk, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, Hkv, hd))
    off = Sk - Sq
    got = blocks.flash_attention(q, k, v, causal=True, q_offset=off,
                                 chunk=128)
    # direct masked softmax reference
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q / jnp.sqrt(jnp.float32(hd)),
                   k.astype(jnp.float32))
    qpos = off + jnp.arange(Sq)
    mask = jnp.arange(Sk)[None, :] <= qpos[:, None]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhgqk,bkhd->bhgqd", p,
                     v.astype(jnp.float32)).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
