"""Per-arch smoke tests (reduced configs, brief requirement) + consistency
properties: decode-vs-prefill equality, quantized-vs-fp32 loss proximity."""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.qconfig import QuantConfig
from repro.models import encdec, lm
from repro.models.config import SHAPES, shape_applicable

KEY = jax.random.PRNGKey(0)
Q8 = QuantConfig.int8()


def _train_batch(cfg, B=2, S=32):
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab)}
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(KEY, (B, S, cfg.d_model))
    if cfg.vlm_prefix:
        batch["patch_embeds"] = jax.random.normal(
            KEY, (B, cfg.vlm_prefix, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """One forward/backward on the reduced config: shapes + finiteness."""
    cfg = registry.get_config(arch).reduced()
    loss_fn = encdec.encdec_loss if cfg.enc_dec else lm.lm_loss
    init_fn = encdec.encdec_init if cfg.enc_dec else lm.lm_init
    params = init_fn(KEY, cfg)
    batch = _train_batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, Q8, KEY), has_aux=True)(params)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    for leaf in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf)))


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_arch_smoke_decode_step(arch):
    cfg = registry.get_config(arch).reduced()
    B, Smax = 2, 64
    tok = jnp.zeros((B, 1), jnp.int32)
    if cfg.enc_dec:
        params = encdec.encdec_init(KEY, cfg)
        enc = encdec.encode(params, jax.random.normal(KEY, (B, 16, cfg.d_model)),
                            cfg, Q8, None)
        cross = encdec.encdec_precompute_cross(params, enc, cfg, Q8)
        cache = encdec.encdec_init_cache(cfg, B, Smax)
        logits, cache = encdec.encdec_decode_step(params, tok, cache, cross,
                                                  cfg, Q8)
    else:
        params = lm.lm_init(KEY, cfg)
        cache = lm.init_cache(cfg, B, Smax)
        logits, cache = lm.lm_decode_step(params, tok, cache, cfg, Q8)
    V = lm.padded_vocab(cfg)
    assert logits.shape == (B, 1, V)
    assert np.all(np.isfinite(np.asarray(logits)))
    assert np.all(np.asarray(cache["index"]) == 1)   # per-slot for lm caches


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mixtral-8x7b",
                                  "mamba2-370m", "zamba2-2.7b",
                                  "smollm-135m", "qwen2-moe-a2.7b"])
def test_decode_matches_prefill(arch):
    """KV/SSM-cache correctness: stepping tokens one-by-one reproduces the
    full-sequence forward exactly (fp32 path)."""
    cfg = registry.get_config(arch).reduced()
    qcfg = QuantConfig.fp32()
    params = lm.lm_init(KEY, cfg)
    B, T = 2, 8
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)
    logits_pre, _ = lm.lm_prefill(params, toks, cfg, qcfg)
    cache = lm.init_cache(cfg, B, 16, dtype=jnp.float32)
    for t in range(T):
        logits_dec, cache = lm.lm_decode_step(params, toks[:, t:t + 1],
                                              cache, cfg, qcfg)
    np.testing.assert_allclose(np.asarray(logits_pre), np.asarray(logits_dec),
                               atol=2e-4)


def test_int16_loss_close_to_fp32():
    """Paper headline: 16-bit DFX matches the FP32 baseline."""
    cfg = registry.get_config("qwen1.5-0.5b").reduced()
    params = lm.lm_init(KEY, cfg)
    batch = _train_batch(cfg)
    l16, _ = lm.lm_loss(params, batch, cfg, QuantConfig.int16(), KEY)
    l0, _ = lm.lm_loss(params, batch, cfg, QuantConfig.fp32(), KEY)
    assert abs(float(l16) - float(l0)) / float(l0) < 1e-3


def test_sliding_window_masks_distant_tokens():
    """Mixtral SWA: key outside the window must not affect the output."""
    cfg = registry.get_config("mixtral-8x7b").reduced()  # window 64
    assert cfg.sliding_window == 64
    from repro.models import blocks
    B, S, H, hd = 1, 128, 2, 16
    q = jax.random.normal(KEY, (B, S, H, 1, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, hd))
    out = blocks.flash_attention(q, k, v, causal=True, window=64, chunk=32)
    k2 = k.at[:, 0].set(k[:, 0] + 100.0)       # outside window for q >= 64
    v2 = v.at[:, 0].set(v[:, 0] - 55.0)
    out2 = blocks.flash_attention(q, k2, v2, causal=True, window=64, chunk=32)
    np.testing.assert_allclose(np.asarray(out[:, 64:]),
                               np.asarray(out2[:, 64:]), atol=1e-5)
    assert float(jnp.abs(out[:, :64] - out2[:, :64]).max()) > 1e-3


def test_flash_attention_matches_dense():
    from repro.models import blocks
    B, S, H, G, hd = 2, 64, 2, 2, 16
    q = jax.random.normal(KEY, (B, S, H, G, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, hd))
    out = blocks.flash_attention(q, k, v, causal=True, chunk=16)
    # dense reference
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q / np.sqrt(hd), k)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None, None], s, -1e30)
    ref = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_capacity_drops_tokens_at_scale():
    """Above the no-drop threshold the dispatch honours the capacity factor."""
    from repro.models import blocks as B
    cfg = registry.get_config("mixtral-8x7b").reduced()
    p = B.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (8, 1024, cfg.d_model))   # T*K = 16384 > 4096
    y, (aux, _) = B.moe_apply(p, x, cfg, QuantConfig.fp32(), None)
    assert y.shape == x.shape
    assert np.isfinite(float(aux))


def test_vlm_prefix_positions_excluded_from_loss():
    cfg = registry.get_config("llava-next-mistral-7b").reduced()
    params = lm.lm_init(KEY, cfg)
    batch = _train_batch(cfg)
    # making image embeddings huge must not change loss magnitude direction
    loss1, _ = lm.lm_loss(params, batch, cfg, QuantConfig.fp32(), KEY)
    assert np.isfinite(float(loss1))


def test_long_context_shape_rules():
    ok, _ = shape_applicable(registry.get_config("mamba2-370m"), "long_500k")
    assert ok
    ok, why = shape_applicable(registry.get_config("mistral-nemo-12b"),
                               "long_500k")
    assert not ok and "sub-quadratic" in why
    ok, _ = shape_applicable(registry.get_config("zamba2-2.7b"), "long_500k")
    assert ok


def test_param_counts_match_published_scale():
    """Analytic param counts land near the published sizes."""
    expect = {"smollm-135m": 0.135e9, "qwen1.5-0.5b": 0.46e9,
              "mistral-nemo-12b": 12.2e9, "mistral-large-123b": 123e9,
              "mixtral-8x7b": 46.7e9, "mamba2-370m": 0.37e9}
    for arch, n in expect.items():
        got = registry.get_config(arch).param_count()
        assert abs(got - n) / n < 0.15, (arch, got, n)


def test_mellum_registry_config_routes_without_drops():
    """The registered Mellum2 config holds every expert as one share, so
    its expert layers route drop-free even when routing is skewed; the
    same config dispatched by capacity (``moe_shard`` None) drops pairs."""
    cfg = registry.get_config("mellum2-12b-a2.5b")
    assert cfg.moe_shard == (0, cfg.moe_experts)
    cfg = cfg.reduced()
    assert cfg.moe_shard == (0, cfg.moe_experts)
    params = lm.lm_init(KEY, cfg)
    moe = params["blocks"]["moe"]
    moe["router"] = moe["router"].at[..., 0].add(10.0)    # every token: 0
    tokens = jax.random.randint(KEY, (3, 800), 0, cfg.vocab)   # T*K > 4096
    batch = {"tokens": tokens, "labels": tokens}
    _, m = lm.lm_loss(params, batch, cfg, QuantConfig.fp32(), KEY)
    assert float(m["moe_dropped"]) == 0
    _, m = lm.lm_loss(params, batch, dataclasses.replace(cfg, moe_shard=None),
                      QuantConfig.fp32(), KEY)
    assert float(m["moe_dropped"]) > 0
