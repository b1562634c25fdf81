"""Whisper-style encoder–decoder backbone (the audio frontend is a stub:
the batch carries precomputed frame embeddings, ``batch["frames"]``).

Encoder: bidirectional self-attention, layernorm, GeLU MLP (integer layers).
Decoder: causal self-attention + cross-attention over encoder output.
Decode step: self-attn KV cache + precomputed cross-attention K/V.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro import utils
from repro.core import health, int_ops
from repro.core.qpolicy import QuantLike, ensure_scope, layer_groups
from repro.models import blocks
from repro.models.blocks import subkey
from repro.models.config import ArchConfig
from repro.models.lm import padded_vocab

Array = jax.Array
Params = Dict[str, Any]

# Quantization scope paths: embed, enc.{i}.*, enc_ln, dec.{i}.*, final_norm,
# lm_head — block indices carry the negative-index alias (enc.-1 = last
# encoder layer) and non-uniform per-index policies split the layer scans
# into runs of identically-resolved layers, exactly as in models/lm.py.
# Attention modules expose the fused integer-attention leaves attn.{qk,pv}
# (and xattn.{qk,pv} for cross-attention) next to the projection weights.

_ATTN = ["attn." + n for n in ("wq", "wk", "wv", "wo", "qk", "pv")]
_XATTN = ["xattn." + n for n in ("wq", "wk", "wv", "wo", "qk", "pv")]


def _enc_leaves(cfg: ArchConfig) -> list:
    return ["ln1", "ln2"] + _ATTN + blocks.mlp_leaves(cfg)


def _dec_leaves(cfg: ArchConfig) -> list:
    return _enc_leaves(cfg) + ["ln_x"] + _XATTN


def _sinusoids(length: int, channels: int) -> Array:
    t = jnp.arange(length)[:, None].astype(jnp.float32)
    inv = jnp.exp(-jnp.arange(channels // 2) * (jnp.log(10000.0) / (channels // 2 - 1)))
    ang = t * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_init(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {"ln1": blocks.norm_init(cfg),
            "attn": blocks.attention_init(ks[0], cfg),
            "ln2": blocks.norm_init(cfg),
            "mlp": blocks.mlp_init(ks[1], cfg)}


def _dec_block_init(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 3)
    return {"ln1": blocks.norm_init(cfg),
            "attn": blocks.attention_init(ks[0], cfg),
            "ln_x": blocks.norm_init(cfg),
            "xattn": blocks.attention_init(ks[1], cfg),
            "ln2": blocks.norm_init(cfg),
            "mlp": blocks.mlp_init(ks[2], cfg)}


def encdec_init(key, cfg: ArchConfig) -> Params:
    V = padded_vocab(cfg)
    ks = jax.random.split(key, 4)
    return {
        "embed": blocks._init(ks[0], (V, cfg.d_model)),
        "enc_blocks": jax.vmap(lambda k: _enc_block_init(k, cfg))(
            jax.random.split(ks[1], cfg.n_enc_layers)),
        "dec_blocks": jax.vmap(lambda k: _dec_block_init(k, cfg))(
            jax.random.split(ks[2], cfg.n_layers)),
        "enc_ln": blocks.norm_init(cfg),
        "final_norm": blocks.norm_init(cfg),
    }


def encode(params: Params, frames: Array, cfg: ArchConfig, qcfg: QuantLike,
           key) -> Array:
    """frames: (B, T, D) precomputed frame embeddings (conv frontend stub)."""
    sc = ensure_scope(qcfg)
    x = frames + _sinusoids(frames.shape[1], cfg.d_model)[None]
    x = sharding.constrain_tokens(x)

    def make_body(bsc):
        def body(x, inp):
            bp, idx = inp
            k = subkey(key, idx)
            h = blocks.norm_apply(bp["ln1"], x, cfg, bsc.child("ln1"),
                                  subkey(k, 0))
            h, _ = blocks.attention_apply(bp["attn"], h, cfg,
                                          bsc.child("attn"), subkey(k, 1),
                                          causal=False, use_rope=False)
            x = sharding.constrain_tokens(x + h)
            h = blocks.norm_apply(bp["ln2"], x, cfg, bsc.child("ln2"),
                                  subkey(k, 2))
            h = blocks.mlp_apply(bp["mlp"], h, cfg, bsc.child("mlp"),
                                 subkey(k, 3))
            return sharding.constrain_tokens(x + h), None
        return utils.checkpoint(body, stack="enc_blocks",
                                depth=cfg.n_enc_layers)

    Le = cfg.n_enc_layers
    groups = layer_groups(sc, Le, _enc_leaves(cfg), stack="enc")
    with health.suspend():     # enc-dec scans have no harvest channel
        x, _ = blocks.scan_stack(make_body, x, groups,
                                 (params["enc_blocks"], jnp.arange(Le)))
    return blocks.norm_apply(params["enc_ln"], x, cfg, sc.child("enc_ln"),
                             subkey(key, -5))


def _cross_kv(bp: Params, enc: Array, cfg: ArchConfig, qcfg: QuantLike, key):
    B, T, _ = enc.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    sc = ensure_scope(qcfg)
    k = int_ops.int_linear(enc, bp["wk"], bp.get("bk"), subkey(key, 0),
                           sc.leaf("wk"))
    v = int_ops.int_linear(enc, bp["wv"], bp.get("bv"), subkey(key, 1),
                           sc.leaf("wv"))
    return k.reshape(B, T, KV, hd), v.reshape(B, T, KV, hd)


def _decoder(params: Params, x: Array, enc: Array, cfg: ArchConfig,
             qcfg: QuantLike, key, *, self_cache=None, index=0):
    """Shared decoder stack. self_cache: (k, v) stacked (L, B, Smax, KV, hd)."""
    sc = ensure_scope(qcfg)

    def body(x, bp, idx, cache, cross, bsc):
        k = subkey(key, idx) if key is not None else None
        h = blocks.norm_apply(bp["ln1"], x, cfg, bsc.child("ln1"),
                              subkey(k, 0))
        h, ncache = blocks.attention_apply(
            bp["attn"], h, cfg, bsc.child("attn"), subkey(k, 1),
            kv_cache=cache, cache_index=index, use_rope=False)
        x = sharding.constrain_tokens(x + h)
        h = blocks.norm_apply(bp["ln_x"], x, cfg, bsc.child("ln_x"),
                              subkey(k, 2))
        if cross is None:
            cross = _cross_kv(bp["xattn"], enc, cfg, bsc.child("xattn"),
                              subkey(k, 3))
        h, _ = blocks.attention_apply(
            bp["xattn"], h, cfg, bsc.child("xattn"), subkey(k, 4),
            causal=False, kv_override=cross, use_rope=False)
        x = sharding.constrain_tokens(x + h)
        h = blocks.norm_apply(bp["ln2"], x, cfg, bsc.child("ln2"),
                              subkey(k, 5))
        h = blocks.mlp_apply(bp["mlp"], h, cfg, bsc.child("mlp"),
                             subkey(k, 6))
        x = sharding.constrain_tokens(x + h)
        return x, ncache

    L = cfg.n_layers
    groups = layer_groups(sc, L, _dec_leaves(cfg), stack="dec")
    if self_cache is None:      # teacher-forced training: cross KV on the fly
        def make_body(bsc):
            return utils.checkpoint(
                lambda c, i: (body(c, i[0], i[1], None, None, bsc)[0], None),
                stack="dec_blocks", depth=L)

        with health.suspend():     # enc-dec scans have no harvest channel
            x, _ = blocks.scan_stack(make_body, x, groups,
                                     (params["dec_blocks"], jnp.arange(L)))
        return x, None
    # decode: per-layer self cache + precomputed cross KV
    ck, cv, xk, xv = self_cache

    def make_cached_body(bsc):
        return lambda c, i: body(c, i[0], i[1], (i[2], i[3]), (i[4], i[5]),
                                 bsc)

    with health.suspend():
        return blocks.scan_stack(
            make_cached_body, x, groups,
            (params["dec_blocks"], jnp.arange(L), ck, cv, xk, xv))


def _dec_embed(params, tokens, cfg, qcfg, key, index=0):
    sc = ensure_scope(qcfg)
    x = int_ops.int_embedding(params["embed"], tokens, subkey(key, -1),
                              sc.leaf("embed"))
    pos = _sinusoids(cfg.max_position_embeddings, cfg.d_model)
    x = x + jax.lax.dynamic_slice_in_dim(pos, index, tokens.shape[1], axis=0)[None]
    return sharding.constrain_tokens(x)


def _head(params, x, cfg, qcfg, key):
    sc = ensure_scope(qcfg)
    x = blocks.norm_apply(params["final_norm"], x, cfg,
                          sc.child("final_norm"), subkey(key, -3))
    logits = int_ops.int_linear(x, params["embed"].T, None, subkey(key, -4),
                                sc.leaf("lm_head"))
    return sharding.constrain(logits, sharding.batch_axes(), None, "model")


def encdec_loss(params: Params, batch: Dict[str, Array], cfg: ArchConfig,
                qcfg: QuantLike, key) -> Tuple[Array, Dict[str, Array]]:
    """batch: frames (B, T, D) f32, tokens (B, S) int32, labels (B, S)."""
    enc = encode(params, batch["frames"], cfg, qcfg, subkey(key, 1))
    x = _dec_embed(params, batch["tokens"], cfg, qcfg, key)
    x, _ = _decoder(params, x, enc, cfg, qcfg, subkey(key, 2))
    logits = _head(params, x, cfg, qcfg, key)
    labels = batch["labels"]
    valid = labels >= 0
    lab = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)
    return loss, {"ce": loss}


def encdec_init_cache(cfg: ArchConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16):
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((L, batch, max_seq, KV, hd), dtype),
            "v": jnp.zeros((L, batch, max_seq, KV, hd), dtype),
            "index": jnp.int32(0)}


def encdec_precompute_cross(params: Params, enc: Array, cfg: ArchConfig,
                            qcfg: QuantLike):
    """Per-layer cross-attention K/V from encoder states, computed once at
    prefill so each decode step only pays the O(1) self-attn projections."""
    sc = ensure_scope(qcfg)

    def make_one(bsc):
        def one(_, bp):
            kx, vx = _cross_kv(bp["xattn"], enc, cfg, bsc.child("xattn"),
                               None)
            return None, (kx, vx)
        return one

    L = cfg.n_layers
    groups = layer_groups(sc, L, ["xattn.wk", "xattn.wv"], stack="dec")
    _, (xk, xv) = blocks.scan_stack(make_one, None, groups,
                                    params["dec_blocks"])
    return xk, xv                      # (L, B, T, KV, hd) each


def encdec_decode_step(params: Params, token: Array, cache, cross_kv,
                       cfg: ArchConfig, qcfg: QuantLike):
    """One decoder token; cross-attends over precomputed cross K/V."""
    index = cache["index"]
    xk, xv = cross_kv
    x = _dec_embed(params, token, cfg, qcfg, None, index=index)
    x, (nk, nv) = _decoder(params, x, None, cfg, qcfg, None,
                           self_cache=(cache["k"], cache["v"], xk, xv),
                           index=index)
    logits = _head(params, x, cfg, qcfg, None)
    return logits, {"k": nk, "v": nv, "index": index + 1}
