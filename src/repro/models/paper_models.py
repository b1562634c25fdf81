"""The paper's own models: BERT-style encoder and ViT classifier.

Used by the reproduction experiments (GLUE/SQuAD/CIFAR proxies in
``benchmarks/``) at reduced scale. Every linear / layer-norm / embedding /
patch-conv goes through the integer layers; softmax/GeLU/pooler-tanh are the
paper's kept ops — FP32 by default, the iapprox fixed-point forms under
``kept_ops="integer"`` (DESIGN.md §10).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import utils
from repro.core import int_ops
from repro.core.qpolicy import QuantLike, ensure_scope, layer_groups
from repro.models import blocks
from repro.models.blocks import subkey
from repro.models.config import ArchConfig

Array = jax.Array
Params = Dict[str, Any]

# Quantization scope paths (resolved against a QuantPolicy at trace time):
#   BERT: embed, type_embed, embed_ln, blocks.{i}.{ln1, attn.*, ln2,
#         mlp.{w1,w2,act}}, pooler (+ pooler.act kept-ops leaf), head,
#         span_head
#   ViT:  patch_embed, blocks.{i}.*, final_ln, head
# Block scopes carry the negative-index alias (blocks.-1 = last layer); a
# policy resolving differently across block indices splits the encoder scan
# into runs of identically-resolved layers (see models/lm.py).
#
# Trace scopes (``jax.named_scope``, read from a profile): embed (with
# embed_ln) / patch_embed, blocks/{ln1, attn, ln2, mlp} (models/blocks.py),
# final_ln, span_head / cls_head, loss.

_ENC_BLOCK_LEAVES = (["ln1", "ln2"]
                     + ["attn." + n
                        for n in ("wq", "wk", "wv", "wo", "qk", "pv")]
                     + ["mlp.w1", "mlp.w2", "mlp.act"])


def bert_config(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
                vocab=30522, name="bert-base") -> ArchConfig:
    return ArchConfig(name=name, family="encoder", n_layers=n_layers,
                      d_model=d_model, n_heads=n_heads, n_kv_heads=n_heads,
                      d_ff=d_ff, vocab=vocab, norm="layernorm", act="gelu",
                      max_position_embeddings=512)


def vit_config(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
               img=224, patch=16, name="vit-base") -> ArchConfig:
    cfg = ArchConfig(name=name, family="encoder", n_layers=n_layers,
                     d_model=d_model, n_heads=n_heads, n_kv_heads=n_heads,
                     d_ff=d_ff, vocab=0, norm="layernorm", act="gelu",
                     max_position_embeddings=(img // patch) ** 2 + 1)
    object.__setattr__(cfg, "frontend", "vision_stub")
    return cfg


def _enc_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": blocks.norm_init(cfg),
            "attn": blocks.attention_init(ks[0], cfg),
            "ln2": blocks.norm_init(cfg),
            "mlp": blocks.mlp_init(ks[1], cfg)}


def _encoder(params, x, cfg, qcfg, key):
    sc = ensure_scope(qcfg)

    def make_body(bsc):
        def body(x, inp):
            bp, idx = inp
            k = subkey(key, idx)
            h = blocks.norm_apply(bp["ln1"], x, cfg, bsc.child("ln1"),
                                  subkey(k, 0))
            h, _ = blocks.attention_apply(bp["attn"], h, cfg,
                                          bsc.child("attn"), subkey(k, 1),
                                          causal=False, use_rope=False)
            x = x + h
            h = blocks.norm_apply(bp["ln2"], x, cfg, bsc.child("ln2"),
                                  subkey(k, 2))
            h = blocks.mlp_apply(bp["mlp"], h, cfg, bsc.child("mlp"),
                                 subkey(k, 3))
            return x + h, None
        return utils.checkpoint(body, stack="blocks", depth=cfg.n_layers)

    L = cfg.n_layers
    groups = layer_groups(sc, L, _ENC_BLOCK_LEAVES)
    x, _ = blocks.scan_stack(make_body, x, groups,
                             (params["blocks"], jnp.arange(L)))
    return x


# ===================== BERT =====================

def bert_init(key, cfg: ArchConfig, num_labels: int = 2,
              span_head: bool = False) -> Params:
    ks = jax.random.split(key, 7)
    p = {
        "embed": blocks._init(ks[0], (cfg.vocab, cfg.d_model)),
        "pos_embed": blocks._init(ks[1], (cfg.max_position_embeddings, cfg.d_model)),
        "type_embed": blocks._init(ks[2], (2, cfg.d_model)),
        "embed_ln": blocks.norm_init(cfg),
        "blocks": jax.vmap(lambda k: _enc_block_init(k, cfg))(
            jax.random.split(ks[3], cfg.n_layers)),
        "pooler": blocks._init(ks[5], (cfg.d_model, cfg.d_model)),
        "pooler_b": jnp.zeros((cfg.d_model,)),
        "head": blocks._init(ks[4], (cfg.d_model, num_labels)),
        "head_b": jnp.zeros((num_labels,)),
    }
    if span_head:
        p["span"] = blocks._init(ks[6], (cfg.d_model, 2))
    return p


def bert_apply(params: Params, tokens: Array, cfg: ArchConfig,
               qcfg: QuantLike, key, segment: Optional[Array] = None,
               pool: bool = True) -> Array:
    B, S = tokens.shape
    sc = ensure_scope(qcfg)
    with jax.named_scope("embed"):
        x = int_ops.int_embedding(params["embed"], tokens, subkey(key, -1),
                                  sc.leaf("embed"))
        x = x + params["pos_embed"][None, :S]
        if segment is not None:
            x = x + int_ops.int_embedding(params["type_embed"], segment,
                                          subkey(key, -2),
                                          sc.leaf("type_embed"))
        x = blocks.norm_apply(params["embed_ln"], x, cfg,
                              sc.child("embed_ln"), subkey(key, -3))
    x = _encoder(params, x, cfg, sc, key)
    if pool:
        with jax.named_scope("cls_head"):
            # BERT pooler: dense + tanh on the CLS token; the tanh is a
            # kept op
            cls = int_ops.int_linear(x[:, 0], params["pooler"],
                                     params["pooler_b"], subkey(key, -5),
                                     sc.leaf("pooler"))
            cls = int_ops.int_activation(
                cls, sc.child("pooler").leaf("act"), "tanh")
            return int_ops.int_linear(cls, params["head"], params["head_b"],
                                      subkey(key, -4), sc.leaf("head"))
    with jax.named_scope("span_head"):
        return int_ops.int_linear(x, params["span"], None, subkey(key, -4),
                                  sc.leaf("span_head"))


def bert_cls_loss(params, batch, cfg, qcfg, key):
    logits = bert_apply(params, batch["tokens"], cfg, qcfg, key,
                        segment=batch.get("segment"))
    return _cls_loss(logits, batch["labels"])


def _cls_loss(logits, labels):
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)
        return -jnp.mean(ll), {"logits": logits}


def bert_span_loss(params, batch, cfg, qcfg, key):
    """SQuAD-style span prediction: logits over positions for start/end."""
    out = bert_apply(params, batch["tokens"], cfg, qcfg, key, pool=False)
    with jax.named_scope("loss"):
        start_lp = jax.nn.log_softmax(out[..., 0].astype(jnp.float32),
                                      axis=-1)
        end_lp = jax.nn.log_softmax(out[..., 1].astype(jnp.float32), axis=-1)
        ls = jnp.take_along_axis(start_lp, batch["span_start"][:, None], 1)
        le = jnp.take_along_axis(end_lp, batch["span_end"][:, None], 1)
        return (-0.5 * jnp.mean(ls + le),
                {"start_lp": start_lp, "end_lp": end_lp})


# ===================== ViT =====================

def vit_init(key, cfg: ArchConfig, num_classes: int = 10,
             img: int = 224, patch: int = 16, channels: int = 3) -> Params:
    ks = jax.random.split(key, 5)
    n_patches = (img // patch) ** 2
    return {
        "patch_w": blocks._init(ks[0], (patch * patch * channels, cfg.d_model)),
        "patch_b": jnp.zeros((cfg.d_model,)),
        "cls": blocks._init(ks[1], (1, 1, cfg.d_model)),
        "pos_embed": blocks._init(ks[2], (n_patches + 1, cfg.d_model)),
        "blocks": jax.vmap(lambda k: _enc_block_init(k, cfg))(
            jax.random.split(ks[3], cfg.n_layers)),
        "final_ln": blocks.norm_init(cfg),
        "head": blocks._init(ks[4], (cfg.d_model, num_classes)),
        "head_b": jnp.zeros((num_classes,)),
    }


def vit_apply(params: Params, images: Array, cfg: ArchConfig,
              qcfg: QuantLike, key, patch: int = 16) -> Array:
    sc = ensure_scope(qcfg)
    with jax.named_scope("patch_embed"):
        x = int_ops.int_patch_embed(images, params["patch_w"],
                                    params["patch_b"], subkey(key, -1),
                                    sc.leaf("patch_embed"), patch)
        B = x.shape[0]
        cls = jnp.broadcast_to(params["cls"], (B, 1, cfg.d_model))
        x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"][None]
    x = _encoder(params, x, cfg, sc, key)
    x = blocks.norm_apply(params["final_ln"], x, cfg, sc.child("final_ln"),
                          subkey(key, -2))
    with jax.named_scope("cls_head"):
        return int_ops.int_linear(x[:, 0], params["head"], params["head_b"],
                                  subkey(key, -3), sc.leaf("head"))


def vit_cls_loss(params, batch, cfg, qcfg, key, patch: int = 16):
    logits = vit_apply(params, batch["images"], cfg, qcfg, key, patch=patch)
    return _cls_loss(logits, batch["labels"])
