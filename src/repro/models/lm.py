"""Decoder-only language model covering the dense / MoE / SSM / hybrid / VLM
families, assembled from the integer blocks.

Layers are **scan-stacked** (one traced layer body, ``lax.scan`` over stacked
params) with ``jax.checkpoint`` remat — keeps the HLO small enough to compile
88-layer/12k-wide configs against a 512-device mesh and bounds activation
memory to one residual checkpoint per layer.

Three entry points per the shape grid:
  * ``loss_fn``      — next-token CE training objective (train_4k)
  * ``prefill``      — forward over a prompt, filling the KV/SSM cache
  * ``decode_step``  — one token with cache (decode_32k / long_500k)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro import utils
from repro.core import health, int_ops
from repro.core.qpolicy import (PolicyScopeError, QuantLike, ensure_scope,
                                layer_groups)
from repro.models import blocks, ssm
from repro.models.blocks import subkey
from repro.models.config import ArchConfig

Array = jax.Array
Params = Dict[str, Any]


# =========================================================================
# Quantization scoping
# =========================================================================
# Module paths (resolved against a QuantPolicy at trace time):
#   embed, mm_proj, final_norm, lm_head
#   blocks.{i}.{ln1, attn.{wq,wk,wv,wo,qk,pv}, ln2, mlp.{...}, moe.{...}}
#     (attn.qk / attn.pv are the fused integer-attention leaves: score
#     matmul bits and P·V / value bits respectively; mlp.act / moe.act are
#     the non-linearity's kept-ops leaves — DESIGN.md §10)
#   blocks.{i}.mamba.{wz,wx,wBC,wdt,conv_x,conv_BC,norm_g,out_proj,
#                     act.{conv_x,conv_BC,gate}}
#     (mamba's selective_scan core — softplus dt and the SSD exp recurrence —
#     is exempt from kept-ops swapping: it is FP32 by design, like the
#     optimizer, and never quantized; only the three SiLU sites route
#     through the policy)
#   shared_attn.{ln1, attn.*, ln2, mlp.*}          (hybrid family)
# Block indices also resolve under their negative alias (blocks.-1 = last
# layer).  Layers are scan-stacked, so a policy that assigns different
# configs to different block indices splits the scan into runs of
# identically-resolved layers (qpolicy.layer_groups); a uniform policy keeps
# the single scan and traces the byte-identical jaxpr of a bare QuantConfig.


def _block_leaves(cfg: ArchConfig) -> list:
    """Every integer-layer leaf path inside one dense transformer block —
    the probe set layer_groups uses to prove two layers resolve equal."""
    leaves = ["ln1", "ln2"] + [
        f"attn.{n}" for n in ("wq", "wk", "wv", "wo", "qk", "pv")]
    if cfg.moe_experts:
        leaves += ["moe.router", "moe.wg_e", "moe.wu_e", "moe.wd_e",
                   "moe.act"]
        if cfg.moe_shared_dff:
            leaves += blocks.mlp_leaves(cfg, "moe.shared")
    else:
        leaves += blocks.mlp_leaves(cfg)
    return leaves


_MAMBA_LEAVES = ["mamba." + n for n in
                 ("wz", "wx", "wBC", "wdt", "conv_x", "conv_BC",
                  "norm_g", "out_proj",
                  "act.conv_x", "act.conv_BC", "act.gate")]


def padded_vocab(cfg: ArchConfig) -> int:
    """Vocab padded to a multiple of 256 so it shards on any mesh axis
    (Megatron-style vocab padding; padded rows are never valid labels)."""
    return ((cfg.vocab + 255) // 256) * 256


# =========================================================================
# Init
# =========================================================================

def _block_init(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "ln1": blocks.norm_init(cfg),
        "attn": blocks.attention_init(ks[0], cfg),
        "ln2": blocks.norm_init(cfg),
    }
    if cfg.moe_experts:
        p["moe"] = blocks.moe_init(ks[1], cfg)
    else:
        p["mlp"] = blocks.mlp_init(ks[1], cfg)
    return p


def lm_init(key, cfg: ArchConfig) -> Params:
    V = padded_vocab(cfg)
    ks = jax.random.split(key, 5)
    params: Params = {
        "embed": blocks._init(ks[0], (V, cfg.d_model)),
        "final_norm": blocks.norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = blocks._init(ks[1], (cfg.d_model, V))

    L = cfg.n_layers
    if cfg.family in ("ssm", "hybrid"):
        params["blocks"] = jax.vmap(
            lambda k: {"mamba": ssm.mamba2_init(k, cfg)})(jax.random.split(ks[2], L))
        if cfg.family == "hybrid":
            params["shared_attn"] = _block_init(ks[3], cfg)
    else:
        params["blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg))(jax.random.split(ks[2], L))
    if cfg.vlm_prefix:
        params["mm_proj"] = blocks._init(ks[4], (cfg.d_model, cfg.d_model))
    return params


# =========================================================================
# Layer bodies
# =========================================================================

def _attn_block(bp: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
                key, *, cache=None, cache_index=0, kind=None):
    sc = ensure_scope(qcfg)
    h = blocks.norm_apply(bp["ln1"], x, cfg, sc.child("ln1"), subkey(key, 0))
    h, new_cache = blocks.attention_apply(
        bp["attn"], h, cfg, sc.child("attn"), subkey(key, 1),
        kv_cache=cache, cache_index=cache_index, kind=kind)
    x = sharding.constrain_tokens(x + h)
    h = blocks.norm_apply(bp["ln2"], x, cfg, sc.child("ln2"), subkey(key, 2))
    aux = blocks.moe_aux_zero()
    if "moe" in bp:
        h, aux = blocks.moe_apply(bp["moe"], h, cfg, sc.child("moe"),
                                  subkey(key, 3))
    else:
        h = blocks.mlp_apply(bp["mlp"], h, cfg, sc.child("mlp"),
                             subkey(key, 3))
    x = sharding.constrain_tokens(x + h)
    return x, aux, new_cache


def _uniform_stack_scope(sc, L: int, leaves, what: str):
    """Single scope for a stack that cannot be group-split (hybrid), with a
    clear error when the policy tries to split it."""
    groups = layer_groups(sc, L, leaves)
    if len(groups) > 1:
        raise PolicyScopeError(
            f"quantization policy resolves non-uniformly over the {what} "
            f"block stack ({len(groups)} groups); per-layer-index scope "
            "rules are not supported for the hybrid family — use rules "
            "uniform over 'blocks.*'")
    return groups[0][2]


def _backbone_train(params: Params, x: Array, cfg: ArchConfig,
                    qcfg: QuantLike, key) -> Tuple[Array, Array]:
    """Runs all layers (training/prefill, no cache). Returns (x, (aux_sum,
    dropped_sum))."""
    L = cfg.n_layers
    sc = ensure_scope(qcfg)

    if cfg.family in ("ssm", "hybrid"):
        # probes are masked here: the hybrid family runs _attn_block inside
        # nested scans with no harvest channel, so a live collector would
        # leak tracers out of the loop trace
        with health.suspend():
            return _backbone_train_ssm(params, x, cfg, sc, key)
    if cfg.layer_pattern:
        return _backbone_train_periods(params, x, cfg, sc, key)

    def make_body(bsc):
        def body(carry, inp):
            x, aux = carry
            bp, idx = inp
            # frame opens INSIDE the remat/scan body: probe tracers ride out
            # as the scan's stacked y-output instead of leaking through the
            # module-global sink (core/health.py)
            with health.frame() as fr:
                x, a, _ = _attn_block(bp, x, cfg, bsc, subkey(key, idx))
            return (x, _add(aux, a)), fr.harvest()
        return utils.checkpoint(body)

    groups = layer_groups(sc, L, _block_leaves(cfg))
    (x, aux), hs = blocks.scan_stack(make_body, (x, blocks.moe_aux_zero()),
                                     groups,
                                     (params["blocks"], jnp.arange(L)))
    health.record_stacked(hs)
    return x, aux


# -------------------------------------------------------------------------
# Layer patterns: one scan step per whole period
# -------------------------------------------------------------------------
# A config with a ``layer_pattern`` (e.g. three sliding-window layers and
# one full layer) scans over periods: each step runs the period's layers in
# the published order, each under the named scope of its kind (``sliding``,
# ``full``) inside ``blocks`` and each rematerialised on its own, as the
# layers of a uniform stack are.  Stacked inputs are viewed (L/P, P, ...).

def _period_groups(sc, cfg: ArchConfig):
    """``layer_groups`` in whole periods; a policy that splits a period
    cannot be scanned by periods."""
    P = len(cfg.layer_pattern)
    groups = layer_groups(sc, cfg.n_layers, _block_leaves(cfg))
    if any(s % P or e % P for s, e, _ in groups):
        raise PolicyScopeError(
            f"quantization policy splits the {P}-layer period of "
            f"{cfg.name}; scope rules must resolve alike over whole periods")
    return [(s // P, e // P, g) for s, e, g in groups]


def _by_period(cfg: ArchConfig, tree):
    P = len(cfg.layer_pattern)
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] // P, P) + a.shape[1:]), tree)


def _at(tree, j: int):
    return jax.tree.map(lambda a: a[j], tree)


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _backbone_train_periods(params: Params, x: Array, cfg: ArchConfig,
                            sc, key) -> Tuple[Array, Any]:
    def make_layer(bsc, kind):
        def layer(x, bp, idx):
            with jax.named_scope(kind), health.frame() as fr:
                x, a, _ = _attn_block(bp, x, cfg, bsc, subkey(key, idx),
                                      kind=kind)
            return x, a, fr.harvest()
        return utils.checkpoint(layer)

    def make_body(bsc):
        layers = {k: make_layer(bsc, k) for k in set(cfg.layer_pattern)}

        def body(carry, inp):
            x, aux = carry
            bp, idx = inp
            harvests = []
            for j, kind in enumerate(cfg.layer_pattern):
                x, a, h = layers[kind](x, _at(bp, j), idx[j])
                aux = _add(aux, a)
                harvests += [h] if h else []
            merged = (functools.reduce(health.merge, harvests)
                      if harvests else None)
            return (x, aux), merged
        return body

    (x, aux), hs = blocks.scan_stack(
        make_body, (x, blocks.moe_aux_zero()), _period_groups(sc, cfg),
        _by_period(cfg, (params["blocks"], jnp.arange(cfg.n_layers))))
    health.record_stacked(hs)
    return x, aux


def _backbone_train_ssm(params: Params, x: Array, cfg: ArchConfig,
                        sc, key) -> Tuple[Array, Array]:
    L = cfg.n_layers
    every = cfg.hybrid_attn_every or L

    def make_mamba_body(bsc):
        def mamba_body(x, inp):
            bp, idx = inp
            k = subkey(key, idx)
            h, _ = ssm.mamba2_apply(bp["mamba"], x, cfg,
                                    bsc.child("mamba"), k)
            return sharding.constrain_tokens(x + h), None
        return utils.checkpoint(mamba_body)

    if cfg.family == "ssm":
        groups = layer_groups(sc, L, _MAMBA_LEAVES)
        x, _ = blocks.scan_stack(make_mamba_body, x, groups,
                                 (params["blocks"], jnp.arange(L)))
        return x, blocks.moe_aux_zero()

    # hybrid: groups of ``every`` mamba layers + the shared attn block
    bsc = _uniform_stack_scope(sc, L, _MAMBA_LEAVES, "hybrid")
    mamba_body = make_mamba_body(bsc)
    G = L // every
    grouped = jax.tree.map(
        lambda a: a.reshape((G, every) + a.shape[1:]), params["blocks"])

    shared_body = utils.checkpoint(
        lambda x, idx: _attn_block(params["shared_attn"], x, cfg,
                                   sc.child("shared_attn"),
                                   subkey(key, 10_000 + idx))[:2])

    def group_body(x, inp):
        gp, gidx = inp
        x, _ = jax.lax.scan(mamba_body, x,
                            (gp, gidx * every + jnp.arange(every)))
        x, _ = shared_body(x, gidx)
        return x, None

    x, _ = jax.lax.scan(group_body, x, (grouped, jnp.arange(G)))
    return x, blocks.moe_aux_zero()


# =========================================================================
# Embedding / head
# =========================================================================

def _embed(params: Params, tokens: Array, cfg: ArchConfig, qcfg: QuantLike,
           key, prefix_embeds: Optional[Array] = None) -> Array:
    sc = ensure_scope(qcfg)
    x = int_ops.int_embedding(params["embed"], tokens, subkey(key, -1),
                              sc.leaf("embed"))
    if prefix_embeds is not None:       # VLM: projected patch embeddings
        pe = int_ops.int_linear(prefix_embeds, params["mm_proj"], None,
                                subkey(key, -2), sc.leaf("mm_proj"))
        x = jnp.concatenate([pe, x], axis=1)
    health.probe(sc.path + ("embed",), x, sc.leaf("embed").act_bits)
    return sharding.constrain_tokens(x)


def _logits(params: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
            key) -> Array:
    sc = ensure_scope(qcfg)
    x = blocks.norm_apply(params["final_norm"], x, cfg,
                          sc.child("final_norm"), subkey(key, -3))
    if cfg.tie_embeddings:
        head = params["embed"].T
    else:
        head = params["lm_head"]
    # the head resolves under "lm_head" whether or not it is tied to the
    # embedding table (a tied table can still be *read* at head precision)
    health.probe(sc.path + ("lm_head",), x, sc.leaf("lm_head").act_bits)
    logits = int_ops.int_linear(x, head, None, subkey(key, -4),
                                sc.leaf("lm_head"))
    return sharding.constrain(logits, sharding.batch_axes(), None, "model")


# =========================================================================
# Training loss
# =========================================================================

def lm_loss(params: Params, batch: Dict[str, Array], cfg: ArchConfig,
            qcfg: QuantLike, key) -> Tuple[Array, Dict[str, Array]]:
    """batch: tokens (B, S) int32, labels (B, S) int32 (-1 = masked);
    VLM adds patch_embeds (B, P, D)."""
    tokens = sharding.constrain_batch(batch["tokens"])
    x = _embed(params, tokens, cfg, qcfg, key,
               prefix_embeds=batch.get("patch_embeds"))
    x, (aux, dropped) = _backbone_train(params, x, cfg, qcfg, key)
    if cfg.vlm_prefix:
        x = x[:, -tokens.shape[1]:]     # loss only over text positions
    logits = _logits(params, x, cfg, qcfg, key)
    labels = batch["labels"]
    valid = labels >= 0
    lab = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)
    counters = {}
    if cfg.moe_experts:
        loss = loss + 0.01 * aux / cfg.n_layers
        counters = {"moe_dropped": dropped}
    return loss, {"ce": loss, "aux": aux, **counters}


# =========================================================================
# Serving: cache init / prefill / decode
# =========================================================================

def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Params:
    """Decode cache. ``index`` is a per-sequence (B,)-vector so continuous
    batching can admit requests into individual slots at position 0 while
    other slots keep decoding at their own positions (serve/engine.py)."""
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    index = jnp.zeros((batch,), jnp.int32)
    if cfg.family == "ssm":
        s = ssm.mamba2_init_state(cfg, batch)
        return {"ssm": jnp.broadcast_to(s[0], (L,) + s[0].shape),
                "conv_x": jnp.broadcast_to(s[1], (L,) + s[1].shape),
                "conv_BC": jnp.broadcast_to(s[2], (L,) + s[2].shape),
                "index": index}
    if cfg.family == "hybrid":
        G = cfg.n_layers // cfg.hybrid_attn_every
        s = ssm.mamba2_init_state(cfg, batch)
        return {
            "ssm": jnp.broadcast_to(s[0], (L,) + s[0].shape),
            "conv_x": jnp.broadcast_to(s[1], (L,) + s[1].shape),
            "conv_BC": jnp.broadcast_to(s[2], (L,) + s[2].shape),
            "k": jnp.zeros((G, batch, max_seq, KV, hd), dtype),
            "v": jnp.zeros((G, batch, max_seq, KV, hd), dtype),
            "index": index,
        }
    return {"k": jnp.zeros((L, batch, max_seq, KV, hd), dtype),
            "v": jnp.zeros((L, batch, max_seq, KV, hd), dtype),
            "index": index}


def _constrain_cache(cache: Params) -> Params:
    out = dict(cache)
    for n in ("k", "v"):
        if n in cache:
            # shard: batch over DP, head_dim over model (kv-head counts like 8
            # or 3 do not divide a 16-way model axis; head_dim does)
            out[n] = sharding.constrain(
                cache[n], None, sharding.batch_axes(), None, None, "model")
    if "ssm" in cache:                   # (L, B, H, P, N): shard heads on model
        out["ssm"] = sharding.constrain(
            cache["ssm"], None, sharding.batch_axes(), "model", None, None)
    for n in ("conv_x", "conv_BC"):      # (L, B, K-1, C): shard channels
        if n in cache:
            out[n] = sharding.constrain(
                cache[n], None, sharding.batch_axes(), None, "model")
    return out


def lm_decode_step(params: Params, token: Array, cache: Params,
                   cfg: ArchConfig, qcfg: QuantLike) -> Tuple[Array, Params]:
    """token: (B, 1) int32. Returns (logits (B, 1, V), new cache)."""
    key = None                                   # no stochastic rounding at serve
    index = cache["index"]
    sc = ensure_scope(qcfg)
    x = _embed(params, token, cfg, sc, key)
    L = cfg.n_layers

    if cfg.family in ("ssm", "hybrid"):
        every = cfg.hybrid_attn_every or L

        def make_mamba_body(bsc):
            def mamba_body(x, inp):
                bp, s_ssm, s_cx, s_cbc = inp
                h, (n_ssm, n_cx, n_cbc) = ssm.mamba2_apply(
                    bp["mamba"], x, cfg, bsc.child("mamba"), None,
                    state=(s_ssm, s_cx, s_cbc), decode=True)
                return x + h, (n_ssm, n_cx, n_cbc)
            return mamba_body

        if cfg.family == "ssm":
            groups = layer_groups(sc, L, _MAMBA_LEAVES)
            x, (n_ssm, n_cx, n_cbc) = blocks.scan_stack(
                make_mamba_body, x, groups,
                (params["blocks"], cache["ssm"], cache["conv_x"],
                 cache["conv_BC"]))
            new_cache = {"ssm": n_ssm, "conv_x": n_cx, "conv_BC": n_cbc,
                         "index": index + 1}
        else:
            bsc = _uniform_stack_scope(sc, L, _MAMBA_LEAVES, "hybrid")
            mamba_body = make_mamba_body(bsc)
            ssc = sc.child("shared_attn")
            G = L // every
            grouped = jax.tree.map(
                lambda a: a.reshape((G, every) + a.shape[1:]), params["blocks"])
            g_states = jax.tree.map(
                lambda a: a.reshape((G, every) + a.shape[1:]),
                (cache["ssm"], cache["conv_x"], cache["conv_BC"]))

            def group_body(x, inp):
                gp, s_ssm, s_cx, s_cbc, ck, cv = inp
                x, ns = jax.lax.scan(mamba_body, x, (gp, s_ssm, s_cx, s_cbc))
                h = blocks.norm_apply(params["shared_attn"]["ln1"], x, cfg,
                                      ssc.child("ln1"), None)
                h, (nk, nv) = blocks.attention_apply(
                    params["shared_attn"]["attn"], h, cfg, ssc.child("attn"),
                    None, kv_cache=(ck, cv), cache_index=index)
                x = x + h
                h = blocks.norm_apply(params["shared_attn"]["ln2"], x, cfg,
                                      ssc.child("ln2"), None)
                h = blocks.mlp_apply(params["shared_attn"]["mlp"], h, cfg,
                                     ssc.child("mlp"), None)
                return x + h, ns + (nk, nv)

            with health.suspend():   # probes inside group_body can't harvest
                x, (n_ssm, n_cx, n_cbc, nk, nv) = jax.lax.scan(
                    group_body, x,
                    (grouped,) + g_states + (cache["k"], cache["v"]))
            new_cache = {
                "ssm": n_ssm.reshape((L,) + n_ssm.shape[2:]),
                "conv_x": n_cx.reshape((L,) + n_cx.shape[2:]),
                "conv_BC": n_cbc.reshape((L,) + n_cbc.shape[2:]),
                "k": nk, "v": nv, "index": index + 1,
            }
        logits = _logits(params, x, cfg, sc, key)
        return logits, _constrain_cache(new_cache)

    return lm_prefill_cache(params, token, cache, cfg, sc)


def lm_prefill_cache(params: Params, tokens: Array, cache: Params,
                     cfg: ArchConfig, qcfg: QuantLike) -> Tuple[Array, Params]:
    """Chunked prefill through the decode cache in ONE dispatch.

    tokens: (B, S) int32 — a prompt chunk (S == 1 is plain decode; this is
    the decode step's dense tail, generalized).  All S tokens are written
    into the KV cache at positions ``cache['index'] .. index+S`` and attend
    causally with per-row ``q_offset = index``, so the serve engine admits a
    whole prompt without issuing O(prompt_len) single-token dispatches.
    Returns (last-position logits (B, 1, V), new cache).  Attention-cache
    families only — SSM/hybrid state recurrence still steps token by token.
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            "lm_prefill_cache supports attention-cache families only; "
            f"got family={cfg.family!r} (use lm_decode_step per token)")
    key = None                                   # no stochastic rounding at serve
    index = cache["index"]
    sc = ensure_scope(qcfg)
    x = _embed(params, tokens, cfg, sc, key)
    L = cfg.n_layers

    if cfg.layer_pattern:
        def make_period(bsc):
            def body(carry, inp):
                x, aux = carry
                bp, ck, cv, _ = inp
                new = []
                for j, kind in enumerate(cfg.layer_pattern):
                    with jax.named_scope(kind):
                        x, a, kv = _attn_block(
                            _at(bp, j), x, cfg, bsc, None,
                            cache=(ck[j], cv[j]), cache_index=index,
                            kind=kind)
                    aux = _add(aux, a)
                    new.append(kv)
                return (x, aux), tuple(jnp.stack(c) for c in zip(*new))
            return body

        with health.suspend():     # serve-path scan has no harvest channel
            (x, _), (nk, nv) = blocks.scan_stack(
                make_period, (x, blocks.moe_aux_zero()),
                _period_groups(sc, cfg),
                _by_period(cfg, (params["blocks"], cache["k"], cache["v"],
                                 jnp.arange(L))))
        nk, nv = (a.reshape((L,) + a.shape[2:]) for a in (nk, nv))
        logits = _logits(params, x[:, -1:], cfg, sc, key)
        new_index = index + tokens.shape[1]
        return logits, _constrain_cache({"k": nk, "v": nv,
                                         "index": new_index})

    def make_body(bsc):
        def body(carry, inp):
            x, aux = carry
            bp, ck, cv, idx = inp
            x, a, ncache = _attn_block(bp, x, cfg, bsc, None,
                                       cache=(ck, cv), cache_index=index)
            return (x, _add(aux, a)), ncache
        return body

    groups = layer_groups(sc, L, _block_leaves(cfg))
    with health.suspend():     # serve-path scan has no harvest channel
        (x, _), (nk, nv) = blocks.scan_stack(
            make_body, (x, blocks.moe_aux_zero()), groups,
            (params["blocks"], cache["k"], cache["v"], jnp.arange(L)))
    logits = _logits(params, x[:, -1:], cfg, sc, key)
    new_index = index + tokens.shape[1]
    return logits, _constrain_cache({"k": nk, "v": nv, "index": new_index})


def lm_prefill(params: Params, tokens: Array, cfg: ArchConfig,
               qcfg: QuantLike,
               prefix_embeds: Optional[Array] = None) -> Tuple[Array, Array]:
    """Forward pass over the full prompt; returns (last-token logits, final
    hidden states). Cache filling for the dense path reuses the training
    backbone (no S×S materialization thanks to flash attention)."""
    x = _embed(params, tokens, cfg, qcfg, None, prefix_embeds=prefix_embeds)
    x, _ = _backbone_train(params, x, cfg, qcfg, None)
    logits = _logits(params, x[:, -1:], cfg, qcfg, None)
    return logits, x
