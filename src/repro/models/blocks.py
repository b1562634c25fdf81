"""Composable transformer blocks built on the integer layers.

Every projection goes through ``int_ops`` (the paper's integer fwd+bwd
layers); softmax / SiLU / GeLU / RoPE stay FP32 per the paper's recipe.

Attention is flash-style (online softmax over KV chunks) so no S×S score
tensor is ever materialized — required for the 32k/500k shapes.  When the
policy enables quantization at the ``attn.qk`` leaf, all shapes (training,
decode, chunked prefill) dispatch to the single ``int_ops.int_attention``
op — integer QK^T and PV with in-kernel FP32 online softmax; the XLA
``flash_attention`` / ``_decode_attention`` paths below serve only the
disabled/fp32 reference.

Quantization argument: every ``apply`` function takes ``qcfg`` as a bare
``QuantConfig`` (uniform, the paper's setting), a ``QuantPolicy`` (path-
scoped mixed precision) or a ``Scope`` (a policy already descended to this
module's path by the caller).  Each integer call site resolves its own leaf
config at trace time — ``scope.leaf("wq")`` — so the kernels below only
ever see plain ``QuantConfig`` leaves.

Trace names: each ``apply`` helper runs under a ``jax.named_scope`` named
by the last segment of its scope path (``ln1``, ``attn``, ``ln2``,
``mlp``, ...), and ``scan_stack`` under its stack's name (``blocks``), so
the quantization policy and a profile name a module alike.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import health, int_ops
from repro.core.qpolicy import QuantLike, ensure_scope
from repro.models.config import ArchConfig, RopeConfig

Array = jax.Array
Params = Dict[str, Any]

_BIG_NEG = -1e30


def _init(key, shape, scale=0.02):
    return (jax.random.normal(key, shape) * scale).astype(jnp.float32)


def subkey(key: Optional[Array], i) -> Optional[Array]:
    if key is None:
        return None
    if isinstance(i, int):
        i = i & 0xFFFFFFFF            # map negative tags into uint32 space
    return jax.random.fold_in(key, i)


def _named_module(default: str):
    """Run an ``apply(p, x, cfg, qcfg, ...)`` helper under the named scope of
    its module: the last segment of ``qcfg``'s scope path, or ``default``
    for a root scope (a bare config)."""
    def wrap(apply):
        @functools.wraps(apply)
        def named(p, x, cfg, qcfg, *args, **kwargs):
            path = ensure_scope(qcfg).path
            with jax.named_scope(path[-1] if path else default):
                return apply(p, x, cfg, qcfg, *args, **kwargs)
        return named
    return wrap


def mlp_leaves(cfg: ArchConfig, prefix: str = "mlp") -> list:
    """Integer-layer leaf paths of one MLP (policy-resolution probe set).
    ``act`` is the non-linearity's kept-ops leaf (DESIGN.md §10)."""
    names = (("wg", "wu", "wd") if cfg.act == "silu" else ("w1", "w2"))
    return [f"{prefix}.{n}" for n in names + ("act",)]


def scan_stack(make_body, carry, groups, xs):
    """Scan a layer stack in runs of identically-resolved policy scopes.

    ``groups`` is ``qpolicy.layer_groups`` output (``[(start, stop,
    scope)]``); ``make_body(scope)`` builds the scan body for one run;
    ``xs`` is a pytree of per-layer stacked inputs whose leaves all have
    the stack depth as leading dim — a ``jnp.arange(L)`` index vector rides
    along as an ordinary element, since ``arange(L)[s:e] == arange(s, e)``.

    With one group (uniform policy, or a bare config) this is exactly
    ``jax.lax.scan(make_body(scope), carry, xs)`` — no slicing, so the traced
    jaxpr is byte-identical to the pre-policy path.  With several, each run
    scans its slice of ``xs`` and stacked outputs are concatenated back in
    layer order (decode caches, per-layer KV, ...).
    """
    # a layer's scope path ends (..., stack, index): the scan takes the
    # stack's name
    with jax.named_scope(groups[0][2].path[-2]):
        if len(groups) == 1:
            return jax.lax.scan(make_body(groups[0][2]), carry, xs)
        outs = []
        for (s, e, bsc) in groups:
            carry, out = jax.lax.scan(
                make_body(bsc), carry,
                jax.tree.map(lambda a, s=s, e=e: a[s:e], xs))
            outs.append(out)
        if all(o is None for o in outs):
            return carry, None
        return carry, jax.tree.map(lambda *ys: jnp.concatenate(ys, axis=0),
                                   *outs)


# =========================================================================
# RoPE (FP32, precision-critical positional map)
# =========================================================================

def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(theta) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs       # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def yarn_inv_freq(rc: RopeConfig, hd: int) -> np.ndarray:
    """YaRN inverse frequencies (float32, ``hd // 2``), as Hugging Face's
    ``_compute_yarn_parameters`` gives them: interpolated by ``factor``
    below the truncated correction range of ``beta_fast``/``beta_slow``
    rotations at ``original_max_position``, extrapolated above it, and
    blended linearly across it."""
    def dim_of(rotations):
        return (hd * np.log(rc.original_max_position
                            / (rotations * 2 * np.pi))
                / (2 * np.log(rc.theta)))

    low = max(np.floor(dim_of(rc.beta_fast)), 0)
    high = min(np.ceil(dim_of(rc.beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    pos = rc.theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ramp = np.clip((np.arange(hd // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolate = 1 - ramp
    inv = (1 / (rc.factor * pos)) * (1 - extrapolate) + (1 / pos) * extrapolate
    return inv.astype(np.float32)


def apply_rope(x: Array, positions: Array, rc: RopeConfig) -> Array:
    """RoPE of one attention kind: ``rope`` at ``rc.theta``, or YaRN with
    cos and sin scaled by ``rc.attention_factor``."""
    if rc.kind == "default":
        return rope(x, positions, rc.theta)
    half = x.shape[-1] // 2
    freqs = jnp.asarray(yarn_inv_freq(rc, x.shape[-1]))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos = (jnp.cos(ang) * rc.attention_factor)[:, :, None, :]
    sin = (jnp.sin(ang) * rc.attention_factor)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# =========================================================================
# Flash attention (online softmax over KV chunks)
# =========================================================================

def flash_attention(
    q: Array,              # (B, Sq, Hkv, G, hd)
    k: Array,              # (B, Sk, Hkv, hd)
    v: Array,              # (B, Sk, Hkv, hd)
    *,
    causal: bool,
    q_offset: Array | int = 0,
    window: Optional[int] = None,
    chunk: int = 1024,
) -> Array:
    """Returns (B, Sq, Hkv, G, hd). FP32 softmax (paper-kept op)."""
    B, Sq, Hkv, G, hd = q.shape
    Sk = k.shape[1]
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        # ragged final KV chunk: zero-pad and mask kpos >= Sk below — the
        # padded columns never enter the softmax
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    q = q.astype(jnp.float32) * scale
    # q_offset may be a scalar (shared decode index) or a (B,)-vector of
    # per-sequence indices (continuous batching slots); qpos is (1|B, Sq).
    qpos = jnp.atleast_1d(jnp.asarray(q_offset))[:, None] + jnp.arange(Sq)

    def body(carry, c):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, c * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, c * chunk, chunk, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, kc.astype(jnp.float32))
        kpos = c * chunk + jnp.arange(chunk)
        ok = jnp.broadcast_to(kpos < Sk, qpos.shape + (chunk,))
        if causal:
            ok &= kpos[None, None, :] <= qpos[..., None]
        if window is not None:
            ok &= kpos[None, None, :] > (qpos[..., None] - window)
        okb = ok[:, None, None]                          # vs (B, H, G, Sq, chunk)
        s = jnp.where(okb, s, _BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(okb, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), _BIG_NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 3, 1, 2, 4)          # (B, Sq, Hkv, G, hd)


def _decode_attention(q: Array, k: Array, v: Array, index,
                      window: Optional[int]) -> Array:
    """One-query attention over a cache. q: (B, 1, Hkv, G, hd);
    k/v: (B, Smax, Hkv, hd); positions > index are masked out.

    ``index`` is a scalar (all rows at the same position) or a (B,)-vector
    of per-row positions (continuous-batching slots admitted at different
    times) — per-row masking keeps each slot's attention to its own tokens.
    """
    B, _, Hkv, G, hd = q.shape
    Smax = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    idx = jnp.atleast_1d(jnp.asarray(index))[:, None]     # (1|B, 1)
    kpos = jnp.arange(Smax)[None, :]                      # (1, Smax)
    ok = kpos <= idx                                      # (1|B, Smax)
    if window is not None:
        ok &= kpos > (idx - window)
    s = jnp.where(ok[:, None, None, None, :], s, _BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)                  # FP32 softmax (kept op)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4)


# =========================================================================
# Attention layer (GQA, optional sliding window, KV cache for decode)
# =========================================================================

def attention_init(key, cfg: ArchConfig) -> Params:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (D, H * hd)),
        "wk": _init(ks[1], (D, KV * hd)),
        "wv": _init(ks[2], (D, KV * hd)),
        "wo": _init(ks[3], (H * hd, D)),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H * hd,)), bk=jnp.zeros((KV * hd,)),
                 bv=jnp.zeros((KV * hd,)))
    return p


@_named_module("attn")
def attention_apply(
    p: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
    key: Optional[Array],
    *,
    causal: bool = True,
    positions: Array | None = None,
    kv_cache: Optional[Tuple[Array, Array]] = None,   # (k, v): (B, Smax, KV, hd)
    cache_index: Array | int = 0,
    kv_override: Optional[Tuple[Array, Array]] = None,  # cross-attention
    use_rope: bool = True,
    kind: Optional[str] = None,
) -> Tuple[Array, Optional[Tuple[Array, Array]]]:
    """Returns (out, updated_cache). x: (B, S, D).  ``kind`` names the
    layer's attention kind in ``cfg.layer_pattern`` (its window and RoPE);
    None for a config whose layers are alike."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV
    sc = ensure_scope(qcfg)
    health.probe(sc.path, x, sc.leaf("wq").act_bits)
    bq = p.get("bq")
    q = int_ops.int_linear(x, p["wq"], bq, subkey(key, 0), sc.leaf("wq"))
    q = q.reshape(B, S, KV, G, hd)
    if kv_override is None:
        k = int_ops.int_linear(x, p["wk"], p.get("bk"), subkey(key, 1),
                               sc.leaf("wk"))
        v = int_ops.int_linear(x, p["wv"], p.get("bv"), subkey(key, 2),
                               sc.leaf("wv"))
        k = k.reshape(B, S, KV, hd)
        v = v.reshape(B, S, KV, hd)
    else:
        k, v = kv_override

    # cache_index: scalar (all rows in step) or (B,)-vector of per-row
    # positions (continuous-batching slots admitted at different times).
    idx = jnp.asarray(cache_index)
    if positions is None:
        positions = jnp.atleast_1d(idx)[:, None] + jnp.arange(S)  # (1|B, S)
        positions = jnp.broadcast_to(positions, (B, S))
    if use_rope:
        rc = cfg.rope_for(kind)
        q = apply_rope(q.reshape(B, S, H, hd), positions, rc).reshape(
            B, S, KV, G, hd)
        if kv_override is None:
            k = apply_rope(k, positions, rc)

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        if idx.ndim == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_index, axis=1)
        else:
            row_upd = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
                    c, u, i, axis=0))
            ck = row_upd(ck, k.astype(ck.dtype), idx)
            cv = row_upd(cv, v.astype(cv.dtype), idx)
        new_cache = (ck, cv)
        k, v = ck, cv
        q_offset = cache_index
    else:
        q_offset = 0

    # Unified integer attention: when the policy enables quantization at
    # this site, every shape — training (Sq == Sk), decode (Sq == 1) and
    # chunked prefill — goes through the single ``int_ops.int_attention``
    # entry point (sim or fused Pallas flash kernels per backend).  The two
    # leaves are ``attn.qk`` (q/k bits + score-grad bits) and ``attn.pv``
    # (v/P bits + incoming-grad bits).  The FP32 XLA paths below remain
    # only as the disabled/fp32 reference.
    leaf_qk = sc.leaf("qk")
    leaf_pv = sc.leaf("pv")
    win = cfg.window_for(kind) if causal else None
    if leaf_qk.enabled:
        o = int_ops.int_attention(q, k, v, jnp.asarray(q_offset),
                                  subkey(key, 4), leaf_qk, leaf_pv,
                                  causal, win)
    elif S == 1 and kv_cache is not None:
        # decode: single-pass attention over the cache (memory-bound optimal;
        # no online-softmax scan needed for one query token)
        o = _decode_attention(q, k, v, cache_index, win)
    else:
        o = flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                            window=win)
    o = o.reshape(B, S, H * hd)
    out = int_ops.int_linear(o, p["wo"], None, subkey(key, 3), sc.leaf("wo"))
    return out, new_cache


# =========================================================================
# Dense MLP (SwiGLU or GeLU)
# =========================================================================

def mlp_init(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Params:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":
        return {"wg": _init(ks[0], (D, F)), "wu": _init(ks[1], (D, F)),
                "wd": _init(ks[2], (F, D))}
    return {"w1": _init(ks[0], (D, F)), "b1": jnp.zeros((F,)),
            "w2": _init(ks[1], (F, D)), "b2": jnp.zeros((D,))}


@_named_module("mlp")
def mlp_apply(p: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
              key: Optional[Array]) -> Array:
    sc = ensure_scope(qcfg)
    health.probe(sc.path, x,
                 sc.leaf("wg" if "wg" in p else "w1").act_bits)
    if "wg" in p:
        g = int_ops.int_linear(x, p["wg"], None, subkey(key, 0), sc.leaf("wg"))
        u = int_ops.int_linear(x, p["wu"], None, subkey(key, 1), sc.leaf("wu"))
        h = int_ops.int_activation(g, sc.leaf("act"), "silu") * u  # kept op
        return int_ops.int_linear(h, p["wd"], None, subkey(key, 2),
                                  sc.leaf("wd"))
    h = int_ops.int_linear(x, p["w1"], p["b1"], subkey(key, 0), sc.leaf("w1"))
    h = int_ops.int_activation(h, sc.leaf("act"), "gelu")
    return int_ops.int_linear(h, p["w2"], p["b2"], subkey(key, 1),
                              sc.leaf("w2"))


# =========================================================================
# Mixture of Experts (top-k, capacity-based sorted dispatch, optional
# always-on shared expert — qwen2-moe style)
# =========================================================================

def moe_init(key, cfg: ArchConfig) -> Params:
    """The router over every expert; the FFNs of the experts held here."""
    D, F, E, H = cfg.d_model, cfg.d_ff, cfg.moe_experts, cfg.moe_held
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (D, E)),
        "wg_e": _init(ks[1], (H, D, F)),
        "wu_e": _init(ks[2], (H, D, F)),
        "wd_e": _init(ks[3], (H, F, D)),
    }
    if cfg.moe_shared_dff:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.moe_shared_dff)
    return p


@_named_module("moe")
def moe_apply(p: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
              key: Optional[Array]) -> Tuple[Array, Tuple[Array, Array]]:
    """Returns (out, (aux_loss, dropped)). x: (B, S, D); ``dropped`` counts
    the (token, choice) pairs that found no room (f32).

    A config with ``moe_shard`` runs the drop-free expert layer over the
    experts it holds (``_expert_share_apply``); any other dispatches by
    capacity, as below, and drops what overflows.

    Dispatch is **shard-local** (per data-parallel group): the token→slot
    position is computed with a cumsum *within* each DP group and every group
    fills its own capacity slice, so dispatch/combine never move tokens
    across data-parallel ranks. A single global cumsum would make every
    position depend on every preceding token, forcing XLA to all-gather the
    full (T·K, D) token matrix (measured: 34 GB/step → collective-bound at
    62–82 s on the MoE train cells; §Perf iteration A.3/A.4).
    """
    from repro import sharding as _sh

    if cfg.moe_shard is not None:
        return _expert_share_apply(p, x, cfg, ensure_scope(qcfg), key)
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_topk
    T = B * S
    sc = ensure_scope(qcfg)
    health.probe(sc.path, x, sc.leaf("router").act_bits)
    xf = x.reshape(T, D)
    logits = int_ops.int_linear(xf, p["router"], None, subkey(key, 0),
                                sc.leaf("router"))
    # FP32 router (kept-ops swappable: i_softmax under kept_ops="integer")
    probs = int_ops.int_softmax(logits.astype(jnp.float32),
                                sc.leaf("router"))
    gate, sel = jax.lax.top_k(probs, K)                          # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(sel[:, 0], E), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * mean_probs)

    # --- shard-local capacity dispatch -----------------------------------
    # G = number of DP shards (1 without a mesh); each group of T/G tokens
    # dispatches into its own (E, Cg) capacity slice. Small token counts
    # (decode) use one group with drop-free capacity so decode == prefill.
    mesh = _sh.get_mesh()
    G = 1
    if mesh is not None and T * K > 4096:
        G = int(np.prod([mesh.shape[a] for a in _sh.batch_axes(mesh)]))
        if B % G:
            G = 1
    Tg = T // G
    if T * K <= 4096:
        Cg = Tg * K
    else:
        Cg = int(cfg.moe_capacity_factor * Tg * K / E) or 1
        Cg = ((Cg + 127) // 128) * 128
    sel_g = sel.reshape(G, Tg * K)                                # per group
    gate_f = gate.reshape(G, Tg * K)
    onehot = jax.nn.one_hot(sel_g, E, dtype=jnp.int32)            # (G, TgK, E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot
    pos_g = jnp.take_along_axis(pos_all, sel_g[..., None], axis=2)[..., 0]
    keep = pos_g < Cg
    pos_c = jnp.where(keep, pos_g, Cg)                            # spill slot
    rows = Cg + 1
    flat_idx = sel_g * rows + pos_c                               # (G, TgK)
    xg = xf.reshape(G, Tg, D)
    tok_idx = jnp.arange(Tg * K) // K
    upd = jnp.take_along_axis(xg, tok_idx[None, :, None], axis=1)  # (G,TgK,D)
    buf = jnp.zeros((G, E * rows, D), x.dtype)
    buf = _sh.constrain(buf, _sh.batch_axes(), None, None)
    buf = jax.vmap(lambda b, i, u: b.at[i].set(u))(buf, flat_idx, upd)
    ex_in = buf.reshape(G, E, rows, D)[:, :, :Cg]                 # (G,E,Cg,D)
    # merge groups into the expert row dim for the batched matmuls
    ex_in = ex_in.transpose(1, 0, 2, 3).reshape(E, G * Cg, D)
    ex_in = _sh.constrain(ex_in, None, _sh.batch_axes(), None)

    # --- per-expert integer SwiGLU (per-expert DFX scales) ---------------
    g = int_ops.int_batched_linear(ex_in, p["wg_e"], subkey(key, 1),
                                   sc.leaf("wg_e"))
    u = int_ops.int_batched_linear(ex_in, p["wu_e"], subkey(key, 2),
                                   sc.leaf("wu_e"))
    h = int_ops.int_activation(g, sc.leaf("act"), "silu") * u
    h = _sh.constrain(h, None, _sh.batch_axes(), "model")
    ex_out = int_ops.int_batched_linear(h, p["wd_e"], subkey(key, 3),
                                        sc.leaf("wd_e"))
    ex_out = _sh.constrain(ex_out, None, _sh.batch_axes(), None)

    # --- combine (shard-local gather) -------------------------------------
    out_g = ex_out.reshape(E, G, Cg, D).transpose(1, 0, 2, 3)      # (G,E,Cg,D)
    out_g = out_g.reshape(G, E * Cg, D)
    flat_take = sel_g * Cg + jnp.minimum(pos_g, Cg - 1)
    y = jnp.take_along_axis(out_g, flat_take[..., None], axis=1)   # (G,TgK,D)
    y = y * (keep[..., None] * gate_f[..., None])
    y = y.reshape(T, K, D).sum(axis=1)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], xf, cfg, sc.child("shared"),
                          subkey(key, 4))
    dropped = jnp.sum(jnp.logical_not(keep)).astype(jnp.float32)
    return y.reshape(B, S, D), (aux, dropped)


def moe_aux_zero() -> Tuple[Array, Array]:
    """A layer stack's running ``(load-balancing term, dropped pairs)``
    before its first layer; a dense layer adds this."""
    return jnp.float32(0), jnp.float32(0)


def _expert_share_apply(p: Params, x: Array, cfg: ArchConfig, sc,
                        key: Optional[Array]):
    """Drop-free expert layer over the share ``cfg.moe_shard`` of experts.

    The router's product and softmax cover all ``moe_experts``; each token
    takes its top ``moe_topk`` and renormalises their gates.  The (token,
    choice) pairs that land on a held expert are sorted by expert into rows
    — each expert's rows padded to the row tile, and the buffer sized for
    the case where every token picks ``min(topk, held)`` held experts, so
    nothing is ever dropped — and the three SwiGLU products run on the
    grouped limb matmul (``int_ops.int_grouped_linear``), one DFX exponent
    per expert.  The gated rows are summed back per token: this layer's
    part of the result, what the absent experts would add left out.
    Returns ``(y, (load-balancing term over every expert, held pairs past
    the rows))``; the second is 0 by the rows' sizing, and counted.

    The router product is float32 at full precision (a departure from the
    integer layers, like the softmax it feeds): top-k selection is
    discrete, and an integer product flips near-ties against the float
    reference.
    """
    from repro.kernels import ops as kops

    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_topk
    first, H = cfg.moe_shard
    T = B * S
    health.probe(sc.path, x, sc.leaf("wg_e").act_bits)
    xf = x.reshape(T, D)
    with jax.named_scope("router"):
        logits = jnp.dot(xf, p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        probs = int_ops.int_softmax(logits, sc.leaf("router"))
        gate, sel = jax.lax.top_k(probs, K)                      # (T, K)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        density = jnp.mean(jax.nn.one_hot(sel[:, 0], E), axis=0)
        aux = E * jnp.sum(density * jnp.mean(probs, axis=0))

    with jax.named_scope("dispatch"):
        tm = kops.group_row_tile(-(-T * K // E))    # an expert's mean rows
        rows = -(-(T * min(K, H) + H * tm) // tm) * tm
        local = sel.reshape(-1) - first
        held = (local >= 0) & (local < H)
        grp = jnp.where(held, local, H).astype(jnp.int32)         # (T*K,)
        counts = jnp.zeros((H + 1,), jnp.int32).at[grp].add(1)[:H]
        padded = jnp.maximum(tm, (counts + tm - 1) // tm * tm)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(padded)]).astype(jnp.int32)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)])
        order = jnp.argsort(grp, stable=True)
        g_sorted = jnp.minimum(grp[order], H - 1)
        row_sorted = (offsets[g_sorted]
                      + jnp.arange(T * K, dtype=jnp.int32) - starts[g_sorted])
        row = jnp.zeros((T * K,), jnp.int32).at[order].set(row_sorted)
        dropped = jnp.sum(held & (row >= rows))
        row = jnp.where(held, row, rows)      # other shares' pairs: a zero row
        tok = jnp.full((rows,), T, jnp.int32).at[row].set(
            jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
        x_rows = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])[tok]

    with jax.named_scope("experts"):
        g = int_ops.int_grouped_linear(x_rows, p["wg_e"], offsets,
                                       subkey(key, 1), sc.leaf("wg_e"), tm)
        u = int_ops.int_grouped_linear(x_rows, p["wu_e"], offsets,
                                       subkey(key, 2), sc.leaf("wu_e"), tm)
        h = int_ops.int_activation(g, sc.leaf("act"), "silu") * u
        y_rows = int_ops.int_grouped_linear(h, p["wd_e"], offsets,
                                            subkey(key, 3), sc.leaf("wd_e"),
                                            tm)

    with jax.named_scope("combine"):
        y_rows = jnp.concatenate([y_rows, jnp.zeros((1, D), y_rows.dtype)])
        w = jnp.where(held, gate.reshape(-1), 0.0).reshape(T, K)
        row = row.reshape(T, K)
        y = sum(y_rows[row[:, k]] * w[:, k, None] for k in range(K))
    return y.reshape(B, S, D), (aux, dropped.astype(jnp.float32))


# =========================================================================
# Norm wrappers
# =========================================================================

def norm_init(cfg: ArchConfig) -> Params:
    if cfg.norm == "layernorm":
        return {"g": jnp.ones((cfg.d_model,)), "b": jnp.zeros((cfg.d_model,))}
    return {"g": jnp.ones((cfg.d_model,))}


@_named_module("norm")
def norm_apply(p: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
               key: Optional[Array]) -> Array:
    sc = ensure_scope(qcfg)
    leaf = sc.cfg()                      # the scope path IS the norm's path
    health.probe(sc.path, x, leaf.act_bits)
    if "b" in p:
        return int_ops.int_layernorm(x, p["g"], p["b"], key, leaf)
    return int_ops.int_rmsnorm(x, p["g"], key, leaf)
