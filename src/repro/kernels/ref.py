"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the semantics of the corresponding kernel exactly —
tests sweep shapes/dtypes and ``assert_allclose`` kernel-vs-oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def bfp_matmul_ref(xm: jax.Array, wm: jax.Array, out_exp: jax.Array) -> jax.Array:
    """Integer mantissa matmul with fused dequant: ``(xm @ wm) * 2**out_exp``.

    xm: (M, K) int8/int16 mantissas; wm: (K, N); out_exp: scalar int32.
    Accumulation is exact integer (int32).
    """
    acc = jax.lax.dot_general(
        xm.astype(jnp.int32), wm.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.exp2(out_exp.astype(jnp.float32))


def bfp_matmul_nt_ref(gm: jax.Array, wm: jax.Array, out_exp: jax.Array) -> jax.Array:
    """NT oracle: ``(gm @ wmᵀ) * 2**out_exp`` — the dX backward product.

    gm: (M, N); wm: (K, N) in forward layout. Exact int32 accumulation.
    """
    acc = jax.lax.dot_general(
        gm.astype(jnp.int32), wm.astype(jnp.int32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.exp2(out_exp.astype(jnp.float32))


def bfp_matmul_tn_ref(xm: jax.Array, gm: jax.Array, out_exp: jax.Array) -> jax.Array:
    """TN oracle: ``(xmᵀ @ gm) * 2**out_exp`` — the dW backward product.

    xm: (M, K) in forward layout; gm: (M, N). Exact int32 accumulation.
    """
    acc = jax.lax.dot_general(
        xm.astype(jnp.int32), gm.astype(jnp.int32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.exp2(out_exp.astype(jnp.float32))


def bfp_matmul_batched_ref(xm: jax.Array, wm: jax.Array,
                           out_exp: jax.Array) -> jax.Array:
    """Batched NN oracle: ``(xm[e] @ wm[e]) * 2**out_exp[e]``.

    xm: (E, M, K); wm: (E, K, N); out_exp: (E,) int32. Exact int32
    accumulation, per-expert dequant scale.
    """
    acc = jax.lax.dot_general(
        xm.astype(jnp.int32), wm.astype(jnp.int32),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    scale = jnp.exp2(out_exp.astype(jnp.float32)).reshape(-1, 1, 1)
    return acc.astype(jnp.float32) * scale


def bfp_matmul_batched_nt_ref(gm: jax.Array, wm: jax.Array,
                              out_exp: jax.Array) -> jax.Array:
    """Batched NT oracle: ``(gm[e] @ wm[e]ᵀ) * 2**out_exp[e]``.

    gm: (E, M, N); wm: (E, K, N) in forward layout; out_exp: (E,).
    """
    acc = jax.lax.dot_general(
        gm.astype(jnp.int32), wm.astype(jnp.int32),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    scale = jnp.exp2(out_exp.astype(jnp.float32)).reshape(-1, 1, 1)
    return acc.astype(jnp.float32) * scale


def bfp_matmul_batched_tn_ref(xm: jax.Array, gm: jax.Array,
                              out_exp: jax.Array) -> jax.Array:
    """Batched TN oracle: ``(xm[e]ᵀ @ gm[e]) * 2**out_exp[e]``.

    xm: (E, M, K) in forward layout; gm: (E, M, N); out_exp: (E,).
    """
    acc = jax.lax.dot_general(
        xm.astype(jnp.int32), gm.astype(jnp.int32),
        (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.int32)
    scale = jnp.exp2(out_exp.astype(jnp.float32)).reshape(-1, 1, 1)
    return acc.astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("dimension_numbers",))
def limb_loop_matmul_ref(xm: jax.Array, wm: jax.Array, out_exp: jax.Array,
                         *, dimension_numbers) -> jax.Array:
    """The REMOVED per-limb-pair dispatch path, reproduced bit-exactly.

    ``xm``/``wm`` are stacked int8 limb planes (leading axis).  Each limb
    pair contracts exactly in int32 (one partial per pair — what each of the
    old per-pair ``pallas_call``s produced), the partial dequantizes by
    ``2**out_exp`` in f32, is scaled by its ``2**(7(jx+jw))`` limb shift
    (exact power-of-two multiplies), and the partials sum in the old loop
    order (x-limbs outer, w-limbs inner).  The fused kernel's epilogue
    follows the identical expression, so kernel-vs-this must be
    **bit-equal** — the acceptance property of the single-dispatch rewrite.

    This function is deliberately **jitted**: the removed path's combine ran
    inside the layers' jitted custom-vjp bodies, where XLA canonicalizes the
    flat f32 add chain (tree-reassociation) — that compiled program, not a
    strictly-left-to-right eager sum, is the semantics being matched.  The
    fused kernel's epilogue compiles through the same canonicalization.

    ``dimension_numbers`` is the per-pair int32 ``dot_general`` contraction
    of the LOGICAL mantissas (e.g. ``(((1,), (0,)), ((), ()))`` for NN);
    ``out_exp`` must already broadcast against the contraction output (pass
    ``(E, 1, 1)`` for the batched layouts).
    """
    scale0 = jnp.exp2(out_exp.astype(jnp.float32))
    out = None
    for jx in range(xm.shape[0]):
        for jw in range(wm.shape[0]):
            acc = jax.lax.dot_general(
                xm[jx].astype(jnp.int32), wm[jw].astype(jnp.int32),
                dimension_numbers, preferred_element_type=jnp.int32)
            part = (acc.astype(jnp.float32) * scale0) * (2.0 ** (7 * (jx + jw)))
            out = part if out is None else out + part
    return out


def dfx_quantize_grouped_ref(x: jax.Array, exp: jax.Array, bits: int,
                             u: jax.Array | None = None) -> jax.Array:
    """Grouped-scale quantize oracle: slice ``e`` shifts by ``exp[e]``.

    x: (E, M, N); exp: (E,). Mirrors ``dfx_quantize_ref`` per leading slice.
    """
    e = exp.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1))
    y = x.astype(jnp.float32) * jnp.exp2(-e)
    y = jnp.floor(y + u) if u is not None else jnp.round(y)
    lim = float(2 ** (bits - 1) - 1)
    dt = jnp.int8 if bits <= 8 else (jnp.int16 if bits <= 16 else jnp.int32)
    return jnp.clip(y, -lim, lim).astype(dt)


def dfx_quantize_ref(x: jax.Array, exp: jax.Array, bits: int,
                     u: jax.Array | None = None) -> jax.Array:
    """Shift-and-round pass of the linear fixed-point mapping.

    ``exp`` is the precomputed scale exponent (``e_max - bits + 1``); ``u`` is
    optional uniform noise in [0,1) enabling stochastic rounding.
    Returns the integer mantissa in the narrowest fitting dtype.
    """
    y = x.astype(jnp.float32) * jnp.exp2(-exp.astype(jnp.float32))
    y = jnp.floor(y + u) if u is not None else jnp.round(y)
    lim = float(2 ** (bits - 1) - 1)
    dt = jnp.int8 if bits <= 8 else (jnp.int16 if bits <= 16 else jnp.int32)
    return jnp.clip(y, -lim, lim).astype(dt)


def _f64(a) -> np.ndarray:
    """Host float64 view — exact for any int16 mantissa moment sum.

    The norm oracles accumulate in numpy float64 on purpose (the one
    deviation from the pure-jnp rule): the moment budget is ``2(b-1) +
    log2 D`` bits (~40 for int16 at D=768) and f64 holds 52, so these are
    the exact ground truth the kernels' int32-limb accumulation is tested
    against.  jnp can't provide that here — with x64 disabled it silently
    truncates to f32, which is exactly the bug being guarded.
    """
    return np.asarray(a, np.float64)


def int_layernorm_fwd_ref(xm: jax.Array, x_exp: jax.Array, gamma: jax.Array,
                          beta: jax.Array, eps: float = 1e-5):
    """Multi-output fused LN forward oracle: one-pass integer statistics.

    Mirrors the kernel semantics — mantissa-domain ``E[x²] − μ²`` moments,
    value-domain eps guard and rsqrt — with exact f64 sums.  Returns
    ``(y, mu, rstd)``; mu/rstd are the value-domain per-row statistics.
    """
    x = _f64(xm)
    d = x.shape[-1]
    scale = 2.0 ** float(np.asarray(x_exp))
    mu_m = x.sum(-1, keepdims=True) / d
    # clamp like the kernel: the one-pass variance is >= 0 in exact
    # arithmetic but rounding can push a constant row microscopically negative
    var_m = np.maximum((x * x).sum(-1, keepdims=True) / d - mu_m * mu_m, 0.0)
    mu = mu_m * scale
    rstd = 1.0 / np.sqrt(var_m * scale * scale + eps)
    xn = (x * scale - mu) * rstd
    y = xn * _f64(gamma) + _f64(beta)
    return (jnp.asarray(y, jnp.float32), jnp.asarray(mu, jnp.float32),
            jnp.asarray(rstd, jnp.float32))


def int_layernorm_bwd_ref(xm: jax.Array, x_exp: jax.Array, gm: jax.Array,
                          g_exp: jax.Array, gamma: jax.Array, mu: jax.Array,
                          rstd: jax.Array):
    """Fused LN backward oracle: ``(dx, dgamma, dbeta)`` in exact f64.

    ``xn`` is rebuilt from the integer activation mantissas and the
    forward-saved statistics — the same contract as the kernel.
    """
    x, g = _f64(xm), _f64(gm)
    xs = 2.0 ** float(np.asarray(x_exp))
    gs = 2.0 ** float(np.asarray(g_exp))
    d = x.shape[-1]
    xn = (x * xs - _f64(mu)) * _f64(rstd)
    gq = g * gs
    gg = gq * _f64(gamma)
    mean_gg = gg.sum(-1, keepdims=True) / d
    mean_ggxn = (gg * xn).sum(-1, keepdims=True) / d
    dx = _f64(rstd) * (gg - mean_gg - xn * mean_ggxn)
    return (jnp.asarray(dx, jnp.float32),
            jnp.asarray((gq * xn).sum(0), jnp.float32),
            jnp.asarray(gq.sum(0), jnp.float32))


def int_rmsnorm_fwd_ref(xm: jax.Array, x_exp: jax.Array, gamma: jax.Array,
                        eps: float = 1e-6):
    """Multi-output fused RMS-norm forward oracle. Returns ``(y, rstd)``."""
    x = _f64(xm)
    d = x.shape[-1]
    scale = 2.0 ** float(np.asarray(x_exp))
    ms = (x * x).sum(-1, keepdims=True) / d * scale * scale
    rstd = 1.0 / np.sqrt(ms + eps)
    y = x * scale * rstd * _f64(gamma)
    return jnp.asarray(y, jnp.float32), jnp.asarray(rstd, jnp.float32)


def int_rmsnorm_bwd_ref(xm: jax.Array, x_exp: jax.Array, gm: jax.Array,
                        g_exp: jax.Array, gamma: jax.Array, rstd: jax.Array):
    """Fused RMS-norm backward oracle: ``(dx, dgamma)`` in exact f64."""
    x, g = _f64(xm), _f64(gm)
    xs = 2.0 ** float(np.asarray(x_exp))
    gs = 2.0 ** float(np.asarray(g_exp))
    d = x.shape[-1]
    xn = x * xs * _f64(rstd)
    gq = g * gs
    gg = gq * _f64(gamma)
    mean_ggxn = (gg * xn).sum(-1, keepdims=True) / d
    dx = _f64(rstd) * (gg - xn * mean_ggxn)
    return (jnp.asarray(dx, jnp.float32),
            jnp.asarray((gq * xn).sum(0), jnp.float32))


# =========================================================================
# Integer flash-attention oracles (DESIGN.md §6)
# =========================================================================

def _attn_mask_ref(B: int, Sq: int, Sk: int, q_offset, causal: bool,
                   window) -> np.ndarray:
    """(B, Sq, Sk) bool validity — the kernel's mask semantics exactly."""
    off = np.broadcast_to(
        np.atleast_1d(np.asarray(q_offset, np.int64)), (B,))
    qpos = off[:, None] + np.arange(Sq)                       # (B, Sq)
    kpos = np.arange(Sk)
    ok = np.ones((B, Sq, Sk), bool)
    if causal:
        ok &= kpos[None, None, :] <= qpos[:, :, None]
    if window is not None:
        ok &= kpos[None, None, :] > qpos[:, :, None] - window
    return ok


def int_attention_fwd_ref(qm: jax.Array, q_exp, km: jax.Array, k_exp,
                          vm: jax.Array, v_exp, p_bits: int, q_offset,
                          *, causal: bool, window=None):
    """Integer flash-attention forward oracle in exact f64.

    ``qm`` (B, Sq, KV, G, hd) and ``km``/``vm`` (B, Sk, KV, hd) are integer
    mantissas (logical, not limb planes); the softmax uses the **global**
    row max, which the kernel's running max reaches exactly for Sk within
    one 128 block — multi-block sweeps compare with a looser tolerance
    because the kernel quantizes P against the running (not final) max.
    Returns ``(o, lse)``: o (B, Sq, KV, G, hd) f32, lse (B, KV, G, Sq).
    """
    q, k, v = _f64(qm), _f64(km), _f64(vm)
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    sc = 1.0 / np.sqrt(hd)
    qs = 2.0 ** float(np.asarray(q_exp))
    ks = 2.0 ** float(np.asarray(k_exp))
    vs = 2.0 ** float(np.asarray(v_exp))
    s = np.einsum("bqhgd,bkhd->bhgqk", q, k) * (qs * ks * sc)
    okb = _attn_mask_ref(B, Sq, Sk, q_offset, causal, window)[:, None, None]
    s = np.where(okb, s, -1e30)
    m = s.max(-1, keepdims=True)
    p = np.where(okb, np.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    lim = float(2 ** (p_bits - 1) - 1)
    pm = np.clip(np.round(p * 2.0 ** (p_bits - 1)), -lim, lim)
    o = np.einsum("bhgqk,bkhd->bhgqd", pm, v) * (vs * 2.0 ** -(p_bits - 1))
    o = o / np.maximum(l, 1e-20)
    lse = m[..., 0] + np.log(np.maximum(l[..., 0], 1e-37))
    return (jnp.asarray(o.transpose(0, 3, 1, 2, 4), jnp.float32),
            jnp.asarray(lse, jnp.float32))


def int_attention_bwd_ref(qm: jax.Array, q_exp, km: jax.Array, k_exp,
                          vm: jax.Array, v_exp, gm: jax.Array, g_exp,
                          lse: jax.Array, delta: jax.Array, ds_exp,
                          p_bits: int, ds_bits: int, q_offset,
                          *, causal: bool, window=None, tile=None):
    """Integer flash-attention backward oracle: ``(dq, dk, dv)`` in f64.

    ``gm`` is the quantized dO mantissa (B, Sq, KV, G, hd); ``lse``
    (B, KV, G, Sq) and ``delta`` (B, Sq, KV, G) are the forward-saved rows
    (delta = rowsum of the RAW upstream grad times O); ``ds_exp`` is the
    bound-derived dS scale exponent, or None with ``tile = (bq, bk)``: each
    tile of ``bq`` queries of one head by ``bk`` keys then takes the frexp
    exponent of its largest |dS|, less ``ds_bits - 1``.  P and dS quantize
    exactly as the kernels do — same clips, same exponents.
    """
    q, k, v, g = _f64(qm), _f64(km), _f64(vm), _f64(gm)
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    sc = 1.0 / np.sqrt(hd)
    qs = 2.0 ** float(np.asarray(q_exp))
    ks = 2.0 ** float(np.asarray(k_exp))
    vs = 2.0 ** float(np.asarray(v_exp))
    gs = 2.0 ** float(np.asarray(g_exp))
    s = np.einsum("bqhgd,bkhd->bhgqk", q, k) * (qs * ks * sc)
    okb = _attn_mask_ref(B, Sq, Sk, q_offset, causal, window)[:, None, None]
    s = np.where(okb, s, -1e30)
    p = np.where(okb, np.exp(s - _f64(lse)[..., None]), 0.0)
    plim = float(2 ** (p_bits - 1) - 1)
    pm = np.clip(np.round(p * 2.0 ** (p_bits - 1)), -plim, plim)
    dv = np.einsum("bhgqk,bqhgd->bkhd", pm, g) * (gs * 2.0 ** -(p_bits - 1))
    dp = np.einsum("bqhgd,bkhd->bhgqk", g, v) * (gs * vs)
    dl = _f64(delta).transpose(0, 2, 3, 1)[..., None]
    ds = p * (dp - dl)
    if ds_exp is None:
        bq, bk = tile
        nq, nk = -(-Sq // bq), -(-Sk // bk)
        a = np.zeros(ds.shape[:3] + (nq * bq, nk * bk))
        a[..., :Sq, :Sk] = np.abs(ds)
        a = a.reshape(ds.shape[:3] + (nq, bq, nk, bk)).max(axis=(4, 6))
        e = np.frexp(a)[1] - (ds_bits - 1)
        dss = 2.0 ** np.repeat(np.repeat(e, bq, axis=3), bk, axis=4)[
            ..., :Sq, :Sk]
    else:
        dss = 2.0 ** float(np.asarray(ds_exp))
    dlim = float(2 ** (ds_bits - 1) - 1)
    dsm = np.clip(np.round(ds / dss), -dlim, dlim) * dss
    dq = np.einsum("bhgqk,bkhd->bqhgd", dsm, k) * (ks * sc)
    dk = np.einsum("bhgqk,bqhgd->bkhd", dsm, q) * (qs * sc)
    return (jnp.asarray(dq, jnp.float32), jnp.asarray(dk, jnp.float32),
            jnp.asarray(dv, jnp.float32))


# ===========================================================================
# iapprox oracles (core/iapprox.py) — the exact f64 functions each integer
# approximation targets.  tests/test_iapprox.py sweeps the full input domain
# of every op against these and pins the DESIGN.md §10 error-bound table.
# ===========================================================================

def i_exp_ref(x: jax.Array) -> jax.Array:
    """Exact ``exp`` on the clamped i_exp domain |x| <= 30."""
    return jnp.asarray(np.exp(np.clip(_f64(x), -30.0, 30.0)), jnp.float32)


def i_recip_ref(y: jax.Array) -> jax.Array:
    return jnp.asarray(1.0 / _f64(y), jnp.float32)


def i_rsqrt_ref(y: jax.Array) -> jax.Array:
    return jnp.asarray(1.0 / np.sqrt(_f64(y)), jnp.float32)


def i_sqrt_ref(y: jax.Array) -> jax.Array:
    return jnp.asarray(np.sqrt(np.maximum(_f64(y), 0.0)), jnp.float32)


def i_sigmoid_ref(x: jax.Array) -> jax.Array:
    return jnp.asarray(1.0 / (1.0 + np.exp(-_f64(x))), jnp.float32)


def i_tanh_ref(x: jax.Array) -> jax.Array:
    return jnp.asarray(np.tanh(_f64(x)), jnp.float32)


def i_gelu_ref(x: jax.Array) -> jax.Array:
    """tanh-form GeLU in exact f64 — the function ``jax.nn.gelu``
    (approximate=True) computes, which is what i_gelu replaces."""
    x = _f64(x)
    u = np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)
    return jnp.asarray(0.5 * x * (1.0 + np.tanh(u)), jnp.float32)


def i_silu_ref(x: jax.Array) -> jax.Array:
    x = _f64(x)
    return jnp.asarray(x / (1.0 + np.exp(-x)), jnp.float32)


def i_softmax_ref(x: jax.Array, axis: int = -1) -> jax.Array:
    x = _f64(x)
    z = np.exp(x - x.max(axis=axis, keepdims=True))
    return jnp.asarray(z / z.sum(axis=axis, keepdims=True), jnp.float32)
