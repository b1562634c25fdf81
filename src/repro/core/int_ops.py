"""Integer-only layers: linear / embedding / layer-norm / rms-norm / conv.

Each layer performs BOTH forward propagation and gradient computation with
integer arithmetic on b-bit dynamic fixed-point mantissas (paper: Fig. 2 and
"Integer-only Layers"):

    forward:   q(X)·q(W)            — integer matmul, output scale = add
    backward:  dX = q(G)·q(W)ᵀ      — integer matmul
               dW = q(X)ᵀ·q(G)      — integer matmul, q(G) stochastically
                                      rounded (Assumption 2 unbiasedness)

Residuals saved for the backward pass are the *quantized* mantissas
(int8/int16), a 4x/2x activation-memory saving over FP32 (the chip
benchmark's ``peak_hbm_gib`` reads the device's peak).

``int_attention`` extends the same contract to the attention block: the two
quadratic contractions (QKᵀ and PV) and all four backward products run on
quantized mantissas — fused flash-attention Pallas kernels on the pallas
backend (kernels/int_attention.py, one forward and two backward
``pallas_call``s), an online-softmax XLA mirror on sim — while the softmax
itself (exp, running max, the 1/l normalizer) stays FP32 *inside* the
fused kernel, exactly like the norm layers' rsqrt (DESIGN.md §6).

Precision-critical ops stay FP32 per the paper: softmax, non-linear
activations, the rsqrt inside the normalization layers, and the optimizer
update.  When ``cfg.enabled`` is False every layer degrades to its exact FP32
reference implementation (the paper's baseline) — same code path for both.

PRNG: layers take an optional ``key``. When ``cfg.stochastic_grad`` and a key
is provided, backward gradient quantization uses stochastic rounding;
otherwise round-to-nearest (used at serve time, where there is no backward).

Backends: ``cfg.backend == "sim"`` runs the mantissa contractions through
XLA ``dot_general`` with the accumulator dtype picked by ``dfx.acc_dtype``;
``cfg.backend == "pallas"`` routes quantization (``quantize_pallas``, with
the stochastic-rounding noise ``u`` drawn from the layer's PRNG key so
Assumption 2 unbiasedness is preserved) and both matmul directions through
the Pallas kernels: forward ``q(X)·q(W)`` via ``dfx_matmul_tiled``, backward
``dX = q(G)·q(W)ᵀ`` / ``dW = q(X)ᵀ·q(G)`` via the transpose-aware
``dfx_matmul_tiled_nt`` / ``dfx_matmul_tiled_tn`` entry points — bit-exact
int32 limb accumulation at any supported bit-width (DESIGN.md §2).  On this
backend the matmul operands (activations, weights, gradients) are quantized
straight into stacked int8 **limb planes** (``limb_planes=True`` — the
balanced base-2⁷ digit split is fused into the quantize kernel) and each
matmul direction is ONE ``pallas_call`` covering every limb pair; the limb
planes are also what the custom-vjp residuals save, so the backward matmuls
reuse them with no re-splitting anywhere in the traced jaxpr.  The MoE
expert layer (``int_batched_linear``) uses the batched twins
(``dfx_matmul_tiled_batched{,_nt,_tn}``, ``quantize_pallas_batched``): the
expert axis rides a leading parallel grid dimension with an (E,)-vector
scale-exponent operand, so ONE kernel dispatch per direction covers all E
experts and all limb pairs — no Python loop over experts.  The norm layers
(``int_layernorm``, ``int_rmsnorm``) run forward AND backward through the
fused kernels in ``repro.kernels.int_norm`` (multi-output forwards whose
saved statistics are exactly what the kernel normalized with; backwards
computing dx plus per-block parameter-gradient partials — DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dfx
from repro.core import iapprox
from repro.core.qconfig import QuantConfig
from repro.kernels import ops as kops

Array = jax.Array


def _float0(x):
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


@jax.named_scope("quantize")
def _pallas_quantize(x: Array, bits: int, *, stochastic: bool = False,
                     key=None, limb_planes: bool = False) -> dfx.DfxTensor:
    """Linear fixed-point mapping via the Pallas quantize kernel.

    The max-abs exponent reduction stays in XLA (pass 1 of the two-pass
    structure, DESIGN.md §2); the shift-round-clip pass runs in the kernel.
    Stochastic rounding noise ``u`` is drawn from ``key`` here and fed to
    the kernel's noise input so gradient rounding stays unbiased.

    ``limb_planes=True`` (the matmul operand path) makes the kernel emit the
    stacked int8 limb planes directly — ``m`` is ``(L,) + x.shape`` and the
    balanced base-2⁷ digit split never appears as XLA arithmetic.
    """
    x = x.astype(jnp.float32)
    e = dfx._scale_exponent(x, None)
    exp = (e - (bits - 1)).astype(jnp.int32)
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim != 2 else x
    u = None
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        u = jax.random.uniform(key, x2.shape, dtype=jnp.float32)
    m = kops.quantize_pallas(x2, exp, bits, u=u, limb_planes=limb_planes)
    shape = (m.shape[0],) + x.shape if limb_planes else x.shape
    return dfx.DfxTensor(m=m.reshape(shape), exp=exp)


def _quantize(x: Array, bits: int, cfg: QuantConfig, *,
              stochastic: bool = False, key=None,
              reduce_axes=None, limb_planes: bool = False) -> dfx.DfxTensor:
    """Backend-routed per-tensor quantization (per-axis stays on sim).

    ``limb_planes`` only takes effect on the pallas route — the sim path
    always returns the logical mantissa it contracts in XLA.  Both routes
    run under the ``quantize`` named scope (``_pallas_quantize``,
    ``dfx.quantize``): the max-abs exponent, the rounding noise, the kernel
    and the limb split are told apart from their caller in a profile.
    """
    if cfg.backend == "pallas" and reduce_axes is None:
        return _pallas_quantize(x, bits, stochastic=stochastic, key=key,
                                limb_planes=limb_planes)
    return dfx.quantize(x, bits, stochastic=stochastic, key=key,
                        reduce_axes=reduce_axes)


def _quant_grad(g: Array, cfg: QuantConfig, key,
                limb_planes: bool = False) -> dfx.DfxTensor:
    stoch = cfg.stochastic_grad and key is not None
    return _quantize(g, cfg.grad_bits, cfg, stochastic=stoch, key=key,
                     limb_planes=limb_planes)


# =========================================================================
# Linear
# =========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def int_linear(x: Array, w: Array, b: Optional[Array], key, cfg: QuantConfig) -> Array:
    """``y = x @ w (+ b)`` with integer forward and integer backward.

    x: (..., K), w: (K, N), b: (N,) or None. ``key`` may be None (RN rounding).
    """
    y, _ = _int_linear_fwd(x, w, b, key, cfg)
    return y


def _int_linear_fwd(x, w, b, key, cfg: QuantConfig):
    if not cfg.enabled:
        y = jnp.einsum("...k,kn->...n", x, w)
        if b is not None:
            y = y + b
        return y, (x, w, b is not None, key)
    kf = None
    if cfg.stochastic_fwd and key is not None:
        key, kf = jax.random.split(key)
    # On pallas the quantize kernel emits stacked limb planes directly (and
    # those planes are the residuals the backward matmuls reuse — the digit
    # split never runs as XLA arithmetic, forward or backward).
    qx = _quantize(x, cfg.act_bits, cfg, stochastic=kf is not None, key=kf,
                   limb_planes=True)
    qw = _quantize(w, cfg.weight_bits, cfg, limb_planes=True)
    if cfg.backend == "pallas":
        # kernel path: batch dims flattened to the 2-D (M, K) @ (K, N)
        # tiling, limb planes riding the leading axis
        y2 = kops.dfx_matmul_tiled(
            qx.m.reshape(qx.m.shape[0], -1, x.shape[-1]), qx.exp,
            cfg.act_bits, qw.m, qw.exp, cfg.weight_bits)
        y = y2.reshape(x.shape[:-1] + (w.shape[-1],))
    else:
        y = dfx.dfx_matmul(qx, qw, bits=(cfg.act_bits, cfg.weight_bits))
    if b is not None:
        y = y + b  # O(N) bias add, not compute-intensive (kept FP32)
    return y, (qx, qw, b is not None, key)


def _int_linear_bwd(cfg: QuantConfig, res, g):
    if not cfg.enabled:
        x, w, has_b, key = res
        dx = jnp.einsum("...n,kn->...k", g, w)
        dw = jnp.einsum("...k,...n->kn", x, g)
        db = g.reshape(-1, g.shape[-1]).sum(0) if has_b else None
        return dx, dw, db, _float0(key) if key is not None else None

    qx, qw, has_b, key = res
    qg = _quant_grad(g, cfg, key, limb_planes=True)
    if cfg.backend == "pallas":
        # both backward products through the transpose-aware kernel entry
        # points; operands stay in forward layout (kernel-side transpose)
        # and arrive as the limb planes saved/emitted by the quantize kernel
        N = g.shape[-1]
        K = qx.m.shape[-1]
        g2 = qg.m.reshape(qg.m.shape[0], -1, N)
        dx2 = kops.dfx_matmul_tiled_nt(g2, qg.exp, cfg.grad_bits,
                                       qw.m, qw.exp, cfg.weight_bits)
        dx = dx2.reshape(g.shape[:-1] + (K,))
        dw = kops.dfx_matmul_tiled_tn(
            qx.m.reshape(qx.m.shape[0], -1, K), qx.exp, cfg.act_bits,
            g2, qg.exp, cfg.grad_bits)
    else:
        # dX = q(G) · q(W)ᵀ  — integer matmul (contract N)
        nd = qg.m.ndim
        dx = dfx.dfx_dot_general(qg, qw, (((nd - 1,), (1,)), ((), ())),
                                 bits=(cfg.grad_bits, cfg.weight_bits))
        # dW = q(X)ᵀ · q(G) — integer matmul (contract all batch dims)
        batch_axes = tuple(range(nd - 1))
        dw = dfx.dfx_dot_general(qx, qg, ((batch_axes, batch_axes), ((), ())),
                                 bits=(cfg.act_bits, cfg.grad_bits))
    db = g.reshape(-1, g.shape[-1]).sum(0) if has_b else None
    return dx, dw, db, _float0(key) if key is not None else None


int_linear.defvjp(_int_linear_fwd, _int_linear_bwd)


# =========================================================================
# Batched (per-expert) linear — MoE expert FFNs with per-expert DFX scales
# =========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def int_batched_linear(x: Array, w: Array, key, cfg: QuantConfig) -> Array:
    """``y[e] = x[e] @ w[e]`` with integer fwd/bwd and per-expert scales.

    x: (E, C, K), w: (E, K, N) -> (E, C, N).
    """
    y, _ = _int_blinear_fwd(x, w, key, cfg)
    return y


_BATCH_DN = (((2,), (1,)), ((0,), (0,)))          # contract K, batch E


def _int_blinear_fwd(x, w, key, cfg: QuantConfig):
    if not cfg.enabled:
        return jnp.einsum("eck,ekn->ecn", x, w), (x, w, key)
    kf = None
    if cfg.stochastic_fwd and key is not None:
        key, kf = jax.random.split(key)
    if cfg.backend == "pallas":
        qx = _stacked_pallas_quantize(x, cfg.act_bits,
                                      stochastic=kf is not None, key=kf,
                                      limb_planes=True)
        qw = _stacked_pallas_quantize(w, cfg.weight_bits, limb_planes=True)
        y = kops.dfx_matmul_tiled_batched(qx.m, qx.exp, cfg.act_bits,
                                          qw.m, qw.exp, cfg.weight_bits)
        return y, (qx, qw, key)
    qx = dfx.quantize(x, cfg.act_bits, stochastic=kf is not None, key=kf,
                      reduce_axes=(1, 2))                     # scale per expert
    qw = dfx.quantize(w, cfg.weight_bits, reduce_axes=(1, 2))
    y = _batched_dfx_dot(qx, qw, _BATCH_DN)
    return y, (qx, qw, key)


@jax.named_scope("quantize")
def _stacked_pallas_quantize(x: Array, bits: int, *, stochastic: bool = False,
                             key=None,
                             limb_planes: bool = False) -> dfx.DfxTensor:
    """Per-expert (leading-axis) pallas quantization with per-expert scales.

    Mirrors ``dfx.quantize(..., reduce_axes=(1, 2))``: each expert slice gets
    its own scale exponent (pass 1, an XLA max-abs reduce over the trailing
    axes); the shift-round-clip pass is ONE grouped-scale kernel launch for
    all E experts (``quantize_pallas_batched``, expert axis on the grid).
    Exponents are (E, 1, 1) so the sim/pallas residual layouts match;
    ``limb_planes=True`` (the matmul operand path) makes ``m`` the
    plane-major ``(L,) + x.shape`` int8 stack the batched matmul kernels
    consume, with the digit split fused into the same launch.  Stochastic
    noise is a single draw over the full stack — bit-identical to the sim
    path under the same key.
    """
    x = x.astype(jnp.float32)
    E = x.shape[0]
    e = dfx._scale_exponent(x, tuple(range(1, x.ndim)))
    exp = (e - (bits - 1)).astype(jnp.int32)                  # (E, 1, ..., 1)
    x3 = x.reshape(E, -1, x.shape[-1])
    u = None
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        u = jax.random.uniform(key, x3.shape, dtype=jnp.float32)
    m = kops.quantize_pallas_batched(x3, exp, bits, u=u,
                                     limb_planes=limb_planes)
    shape = (m.shape[0],) + x.shape if limb_planes else x.shape
    return dfx.DfxTensor(m=m.reshape(shape),
                         exp=exp.reshape((E,) + (1,) * (x.ndim - 1)))


def _batched_dfx_dot(a: dfx.DfxTensor, b: dfx.DfxTensor, dn) -> Array:
    prod = jax.lax.dot_general(a.m.astype(jnp.float32), b.m.astype(jnp.float32),
                               dimension_numbers=dn,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    out_exp = (a.exp + b.exp).astype(jnp.float32)             # (E, 1, 1)
    return prod * jnp.exp2(out_exp.reshape(-1, 1, 1))


def _int_blinear_bwd(cfg: QuantConfig, res, g):
    if not cfg.enabled:
        x, w, key = res
        dx = jnp.einsum("ecn,ekn->eck", g, w)
        dw = jnp.einsum("eck,ecn->ekn", x, g)
        return dx, dw, _float0(key) if key is not None else None
    qx, qw, key = res
    stoch = cfg.stochastic_grad and key is not None
    if cfg.backend == "pallas":
        qg = _stacked_pallas_quantize(g, cfg.grad_bits, stochastic=stoch,
                                      key=key, limb_planes=True)
        # dX[e] = G[e]·W[e]ᵀ (NT), dW[e] = X[e]ᵀ·G[e] (TN) — ONE batched
        # kernel dispatch per direction covers every expert and limb pair
        dx = kops.dfx_matmul_tiled_batched_nt(qg.m, qg.exp, cfg.grad_bits,
                                              qw.m, qw.exp, cfg.weight_bits)
        dw = kops.dfx_matmul_tiled_batched_tn(qx.m, qx.exp, cfg.act_bits,
                                              qg.m, qg.exp, cfg.grad_bits)
        return dx, dw, _float0(key) if key is not None else None
    qg = dfx.quantize(g, cfg.grad_bits, stochastic=stoch, key=key,
                      reduce_axes=(1, 2))
    # dX[e] = G[e] · W[e]ᵀ ; dW[e] = X[e]ᵀ · G[e] — integer batched matmuls
    dx = _batched_dfx_dot(qg, qw, (((2,), (2,)), ((0,), (0,))))
    dw = _batched_dfx_dot(qx, qg, (((1,), (1,)), ((0,), (0,))))
    return dx, dw, _float0(key) if key is not None else None


int_batched_linear.defvjp(_int_blinear_fwd, _int_blinear_bwd)


# =========================================================================
# Grouped (sorted-rows) linear — an expert share's SwiGLU products, drop-free
# =========================================================================
#
# Rows are (token, choice) pairs sorted by expert, group ``g`` in
# ``[offsets[g], offsets[g+1])``, each group padded with zero rows to a
# multiple of the row tile ``tm`` and at least one tile long; rows past
# ``offsets[G]`` are zero.  Each group's rows take their own DFX exponent,
# as ``int_batched_linear`` gives each expert's slab its own.

def row_groups(offsets: Array, rows: int) -> Array:
    """(rows,) group of every row: ``G`` past the used rows."""
    r = jnp.arange(rows, dtype=jnp.int32)
    return jnp.sum(r[:, None] >= offsets[None, 1:], axis=1).astype(jnp.int32)


@jax.named_scope("quantize")
def _grouped_quantize(x: Array, gid: Array, groups: int, bits: int,
                      cfg: QuantConfig, *, stochastic: bool = False,
                      key=None) -> dfx.DfxTensor:
    """Per-group quantization of sorted rows: ``exp`` is (G,).

    The rows are scaled by their group's ``2**-exp`` in XLA (exact: a power
    of two) and rounded at exponent 0, on pallas by the quantize kernel as
    limb planes, on sim in XLA — the same bits as quantizing each group's
    rows at its own exponent.
    """
    x = x.astype(jnp.float32)
    absmax = jax.ops.segment_max(jnp.max(jnp.abs(x), axis=-1), gid,
                                 num_segments=groups + 1)[:groups]
    _, e = jnp.frexp(absmax)
    exp = (jnp.where(absmax > 0, e, 0) - (bits - 1)).astype(jnp.int32)
    row_exp = jnp.concatenate([exp, jnp.zeros((1,), jnp.int32)])[gid]
    xs = x * jnp.exp2(-row_exp.astype(jnp.float32))[:, None]
    u = None
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        u = jax.random.uniform(key, xs.shape, dtype=jnp.float32)
    if cfg.backend == "pallas":
        m = kops.quantize_pallas(xs, jnp.int32(0), bits, u=u,
                                 limb_planes=True)
    else:
        y = jnp.floor(xs + u) if stochastic else jnp.round(xs)
        lim = float(2 ** (bits - 1) - 1)
        m = jnp.clip(y, -lim, lim).astype(dfx.storage_dtype(bits))
    return dfx.DfxTensor(m=m, exp=exp)


def _grouped_sim_dot(a: dfx.DfxTensor, b: dfx.DfxTensor, gid: Array,
                     dims) -> Array:
    """Sim path: each group's rows times its expert's matrix, at the sum of
    the two exponents; rows of other groups masked out."""
    out = 0.0
    for g in range(b.m.shape[0]):
        prod = jax.lax.dot_general(
            a.m.astype(jnp.float32), b.m[g].astype(jnp.float32),
            (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        scale = jnp.exp2((a.exp[g] + jnp.reshape(b.exp, (-1,))[g]).astype(
            jnp.float32))
        out = out + jnp.where((gid == g)[:, None], prod * scale, 0.0)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def int_grouped_linear(x: Array, w: Array, offsets: Array, key,
                       cfg: QuantConfig, tm: int) -> Array:
    """``y[r] = x[r] @ w[g]`` for every row ``r`` of group ``g``, integer
    forward and backward, one DFX exponent per group.

    x: (M, K) rows sorted by group, w: (G, K, N), offsets: (G+1,) int32
    (see above; ``tm`` the row tile each group is padded to).  Returns
    (M, N), zero past ``offsets[G]``.
    """
    y, _ = _int_glinear_fwd(x, w, offsets, key, cfg, tm)
    return y


def _int_glinear_fwd(x, w, offsets, key, cfg: QuantConfig, tm: int):
    G = w.shape[0]
    gid = row_groups(offsets, x.shape[0])
    if not cfg.enabled:
        y = _grouped_sim_dot(dfx.DfxTensor(x, jnp.zeros((G,), jnp.int32)),
                             dfx.DfxTensor(w, jnp.zeros((G,), jnp.int32)),
                             gid, ((1,), (0,)))
        return y, (x, w, offsets, key)
    kf = None
    if cfg.stochastic_fwd and key is not None:
        key, kf = jax.random.split(key)
    qx = _grouped_quantize(x, gid, G, cfg.act_bits, cfg,
                           stochastic=kf is not None, key=kf)
    if cfg.backend == "pallas":
        qw = _stacked_pallas_quantize(w, cfg.weight_bits, limb_planes=True)
        y = kops.dfx_matmul_grouped(qx.m, qx.exp, cfg.act_bits, qw.m,
                                    qw.exp, cfg.weight_bits, offsets, tm)
    else:
        qw = dfx.quantize(w, cfg.weight_bits, reduce_axes=(1, 2))
        y = _grouped_sim_dot(qx, qw, gid, ((1,), (0,)))
    return y, (qx, qw, offsets, key)


def _int_glinear_bwd(cfg: QuantConfig, tm: int, res, g):
    if not cfg.enabled:
        x, w, offsets, key = res
        G = w.shape[0]
        gid = row_groups(offsets, x.shape[0])
        z = jnp.zeros((G,), jnp.int32)
        dx = _grouped_sim_dot(dfx.DfxTensor(g, z), dfx.DfxTensor(w, z), gid,
                              ((1,), (1,)))
        dw = jnp.stack([jnp.einsum("mk,mn->kn",
                                   jnp.where((gid == e)[:, None], x, 0.0), g)
                        for e in range(G)])
        return dx, dw, _float0(offsets), _float0(key) if key is not None \
            else None
    qx, qw, offsets, key = res
    G = qw.m.shape[-3]
    gid = row_groups(offsets, g.shape[0])
    stoch = cfg.stochastic_grad and key is not None
    qg = _grouped_quantize(g, gid, G, cfg.grad_bits, cfg, stochastic=stoch,
                           key=key)
    if cfg.backend == "pallas":
        dx = kops.dfx_matmul_grouped_nt(qg.m, qg.exp, cfg.grad_bits,
                                        qw.m, qw.exp, cfg.weight_bits,
                                        offsets, tm)
        dw = kops.dfx_matmul_grouped_tn(qx.m, qx.exp, cfg.act_bits,
                                        qg.m, qg.exp, cfg.grad_bits,
                                        offsets, tm)
    else:
        dx = _grouped_sim_dot(qg, qw, gid, ((1,), (1,)))
        dw = jnp.stack([
            jax.lax.dot_general(
                jnp.where((gid == e)[:, None], qx.m, 0).astype(jnp.float32),
                qg.m.astype(jnp.float32), (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            * jnp.exp2((qx.exp[e] + qg.exp[e]).astype(jnp.float32))
            for e in range(G)])
    return dx, dw, _float0(offsets), _float0(key) if key is not None else None


int_grouped_linear.defvjp(_int_glinear_fwd, _int_glinear_bwd)


# =========================================================================
# Embedding
# =========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def int_embedding(table: Array, ids: Array, key, cfg: QuantConfig) -> Array:
    """Embedding lookup from a b-bit quantized table; integer scatter-add bwd."""
    y, _ = _int_embedding_fwd(table, ids, key, cfg)
    return y


def _int_embedding_fwd(table, ids, key, cfg: QuantConfig):
    if not cfg.enabled:
        return table[ids], (table.shape, ids, key)
    # backend-routed: QuantConfig(backend="pallas") quantizes the table
    # through the Pallas kernel like every other integer layer
    qt = _quantize(table, cfg.weight_bits, cfg)
    # Gather integer mantissas, then inverse-map (a gather is index movement,
    # integer end-to-end).
    y = qt.m[ids].astype(jnp.float32) * jnp.exp2(qt.exp.astype(jnp.float32))
    return y, (table.shape, ids, key)


def _int_embedding_bwd(cfg: QuantConfig, res, g):
    table_shape, ids, key = res
    if not cfg.enabled:
        gq = g
    else:
        gq = dfx.dequantize(_quant_grad(g, cfg, key))
    dt = jnp.zeros(table_shape, jnp.float32).at[ids].add(gq)
    return (dt, _float0(ids), _float0(key) if key is not None else None)


int_embedding.defvjp(_int_embedding_fwd, _int_embedding_bwd)


# =========================================================================
# Layer norm (and RMS norm)
# =========================================================================
# Backend semantics of the normalization reductions:
#
# * pallas — forward AND backward are fused kernels over the integer
#   mantissas (kernels/int_norm.py).  The forward moment sums are exact
#   int32-limb accumulations; the multi-output forward returns the
#   value-domain (mu, rstd) it actually normalized with, and the backward
#   kernel rebuilds xn from those residuals (bit-identical to the forward's
#   xn) and computes dx plus per-block dgamma/dbeta partials in-kernel —
#   dbeta's row sums are exact int32 over the gradient mantissas; the only
#   XLA epilogue is the small cross-block partial combine.  The upstream
#   gradient is quantized through the quantize kernel first.
# * sim — the same reductions as value-domain FP32 reductions over the
#   *quantized* (integer-valued, but FP32-stored) tensors: two-pass
#   mean/var forward, XLA sums backward.  Integer-valued operands, float
#   arithmetic — parity with pallas is bounded by f32 rounding, not exact.
#
# The rsqrt is the paper's kept op (precision-critical, same category as
# softmax); under ``cfg.kept_ops == "integer"`` it swaps for the fixed-point
# Newton ``iapprox.i_rsqrt`` (DESIGN.md §10) — in-kernel on pallas, the same
# XLA form on sim.  The backward kernels consume the forward-saved rstd, so
# the swap is forward-only.  Both layers honor cfg.stochastic_fwd with the same key-split
# contract as the linear layers (activation noise from the first split,
# grad-quantization noise from the remainder; bit-identical across backends
# under the same key).

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def int_layernorm(x: Array, gamma: Array, beta: Array, key,
                  cfg: QuantConfig, eps: float = 1e-5) -> Array:
    y, _ = _int_ln_fwd(x, gamma, beta, key, cfg, eps)
    return y


def _int_ln_fwd(x, gamma, beta, key, cfg: QuantConfig, eps):
    ik = cfg.enabled and cfg.kept_ops == "integer"
    if cfg.enabled:
        kf = None
        if cfg.stochastic_fwd and key is not None:
            key, kf = jax.random.split(key)
        xq = _quantize(x, cfg.act_bits, cfg, stochastic=kf is not None, key=kf)
        gv = dfx.dequantize(_quantize(gamma, cfg.weight_bits, cfg))
        if cfg.backend == "pallas":
            D = x.shape[-1]
            y, mu, rstd = kops.layernorm_pallas(xq.m.reshape(-1, D), xq.exp,
                                                gv, beta, eps=eps,
                                                integer_rsqrt=ik)
            # the residual statistics ARE the kernel's outputs — the exact
            # (mu, rstd) it normalized with, not a value-domain recompute
            lead = x.shape[:-1]
            return (y.reshape(x.shape),
                    (xq, gv, rstd.reshape(lead + (1,)),
                     mu.reshape(lead + (1,)), key))
        xv = dfx.dequantize(xq)
        res_x = xq
    else:
        xv, gv = x, gamma
        res_x = x
    mu = jnp.mean(xv, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xv - mu), axis=-1, keepdims=True)
    rstd = (iapprox.i_rsqrt(var + eps) if ik    # kept op: FP32 or i_rsqrt
            else jax.lax.rsqrt(var + eps))
    xn = (xv - mu) * rstd
    y = xn * gv + beta
    return y, (res_x, gv, rstd, mu, key)


def _int_ln_bwd(cfg: QuantConfig, eps, res, g):
    xr, gv, rstd, mu, key = res
    if cfg.enabled and cfg.backend == "pallas":
        qg = _quant_grad(g, cfg, key)
        D = g.shape[-1]
        dx, dgamma, dbeta = kops.layernorm_bwd_pallas(
            xr.m.reshape(-1, D), xr.exp, qg.m.reshape(-1, D), qg.exp,
            gv, mu.reshape(-1, 1), rstd.reshape(-1, 1))
        return (dx.reshape(g.shape), dgamma, dbeta,
                _float0(key) if key is not None else None)
    if cfg.enabled:
        xv = dfx.dequantize(xr)
        gq = dfx.dequantize(_quant_grad(g, cfg, key))
    else:
        xv, gq = xr, g
    xn = (xv - mu) * rstd
    dgamma = jnp.sum(gq * xn, axis=tuple(range(gq.ndim - 1)))
    dbeta = jnp.sum(gq, axis=tuple(range(gq.ndim - 1)))
    gg = gq * gv
    mean_gg = jnp.mean(gg, axis=-1, keepdims=True)
    mean_ggxn = jnp.mean(gg * xn, axis=-1, keepdims=True)
    dx = rstd * (gg - mean_gg - xn * mean_ggxn)
    return dx, dgamma, dbeta, _float0(key) if key is not None else None


int_layernorm.defvjp(_int_ln_fwd, _int_ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def int_rmsnorm(x: Array, gamma: Array, key, cfg: QuantConfig,
                eps: float = 1e-6) -> Array:
    y, _ = _int_rms_fwd(x, gamma, key, cfg, eps)
    return y


def _int_rms_fwd(x, gamma, key, cfg: QuantConfig, eps):
    ik = cfg.enabled and cfg.kept_ops == "integer"
    if cfg.enabled:
        kf = None
        if cfg.stochastic_fwd and key is not None:
            key, kf = jax.random.split(key)
        xq = _quantize(x, cfg.act_bits, cfg, stochastic=kf is not None, key=kf)
        gv = dfx.dequantize(_quantize(gamma, cfg.weight_bits, cfg))
        if cfg.backend == "pallas":
            D = x.shape[-1]
            y, rstd = kops.rmsnorm_pallas(xq.m.reshape(-1, D), xq.exp, gv,
                                          eps=eps, integer_rsqrt=ik)
            return (y.reshape(x.shape),
                    (xq, gv, rstd.reshape(x.shape[:-1] + (1,)), key))
        xv = dfx.dequantize(xq)
        res_x = xq
    else:
        xv, gv = x, gamma
        res_x = x
    ms = jnp.mean(jnp.square(xv), axis=-1, keepdims=True)
    rstd = (iapprox.i_rsqrt(ms + eps) if ik
            else jax.lax.rsqrt(ms + eps))
    y = xv * rstd * gv
    return y, (res_x, gv, rstd, key)


def _int_rms_bwd(cfg: QuantConfig, eps, res, g):
    xr, gv, rstd, key = res
    if cfg.enabled and cfg.backend == "pallas":
        qg = _quant_grad(g, cfg, key)
        D = g.shape[-1]
        dx, dgamma = kops.rmsnorm_bwd_pallas(
            xr.m.reshape(-1, D), xr.exp, qg.m.reshape(-1, D), qg.exp,
            gv, rstd.reshape(-1, 1))
        return (dx.reshape(g.shape), dgamma,
                _float0(key) if key is not None else None)
    if cfg.enabled:
        xv = dfx.dequantize(xr)
        gq = dfx.dequantize(_quant_grad(g, cfg, key))
    else:
        xv, gq = xr, g
    xn = xv * rstd
    dgamma = jnp.sum(gq * xn, axis=tuple(range(gq.ndim - 1)))
    gg = gq * gv
    mean_ggxn = jnp.mean(gg * xn, axis=-1, keepdims=True)
    dx = rstd * (gg - xn * mean_ggxn)
    return dx, dgamma, _float0(key) if key is not None else None


int_rmsnorm.defvjp(_int_rms_fwd, _int_rms_bwd)


# =========================================================================
# Kept-op activations — GeLU / SiLU / tanh (DESIGN.md §10)
# =========================================================================
# The paper keeps the nonlinearities in FP32; ``kept_ops="integer"`` swaps
# each for its iapprox fixed-point form.  There is NO pallas_call here — the
# swap must add zero traced dispatches (the acceptance pins the dispatch
# baseline), and iapprox is deterministic integer arithmetic plus exact
# power-of-two float scalings, so the XLA trace is the bit-identical form
# both backends run.  The integer branch carries a custom_vjp whose backward
# is built from the same iapprox ops, so the *backward* jaxpr is QL008-clean
# too (no tanh/logistic/erf primitives from autodiff).

_ACT_FNS = {
    # kind -> (fp32 form, integer forward, integer derivative)
    "gelu": (jax.nn.gelu, iapprox.i_gelu, iapprox.d_gelu),
    "silu": (jax.nn.silu, iapprox.i_silu, iapprox.d_silu),
    "tanh": (jnp.tanh, iapprox.i_tanh, iapprox.d_tanh),
}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int_act(kind: str, x: Array) -> Array:
    return _ACT_FNS[kind][1](x)


def _int_act_fwd(kind: str, x):
    return _ACT_FNS[kind][1](x), x


def _int_act_bwd(kind: str, x, g):
    return (g * _ACT_FNS[kind][2](x),)


_int_act.defvjp(_int_act_fwd, _int_act_bwd)


def int_activation(x: Array, cfg: QuantConfig, kind: str) -> Array:
    """Policy-routed activation: ``kind`` in {"gelu", "silu", "tanh"}.

    ``cfg`` is the resolved leaf for the call site's scope path (e.g.
    ``blocks.3.mlp.act``); with ``cfg.kept_ops == "fp32"`` (or quantization
    disabled) this IS the stock float op — same primitive, natively
    differentiable — so FP32 baselines are untouched.  Under an enabled
    config with ``kept_ops="integer"`` the iapprox form runs instead, with
    an iapprox-built backward."""
    if kind not in _ACT_FNS:
        raise KeyError(f"int_activation kind {kind!r} not in "
                       f"{sorted(_ACT_FNS)}")
    if cfg.enabled and cfg.kept_ops == "integer":
        return _int_act(kind, x)
    return _ACT_FNS[kind][0](x)


def int_softmax(x: Array, cfg: QuantConfig, axis: int = -1) -> Array:
    """Policy-routed softmax for out-of-attention call sites (the MoE
    router gate).  Attention's softmax lives inside the flash kernels and
    swaps its exp there; this covers the standalone form: under an enabled
    config with ``kept_ops="integer"`` the row softmax runs as ``i_exp`` +
    the fixed-point reciprocal normalizer (rows sum to 1 within the i_recip
    bound, DESIGN.md §10), else the stock float op."""
    if cfg.enabled and cfg.kept_ops == "integer":
        return iapprox.i_softmax(x, axis=axis)
    return jax.nn.softmax(x, axis=axis)


# =========================================================================
# Attention — fused integer flash attention (DESIGN.md §6)
# =========================================================================
# Value semantics shared by both backends (and the f64 oracles in
# kernels/ref.py):
#
# * q, k quantize at ``cfg_qk.act_bits``; v (and the P mantissa) at
#   ``cfg_pv.act_bits`` — two QuantPolicy leaves, resolved per call site
#   ("blocks.*.attn.qk" / "...attn.pv"), so score and value precision tune
#   independently.
# * scores s = sc·(q·kᵀ) from the integer product; softmax in f32 with the
#   flash running max, masked columns exactly zero.  P quantizes at the
#   STATIC exponent -(p_bits-1) (p <= 1 by construction — no max pass); the
#   normalizer l accumulates the unquantized p (a kept op, like rsqrt).
# * backward (FA2): p rebuilt from the saved per-row lse; delta = rowsum of
#   the RAW upstream grad times o (an O(N·hd) XLA f32 reduce — kept op);
#   dS = p·(dp - delta) quantizes at a norm-derived exponent (see
#   ``_ds_exp`` — O(N·hd) row norms, no max pass over the S×S matrix) in a
#   call that is neither causal nor windowed, and in one that is at each
#   kernel tile's own DFX exponent (``_ds_tile_exp``), and dq/dk/dv are
#   integer products of the quantized planes.
#
# The sim forward mirrors the kernel's 128-wide chunked online softmax so
# the per-chunk P quantization (against the running, not global, max) agrees
# between backends; within one 128 block running max == global max and the
# f64 oracle comparison is tight.

def _attn_off(q_offset, B: int) -> Array:
    """(B,) int32 query offsets from a scalar or per-row ``q_offset``."""
    off = jnp.atleast_1d(jnp.asarray(q_offset)).astype(jnp.int32)
    return jnp.broadcast_to(off, (B,))


def _max_row_norm(x: Array) -> Array:
    """max over rows of ||x_row||_2 along the trailing (head) dim — f32
    scalar, O(N·hd)."""
    return jnp.sqrt(jnp.max(jnp.sum(
        jnp.square(x.astype(jnp.float32)), axis=-1)))


def _ds_exp(g_norm: Array, v_norm: Array, ds_bits: int) -> Array:
    """Norm-derived dS scale exponent (traced int32 scalar).

    dS = p·(dp - delta) with |dp_ij| <= ||dO_i||·||V_j|| (Cauchy–Schwarz),
    |delta_i| = |dO_i · o_i| <= ||dO_i||·max_j||V_j|| (o is a convex
    combination of V rows) and p <= 1, so |dS| <= 2·max||dO||·max||V||.
    Two O(N·hd) row-norm maxes — no pass over the S×S score matrix, and
    ~4–8 bits tighter than the static mantissa worst case 2^(gb+vb)·hd
    (which at 8-bit grads rounds every score gradient to zero).
    """
    bound = 2.0 * g_norm * v_norm
    e = jnp.ceil(jnp.log2(jnp.maximum(bound, 1e-30))) - (ds_bits - 1)
    return e.astype(jnp.int32)


def _ds_tile_exp(ds: Array, hd: int, ds_bits: int) -> Array:
    """Per-element f32 dS scale exponent of a causal or windowed call: the
    DFX exponent of the largest magnitude in each (bq, bk) tile of the
    kernels' rows layout (``kops._attn_dims``; a q tile is ``bq``
    consecutive queries of one head), as the kernels take it in-tile.
    ds: (B, KV, G, Sq, Sk)."""
    B, KV, G, Sq, Sk = ds.shape
    bq, sq_p, bk, sk_p, _ = kops._attn_dims(Sq, Sk, hd)
    a = jnp.pad(jnp.abs(ds), [(0, 0)] * 3 + [(0, sq_p - Sq), (0, sk_p - Sk)])
    a = jnp.max(a.reshape(B, KV, G, sq_p // bq, bq, sk_p // bk, bk),
                axis=(4, 6), keepdims=True)
    e = jnp.frexp(a)[1] - (ds_bits - 1)
    e = jnp.broadcast_to(e, (B, KV, G, sq_p // bq, bq, sk_p // bk, bk))
    return e.reshape(B, KV, G, sq_p, sk_p)[..., :Sq, :Sk].astype(jnp.float32)


def _sim_attention_fwd(qd: Array, kd: Array, vd: Array, off: Array,
                       p_bits: int, causal: bool, window,
                       integer_exp: bool = False):
    """XLA online-softmax forward on dequantized values, 128-wide chunks.

    ``integer_exp`` mirrors the pallas kernel's kept-ops swap: the chunked
    recurrence is unchanged, but p/alpha come from ``iapprox.i_exp`` and
    the final normalizer from ``iapprox.i_recip``."""
    _exp = iapprox.i_exp if integer_exp else jnp.exp
    B, Sq, KV, G, hd = qd.shape
    Sk = kd.shape[1]
    sc = 1.0 / float(hd) ** 0.5
    chunk = min(128, Sk)
    n = -(-Sk // chunk)
    pad = n * chunk - Sk
    kp = jnp.pad(kd, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(vd, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kp.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    qpos = off[:, None] + jnp.arange(Sq)                      # (B, Sq)
    lim = float(2 ** (p_bits - 1) - 1)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, j = xs
        kpos = j * chunk + jnp.arange(chunk)
        ok = jnp.broadcast_to(kpos < Sk, (B, Sq, chunk))
        if causal:
            ok = jnp.logical_and(ok, kpos[None, None, :] <= qpos[:, :, None])
        if window is not None:
            ok = jnp.logical_and(
                ok, kpos[None, None, :] > qpos[:, :, None] - window)
        okb = ok[:, None, None]                               # (B,1,1,Sq,ck)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qd, kb) * sc
        s = jnp.where(okb, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(okb, _exp(s - m_new), 0.0)
        alpha = _exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pm = jnp.clip(jnp.round(p * 2.0 ** (p_bits - 1)), -lim, lim)
        acc = acc * alpha + (jnp.einsum("bhgqk,bkhd->bhgqd", pm, vb)
                             * 2.0 ** -(p_bits - 1))
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, G, Sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq, 1), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(n)))
    if integer_exp:
        o = (acc * iapprox.i_recip(jnp.maximum(l, 1e-20))
             ).transpose(0, 3, 1, 2, 4)
    else:
        o = (acc / jnp.maximum(l, 1e-20)).transpose(0, 3, 1, 2, 4)
    lse = (m + jnp.log(jnp.maximum(l, 1e-37)))[..., 0]        # (B,KV,G,Sq)
    return o, lse


def _sim_attention_bwd(qd: Array, kd: Array, vd: Array, gd: Array,
                       lse: Array, delta: Array, ds_exp: Array, off: Array,
                       p_bits: int, ds_bits: int, causal: bool, window,
                       integer_exp: bool = False):
    """XLA backward on dequantized values — same quantization points as the
    kernels (P and dS clipped at their exponents; ``ds_exp`` None for a
    causal or windowed call, which takes each tile's own)."""
    _exp = iapprox.i_exp if integer_exp else jnp.exp
    B, Sq, KV, G, hd = qd.shape
    Sk = kd.shape[1]
    sc = 1.0 / float(hd) ** 0.5
    qpos = off[:, None] + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    ok = jnp.ones((B, Sq, Sk), bool)
    if causal:
        ok = jnp.logical_and(ok, kpos[None, None, :] <= qpos[:, :, None])
    if window is not None:
        ok = jnp.logical_and(ok, kpos[None, None, :] > qpos[:, :, None] - window)
    okb = ok[:, None, None]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qd, kd) * sc
    s = jnp.where(okb, s, -1e30)
    p = jnp.where(okb, _exp(s - lse[..., None]), 0.0)
    plim = float(2 ** (p_bits - 1) - 1)
    pm = jnp.clip(jnp.round(p * 2.0 ** (p_bits - 1)), -plim, plim)
    dv = (jnp.einsum("bhgqk,bqhgd->bkhd", pm, gd) * 2.0 ** -(p_bits - 1))
    dp = jnp.einsum("bqhgd,bkhd->bhgqk", gd, vd)
    dl = delta.transpose(0, 2, 3, 1)[..., None]
    ds = p * (dp - dl)
    dlim = float(2 ** (ds_bits - 1) - 1)
    if ds_exp is None:
        e = _ds_tile_exp(ds, hd, ds_bits)
        ds = jnp.clip(jnp.round(ds * jnp.exp2(-e)), -dlim, dlim) * jnp.exp2(e)
        dq = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kd) * sc
        dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qd) * sc
        return dq, dk, dv
    dss = jnp.exp2(ds_exp.astype(jnp.float32))
    dsm = jnp.clip(jnp.round(ds * jnp.exp2(-ds_exp.astype(jnp.float32))),
                   -dlim, dlim)
    dq = jnp.einsum("bhgqk,bkhd->bqhgd", dsm, kd) * dss * sc
    dk = jnp.einsum("bhgqk,bqhgd->bkhd", dsm, qd) * dss * sc
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def int_attention(q: Array, k: Array, v: Array, q_offset, key,
                  cfg_qk: QuantConfig, cfg_pv: QuantConfig,
                  causal: bool, window) -> Array:
    """Scaled-dot-product attention with integer fwd and bwd products.

    q: (B, Sq, KV, G, hd); k, v: (B, Sk, KV, hd) — GQA layout (G query
    heads per kv head).  ``q_offset`` is a scalar or (B,) int array of
    query positions (cache index at decode / chunked prefill; 0 in
    training); it is masked via ``kpos <= q_offset + i`` so one entry point
    serves training (Sq = Sk), decode (Sq = 1) and chunked prefill.
    Callers gate on ``cfg_qk.enabled`` — the FP32 path stays in
    models/blocks.py.  Returns (B, Sq, KV, G, hd) f32.
    """
    o, _ = _int_attention_fwd(q, k, v, q_offset, key, cfg_qk, cfg_pv,
                              causal, window)
    return o


def _int_attention_fwd(q, k, v, q_offset, key, cfg_qk: QuantConfig,
                       cfg_pv: QuantConfig, causal, window):
    off = _attn_off(q_offset, q.shape[0])
    kf = None
    if cfg_qk.stochastic_fwd and key is not None:
        key, kf = jax.random.split(key)
    kq = kk = kv = None
    if kf is not None:
        kq, kk, kv = jax.random.split(kf, 3)
    planes = cfg_qk.backend == "pallas"
    qq = _quantize(q, cfg_qk.act_bits, cfg_qk, stochastic=kf is not None,
                   key=kq, limb_planes=planes)
    qk = _quantize(k, cfg_qk.act_bits, cfg_qk, stochastic=kf is not None,
                   key=kk, limb_planes=planes)
    qv = _quantize(v, cfg_pv.act_bits, cfg_pv, stochastic=kf is not None,
                   key=kv, limb_planes=planes)
    p_bits = cfg_pv.act_bits
    iexp = cfg_qk.enabled and cfg_qk.kept_ops == "integer"
    if planes:
        o, lse = kops.attention_fwd(qq.m, qq.exp, qk.m, qk.exp, qv.m, qv.exp,
                                    off, p_bits, causal=causal, window=window,
                                    integer_exp=iexp)
    else:
        o, lse = _sim_attention_fwd(dfx.dequantize(qq), dfx.dequantize(qk),
                                    dfx.dequantize(qv), off, p_bits,
                                    causal, window, integer_exp=iexp)
    # residual for the bwd dS exponent of a call that is neither causal
    # nor windowed; the others take each tile's own
    v_norm = (None if causal or window is not None else _max_row_norm(v))
    return o, (qq, qk, qv, o, lse, v_norm, q_offset, off, key)


def _int_attention_bwd(cfg_qk: QuantConfig, cfg_pv: QuantConfig, causal,
                       window, res, g):
    qq, qk, qv, o, lse, v_norm, q_offset, off, key = res
    planes = cfg_qk.backend == "pallas"
    qg = _quant_grad(g, cfg_pv, key, limb_planes=planes)
    # delta = rowsum(dO ∘ O) over the RAW upstream grad — an O(N·hd) f32
    # reduce, a kept op like the softmax it linearizes
    delta = jnp.sum(g * o, axis=-1)                           # (B,Sq,KV,G)
    p_bits = cfg_pv.act_bits
    ds_bits = cfg_qk.grad_bits
    ds_exp = (None if v_norm is None
              else _ds_exp(_max_row_norm(g), v_norm, ds_bits))
    iexp = cfg_qk.enabled and cfg_qk.kept_ops == "integer"
    if planes:
        dq, dk, dv = kops.attention_bwd(
            qq.m, qq.exp, qk.m, qk.exp, qv.m, qv.exp, qg.m, qg.exp,
            lse, delta, ds_exp, off, p_bits, ds_bits,
            causal=causal, window=window, integer_exp=iexp)
    else:
        dq, dk, dv = _sim_attention_bwd(
            dfx.dequantize(qq), dfx.dequantize(qk), dfx.dequantize(qv),
            dfx.dequantize(qg), lse, delta, ds_exp, off,
            p_bits, ds_bits, causal, window, integer_exp=iexp)
    return (dq, dk, dv, _float0(q_offset),
            _float0(key) if key is not None else None)


int_attention.defvjp(_int_attention_fwd, _int_attention_bwd)


# =========================================================================
# Convolutions
# =========================================================================

def int_patch_embed(images: Array, w: Array, b: Optional[Array], key,
                    cfg: QuantConfig, patch: int) -> Array:
    """ViT patch embedding = non-overlapping conv = reshape + int_linear.

    images: (B, H, W, C); w: (patch*patch*C, D).
    """
    B, H, W, C = images.shape
    x = images.reshape(B, H // patch, patch, W // patch, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, (H // patch) * (W // patch), -1)
    return int_linear(x, w, b, key, cfg)


def int_conv1d_depthwise(x: Array, w: Array, key, cfg: QuantConfig) -> Array:
    """Causal depthwise conv1d (Mamba frontend), integer fwd/bwd.

    x: (B, L, D); w: (K, D). Implemented as a sum of K shifted integer
    elementwise products — each product is an integer multiply of two DFX
    mantissas, so forward and backward stay integer (backward follows from
    int_linear-style custom_vjp on the unrolled form).

    Honors ``cfg.stochastic_fwd`` with the linear layers' key-split
    contract: forward activation noise from the first split, gradient
    quantization from the remainder — bit-identical across backends under
    the same key (tests/test_conv_stochastic.py).
    """
    K = w.shape[0]
    if not cfg.enabled:
        pads = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        return sum(pads[:, k:k + x.shape[1], :] * w[k] for k in range(K))
    return _int_dwconv(x, w, key, cfg, K)


def _conv_digits(m) -> tuple:
    """Balanced base-2⁸ digit planes of an integer mantissa tensor:
    ``m = hi * 256 + lo`` with ``|lo| <= 128``, ``|hi| <= 128`` for 16-bit
    storage (identically zero for 8-bit).  Same split as the norm kernels'
    ``_exact_moments``, in XLA — the and-mask idiom avoids the ``rem``/
    ``div`` chain the integer-closure lint (QL001) rejects."""
    m32 = m.astype(jnp.int32)
    lo = ((m32 + 128) & 255) - 128
    hi = (m32 - lo) >> 8
    return hi, lo


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _int_dwconv(x, w, key, cfg: QuantConfig, K: int):
    y, _ = _int_dwconv_fwd(x, w, key, cfg, K)
    return y


def _int_dwconv_fwd(x, w, key, cfg: QuantConfig, K: int):
    # backend-routed quantization (the shifted elementwise products stay in
    # XLA — they are VPU work, not MXU work; only the mapping runs in-kernel)
    kf = None
    if cfg.stochastic_fwd and key is not None:
        key, kf = jax.random.split(key)
    qx = _quantize(x, cfg.act_bits, cfg, stochastic=kf is not None, key=kf)
    qw = _quantize(w, cfg.weight_bits, cfg)
    # Exact integer accumulation: split w into base-2⁸ digits so every
    # int32 partial is bounded by 2^(b_act-1) · 2^7 · K — f32 would round
    # past 2^24 already at b_act + b_w + log2 K > 25 (QL006).  The digit
    # planes are combined scaled in f32, one rounding at the output, same
    # contract as the limb-matmul kernel epilogue.
    xm = qx.m.astype(jnp.int32)
    wh, wl = _conv_digits(qw.m)
    pads = jnp.pad(xm, ((0, 0), (K - 1, 0), (0, 0)))
    sh = [pads[:, k:k + x.shape[1], :] for k in range(K)]
    acc_h = sum(s * wh[k] for k, s in enumerate(sh))
    acc_l = sum(s * wl[k] for k, s in enumerate(sh))
    acc = acc_h.astype(jnp.float32) * 256.0 + acc_l.astype(jnp.float32)
    scale = jnp.exp2((qx.exp + qw.exp).astype(jnp.float32))
    return acc * scale, (qx, qw, key)


def _int_dwconv_bwd(cfg: QuantConfig, K: int, res, g):
    qx, qw, key = res
    qg = _quant_grad(g, cfg, key)
    gm = qg.m.astype(jnp.int32)
    L = gm.shape[1]
    # dx[l] = sum_k g[l + K-1-k ... ] — correlate; w split as in forward
    wh, wl = _conv_digits(qw.m)
    gpad = jnp.pad(gm, ((0, 0), (0, K - 1), (0, 0)))
    gs = [gpad[:, (K - 1 - k):(K - 1 - k) + L, :] for k in range(K)]
    dx_h = sum(s * wh[k] for k, s in enumerate(gs))
    dx_l = sum(s * wl[k] for k, s in enumerate(gs))
    dxm = dx_h.astype(jnp.float32) * 256.0 + dx_l.astype(jnp.float32)
    dx = dxm * jnp.exp2((qg.exp + qw.exp).astype(jnp.float32))
    # dw reduces mantissa products over B·L — both operands digit-split so
    # each int32 partial is bounded by 2^14 · B·L (exact to B·L = 2^17),
    # where the old f32 sum rounded past 2^24 at b_act + b_grad + log2(B·L)
    # > 25 (the lint's QL006 site for the 8/16-bit presets).
    xh, xl = _conv_digits(qx.m)
    xh = jnp.pad(xh, ((0, 0), (K - 1, 0), (0, 0)))
    xl = jnp.pad(xl, ((0, 0), (K - 1, 0), (0, 0)))
    gh, gl = _conv_digits(gm)

    def _plane(a, b):
        return jnp.stack([jnp.sum(a[:, k:k + L, :] * b, axis=(0, 1))
                          for k in range(K)]).astype(jnp.float32)

    dwm = (_plane(xh, gh) * 65536.0
           + (_plane(xh, gl) + _plane(xl, gh)) * 256.0
           + _plane(xl, gl))
    dw = dwm * jnp.exp2((qx.exp + qg.exp).astype(jnp.float32))
    return dx, dw, _float0(key) if key is not None else None


_int_dwconv.defvjp(_int_dwconv_fwd, _int_dwconv_bwd)
