"""Mamba2 (SSD — state-space duality) block, integer-quantized projections.

The paper's rule maps onto SSM blocks as: in/out projections, the depthwise
convs, the gated RMS-norm and the embedding are **integer** (they are the
compute-intensive dense ops); the selective-state recurrence itself is
precision-critical (it is the SSM analogue of softmax) and stays FP32 —
recorded in DESIGN.md §4.

Projections are kept as separate matrices (z / x / BC / dt) instead of one
fused ``in_proj`` so each output dim shards cleanly on the ``model`` axis
(the fused concat dim would slice across segment boundaries under TP).

Implements the chunked SSD algorithm (Dao & Gu 2024, arXiv:2405.21060):
intra-chunk quadratic term + inter-chunk recurrent state passing via
``lax.scan``; plus the O(1)-state single-token decode step used by the
``long_500k`` shape.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import int_ops
from repro.core.qpolicy import QuantLike, ensure_scope
from repro.models.blocks import subkey, _init
from repro.models.config import ArchConfig

Array = jax.Array
Params = Dict[str, Any]


def _segsum(x: Array) -> Array:
    """out[..., i, j] = sum_{j < k <= i} x[..., k]; -inf above diagonal."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    ii = jnp.arange(Q)
    mask = ii[:, None] >= ii[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def mamba2_init(key, cfg: ArchConfig) -> Params:
    D, DI, N, NH = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    ks = jax.random.split(key, 6)
    return {
        "wz": _init(ks[0], (D, DI)),
        "wx": _init(ks[1], (D, DI)),
        "wBC": _init(ks[2], (D, 2 * N)),
        "wdt": _init(ks[3], (D, NH)),
        "conv_x": _init(ks[4], (cfg.ssm_conv, DI), scale=0.1),
        "conv_BC": _init(ks[5], (cfg.ssm_conv, 2 * N), scale=0.1),
        "A_log": jnp.log(jnp.arange(1, NH + 1, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((NH,)),
        "D_skip": jnp.ones((NH,)),
        "norm_g": jnp.ones((DI,)),
        "out_proj": _init(ks[0], (DI, D)),
    }


def ssd_chunked(x: Array, dt: Array, A: Array, B: Array, C: Array,
                chunk: int, init_state: Optional[Array] = None
                ) -> Tuple[Array, Array]:
    """Chunked SSD scan (FP32).

    x: (b, L, H, P), dt: (b, L, H), A: (H,), B/C: (b, L, N).
    Returns (y (b, L, H, P), final_state (b, H, P, N)).
    """
    b, L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q
    xr = x.reshape(b, nc, Q, H, P)
    dtr = dt.reshape(b, nc, Q, H)
    Br = B.reshape(b, nc, Q, N)
    Cr = C.reshape(b, nc, Q, N)
    dA = dtr * A[None, None, None, :]                      # (b, nc, Q, H) <= 0
    dA_cs = jnp.cumsum(dA, axis=2)

    # intra-chunk (quadratic within chunk)
    Lmat = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))      # (b, nc, H, Q, Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cr, Br)
    xdt = xr * dtr[..., None]
    y_diag = jnp.einsum("bchqk,bcqk,bckhp->bcqhp", Lmat, scores, xdt)

    # per-chunk end states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)    # (b, nc, Q, H)
    states = jnp.einsum("bckn,bckh,bckhp->bchpn", Br, decay_states, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])              # (b, nc, H)

    def scan_fn(s, inp):
        st_c, dec_c = inp
        s_in = s
        s = s * dec_c[..., None, None] + st_c
        return s, s_in

    s0 = init_state if init_state is not None else jnp.zeros((b, H, P, N), jnp.float32)
    # cheap elementwise recurrence (the heavy intra-chunk einsums above are
    # batched over chunks already)
    final_state, prev_states = jax.lax.scan(
        scan_fn, s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)      # (b, nc, H, P, N)

    state_decay_in = jnp.exp(dA_cs)
    y_off = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cr, state_decay_in, prev_states)
    y = (y_diag + y_off).reshape(b, L, H, P)
    return y, final_state


def ssd_decode_step(state: Array, x: Array, dt: Array, A: Array,
                    B: Array, C: Array) -> Tuple[Array, Array]:
    """One-token SSD update. state: (b,H,P,N); x: (b,H,P); dt: (b,H); B/C: (b,N)."""
    dA = jnp.exp(dt * A[None, :])
    dBx = jnp.einsum("bn,bh,bhp->bhpn", B, dt, x)
    state = state * dA[..., None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", C, state)
    return state, y


def mamba2_apply(
    p: Params, x: Array, cfg: ArchConfig, qcfg: QuantLike,
    key: Optional[Array],
    *,
    state: Optional[Tuple[Array, Array, Array]] = None,  # (ssm, conv_x, conv_BC)
    decode: bool = False,
) -> Tuple[Array, Optional[Tuple[Array, Array, Array]]]:
    """x: (B, S, D) -> (out, new_state).

    Integer ops: wz/wx/wBC/wdt/out_proj (int_linear), convs
    (int_conv1d_depthwise), gated norm (int_rmsnorm).  The three SiLU gates
    route through ``int_ops.int_activation`` under the scope leaves
    ``act.{conv_x, conv_BC, gate}`` so ``*.ssm.act`` is kept-ops tunable.
    FP32 by design (exempt from kept-ops swapping): softplus dt and the SSD
    ``selective_scan`` recurrence — never quantized, same category as the
    optimizer (see the scope docs in models/lm.py).
    """
    B_, S, D = x.shape
    DI, N, NH, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    sc = ensure_scope(qcfg)
    act = sc.child("act")
    z = int_ops.int_linear(x, p["wz"], None, subkey(key, 0), sc.leaf("wz"))
    xi = int_ops.int_linear(x, p["wx"], None, subkey(key, 1), sc.leaf("wx"))
    bc = int_ops.int_linear(x, p["wBC"], None, subkey(key, 2), sc.leaf("wBC"))
    dt = int_ops.int_linear(x, p["wdt"], None, subkey(key, 3), sc.leaf("wdt"))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if decode:
        assert S == 1
        ssm_s, cx_s, cbc_s = state
        cx = jnp.concatenate([cx_s, xi], axis=1)
        cbc = jnp.concatenate([cbc_s, bc], axis=1)
        xi = int_ops.int_activation(
            jnp.einsum("bkc,kc->bc", cx, p["conv_x"]),
            act.leaf("conv_x"), "silu")[:, None]
        bc = int_ops.int_activation(
            jnp.einsum("bkc,kc->bc", cbc, p["conv_BC"]),
            act.leaf("conv_BC"), "silu")[:, None]
        new_cx, new_cbc = cx[:, 1:], cbc[:, 1:]
    else:
        xi = int_ops.int_activation(int_ops.int_conv1d_depthwise(
            xi, p["conv_x"], subkey(key, 4), sc.leaf("conv_x")),
            act.leaf("conv_x"), "silu")
        bc = int_ops.int_activation(int_ops.int_conv1d_depthwise(
            bc, p["conv_BC"], subkey(key, 5), sc.leaf("conv_BC")),
            act.leaf("conv_BC"), "silu")

    xs = xi.reshape(B_, S, NH, P)
    Bmat, Cmat = bc[..., :N], bc[..., N:]

    if decode:
        new_ssm, y = ssd_decode_step(ssm_s, xs[:, 0], dt[:, 0], A,
                                     Bmat[:, 0], Cmat[:, 0])
        y = y[:, None]
        new_state = (new_ssm, new_cx, new_cbc)
    else:
        init = state[0] if state is not None else None
        y, final = ssd_chunked(xs, dt, A, Bmat, Cmat, cfg.ssm_chunk, init)
        new_state = (final, None, None)

    y = y + xs * p["D_skip"][None, None, :, None]
    y = y.reshape(B_, S, DI)
    y = int_ops.int_rmsnorm(
        y * int_ops.int_activation(z, act.leaf("gate"), "silu"),
        p["norm_g"], subkey(key, 6), sc.leaf("norm_g"))
    return int_ops.int_linear(y, p["out_proj"], None, subkey(key, 7),
                              sc.leaf("out_proj")), new_state


def mamba2_init_state(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    K = cfg.ssm_conv
    return (
        jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), dtype),
        jnp.zeros((batch, K - 1, cfg.d_inner), dtype),
        jnp.zeros((batch, K - 1, 2 * cfg.ssm_state), dtype),
    )
