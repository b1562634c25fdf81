"""AdamW with FP32 master weights — the paper keeps the weight update in
FP32 while the layer compute is integer; the master params therefore stay
float32 regardless of the quantization preset.

The *moments* are a different story: they are pure state (never touched by
autodiff, read once per step), so with ``state_bits > 0`` they live as
:class:`repro.core.qtensor.QTensor` — int8 DFX limb planes + per-group
exponents, 4x smaller resident and checkpointed.  The EMA is computed in
FP32 and re-quantized with **stochastic rounding** (``qtensor.ema_update``),
whose unbiasedness keeps the quantized moment mean-preserving across steps;
round-to-nearest would absorb every sub-step update of a small gradient and
stall it (DESIGN.md §7).  ``state_bits=0`` (default) is the bit-exact FP32
path every existing caller gets.

Pure-pytree implementation (no optax dependency): init/update functions over
arbitrary param trees, global-norm clipping, linear-warmup + cosine decay.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qtensor


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 2e-5                  # paper's GLUE fine-tuning LR
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    warmup_steps: int = 0
    total_steps: int = 0              # 0 => constant LR (paper: constant)
    schedule: str = "constant"        # constant | cosine | linear
    state_bits: int = 0               # 0 = FP32 moments; 8/16 = QTensor m, v
    seed: int = 0                     # SR stream for quantized-moment EMA


class OptState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


def init(params: Any, cfg: Optional[OptimizerConfig] = None) -> OptState:
    """Zero moments; QTensor moments when ``cfg.state_bits > 0``.

    Quantized moments carry one exponent per leading-axis slice for matrices
    and stacks (per-layer for scan-stacked params — the granularity of
    ``dfx_quantize_grouped``) and a single scalar for vectors.
    """
    if cfg is None or cfg.state_bits == 0:
        zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                             params)
        return OptState(step=jnp.zeros((), jnp.int32), m=zeros,
                        v=jax.tree.map(jnp.copy, zeros))

    def zq(p):
        return qtensor.zeros(p.shape, cfg.state_bits,
                             group_axis=0 if p.ndim >= 2 else None)

    return OptState(step=jnp.zeros((), jnp.int32),
                    m=jax.tree.map(zq, params),
                    v=jax.tree.map(zq, params))


def _schedule(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    lr = jnp.float32(cfg.lr)
    s = step.astype(jnp.float32)
    if cfg.warmup_steps > 0:
        lr = lr * jnp.minimum(1.0, (s + 1) / cfg.warmup_steps)
    if cfg.total_steps > 0 and cfg.schedule != "constant":
        frac = jnp.clip((s - cfg.warmup_steps) /
                        max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        if cfg.schedule == "cosine":
            lr = lr * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        elif cfg.schedule == "linear":
            lr = lr * (1 - frac)
    return lr


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _check_tree(name: str, tree: Any, tdef) -> None:
    td = jax.tree.structure(tree, is_leaf=qtensor.is_qtensor)
    if td != tdef:
        # a silent zip() over mismatched trees would pair leaves with the
        # wrong moments/params and corrupt the update (same contract as
        # grad_compress.compressed_psum_mean)
        raise ValueError(
            f"{name} tree does not match the param tree "
            f"(params: {tdef}, {name}: {td}); build the optimizer state "
            "with optimizer.init(params, cfg)")


@jax.named_scope("optimizer")
def update(cfg: OptimizerConfig, grads: Any, state: OptState, params: Any
           ) -> Tuple[Any, OptState, dict]:
    """Returns (new_params, new_state, metrics), traced under the
    ``optimizer`` named scope for every train step that calls it."""
    tdef = jax.tree.structure(params)
    _check_tree("gradient", grads, tdef)
    _check_tree("moment (m)", state.m, tdef)
    _check_tree("moment (v)", state.v, tdef)

    gnorm = global_norm(grads)
    if cfg.grad_clip > 0:
        scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    step = state.step + 1
    lr = _schedule(cfg, state.step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    flat_p = tdef.flatten_up_to(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m, is_leaf=qtensor.is_qtensor)
    flat_v = jax.tree.leaves(state.v, is_leaf=qtensor.is_qtensor)

    # one SR key per (step, leaf); derived, not threaded — the update
    # signature stays (cfg, grads, state, params) for every caller
    quantized = any(qtensor.is_qtensor(m) for m in flat_m)
    base_key = (jax.random.fold_in(jax.random.PRNGKey(cfg.seed), state.step)
                if quantized else None)

    def upd(i, p, g, m, v):
        g = g.astype(jnp.float32)
        if qtensor.is_qtensor(m):
            km, kv = jax.random.split(jax.random.fold_in(base_key, i))
            m_new = qtensor.ema_update(m, g, b1, km)
            v_new = qtensor.ema_update(v, jnp.square(g), b2, kv)
            mf = qtensor.dequantize(m_new)
            vf = qtensor.dequantize(v_new)
            # linear b-bit quantization cannot represent v below one step
            # of its group's scale — entries there round to 0 and
            # mhat/(sqrt(0)+eps) explodes.  Floor the denominator at the
            # storage resolution: sub-step entries get a conservatively
            # small update instead of a catastrophically large one.
            vf = jnp.maximum(vf, jnp.exp2(v_new.exp.astype(jnp.float32)))
        else:
            m_new = mf = b1 * m + (1 - b1) * g
            v_new = vf = b2 * v + (1 - b2) * jnp.square(g)
        mhat = mf / bc1
        vhat = vf / bc2
        # FP32 master weight update (paper-kept op)
        newp = p - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                         + cfg.weight_decay * p)
        return newp.astype(p.dtype), m_new, v_new

    out = [upd(i, p, g, m, v)
           for i, (p, g, m, v) in enumerate(zip(flat_p, flat_g, flat_m, flat_v))]
    unflat = lambda xs: jax.tree.unflatten(tdef, xs)  # noqa: E731
    return (unflat([o[0] for o in out]),
            OptState(step, unflat([o[1] for o in out]),
                     unflat([o[2] for o in out])),
            {"grad_norm": gnorm, "lr": lr})
