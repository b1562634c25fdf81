"""Distributed train-step factory.

* standard mode — one ``jax.jit`` SPMD program: batch over (pod, data),
  params per the rule-based partitioner (TP/FSDP), gradient reductions
  inserted by XLA, scan-over-layers remat inside the model.
* microbatching — ``lax.scan`` gradient accumulation inside the step.
* compressed mode — ``shard_map`` over the ``pod`` axis with data/model left
  to XLA auto partitioning inside; the cross-pod gradient all-reduce moves
  int8 DFX mantissas with error feedback (core/grad_compress.py).
* quantized state plane (DESIGN.md §7) — ``TrainConfig.gather_bits`` makes
  the FSDP param materialization an int8 QTensor all-gather (FP32 masters
  stay sharded; compute sees the b-bit image, gradients flow straight
  through); ``OptimizerConfig.state_bits`` stores Adam moments as QTensors.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.core import grad_compress, qtensor
from repro.core.qconfig import QuantConfig  # noqa: F401  (re-export)
from repro.core.qpolicy import QuantLike
from repro.train import optimizer as opt_lib

LossFn = Callable[..., Tuple[jax.Array, Dict[str, Any]]]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    grad_compress_bits: int = 0          # 0 = off; 8 = int8 cross-pod psum
    gather_bits: int = 0                 # 0 = f32 FSDP gather; 8 = QTensor
    donate: bool = True


def _split_micro(batch: Any, n: int) -> Any:
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def make_grads_fn(loss_fn: LossFn, cfg, qcfg: QuantLike, microbatches: int):
    """(params, batch, key) -> (grads, metrics), with grad accumulation."""

    def single(params, batch, key):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg, qcfg, key)
        # scalar metrics only (arrays would blow up the replicated metric
        # tree) — but nested dicts of scalars (the sentinel health pytree)
        # pass whole
        return grads, {"loss": loss,
                       **{k: v for k, v in metrics.items()
                          if all(jnp.ndim(l) == 0
                                 for l in jax.tree.leaves(v))}}

    if microbatches <= 1:
        return single

    def accumulated(params, batch, key):
        mb = _split_micro(batch, microbatches)

        def body(carry, inp):
            acc, met_acc = carry
            mbatch, idx = inp
            k = None if key is None else jax.random.fold_in(key, idx)
            g, met = single(params, mbatch, k)
            acc = jax.tree.map(jnp.add, acc, g)
            met_acc = jax.tree.map(jnp.add, met_acc, met)
            return (acc, met_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        first_mb = jax.tree.map(lambda x: x[0], mb)
        _, m0 = jax.eval_shape(lambda: single(params, first_mb, key))
        m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m0)
        (grads, mets), _ = jax.lax.scan(
            body, (g0, m0), (mb, jnp.arange(microbatches)))
        inv = 1.0 / microbatches
        return (jax.tree.map(lambda g: g * inv, grads),
                jax.tree.map(lambda m: m * inv, mets))

    return accumulated


# =========================================================================
# Standard SPMD train step
# =========================================================================

def make_train_step(loss_fn: LossFn, cfg, qcfg: QuantLike,
                    opt_cfg: opt_lib.OptimizerConfig,
                    train_cfg: TrainConfig = TrainConfig(),
                    *, mesh: Optional[Mesh] = None,
                    param_specs: Any = None):
    """``mesh``/``param_specs`` are only consulted when
    ``train_cfg.gather_bits > 0``: with a data axis the params reach compute
    through the int8 QTensor all-gather (sharding.quantized_all_gather);
    without one they take the single-host straight-through form."""
    grads_fn = make_grads_fn(loss_fn, cfg, qcfg, train_cfg.microbatches)
    gb = train_cfg.gather_bits

    def step(params, opt_state, batch, key):
        if gb and mesh is not None and "data" in mesh.axis_names:
            qparams = sharding.quantized_all_gather(
                params, mesh, bits=gb, pspecs=param_specs)
        elif gb:
            with jax.named_scope("gather"):
                qparams = jax.tree.map(
                    lambda p: qtensor.fake_quant_ste(p, gb), params)
        else:
            qparams = params
        grads, metrics = grads_fn(qparams, batch, key)
        params, opt_state, om = opt_lib.update(opt_cfg, grads, opt_state, params)
        return params, opt_state, {**metrics, **om}

    return step


def jit_train_step(step, mesh: Mesh, param_specs, *, donate: bool = True,
                   opt_state_like: Any = None):
    """jit with explicit in/out shardings for params + optimizer state.

    ``opt_state_like`` (an OptState of arrays or ShapeDtypeStructs) is only
    needed when the moments are QTensors — its structure decides the moment
    shardings via sharding.qtensor_pspecs; omitted, moments are assumed to
    mirror the params (the FP32 layout).
    """
    if opt_state_like is None:
        m_specs = v_specs = param_specs
    else:
        m_specs = sharding.qtensor_pspecs(opt_state_like.m, param_specs, mesh)
        v_specs = sharding.qtensor_pspecs(opt_state_like.v, param_specs, mesh)
    opt_specs = opt_lib.OptState(
        step=NamedSharding(mesh, P()), m=m_specs, v=v_specs)
    batch_spec = NamedSharding(mesh, P(sharding.batch_axes(mesh)))
    rep = NamedSharding(mesh, P())
    return jax.jit(
        step,
        in_shardings=(param_specs, opt_specs, batch_spec, rep),
        out_shardings=(param_specs, opt_specs, rep),
        donate_argnums=(0, 1) if donate else (),
    )


# =========================================================================
# Compressed cross-pod step (shard_map over "pod", auto inside)
# =========================================================================

def make_compressed_train_step(loss_fn: LossFn, cfg, qcfg: QuantLike,
                               opt_cfg: opt_lib.OptimizerConfig,
                               mesh: Mesh,
                               train_cfg: TrainConfig = TrainConfig()):
    """Train step whose cross-pod gradient sync is an int8 DFX all-reduce.

    State layout: (params, opt_state, residuals); params/opt replicated,
    batch split over every data-parallel axis.  The gradient reduction is
    hierarchical: a plain FP32 ``psum`` over the fast intra-pod ``data``
    links first, then the int8 DFX compressed psum over the slow cross-pod
    link — compression exactly where bandwidth is scarce.

    The shard_map is fully manual over all mesh axes (this jax line's SPMD
    partitioner aborts on grad-of-scan under partially-manual meshes), so
    the model runs replicated over any ``model`` axis; keep TP out of the
    compressed step's mesh.  ``gather_bits`` takes the straight-through
    per-leaf form here (the wire saving of the sharded gather belongs to
    the FSDP path).
    """
    assert "pod" in mesh.axis_names, "compressed step needs the multi-pod mesh"
    grads_fn = make_grads_fn(loss_fn, cfg, qcfg, train_cfg.microbatches)
    bits = train_cfg.grad_compress_bits or 8
    gb = train_cfg.gather_bits
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    has_data = "data" in mesh.axis_names and mesh.shape["data"] > 1

    def body(params, opt_state, residuals, batch, key):
        # the model's free constrain() calls must not fight the manual mesh
        with sharding.manual_axes_active(set(mesh.axis_names)):
            qparams = (jax.tree.map(lambda p: qtensor.fake_quant_ste(p, gb),
                                    params) if gb else params)
            grads, metrics = grads_fn(qparams, batch, key)
            if has_data:
                ndata = jax.lax.psum(1, "data")
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, "data") / ndata, grads)
            grads, residuals = grad_compress.compressed_psum_mean(
                grads, residuals, bits=bits, axis="pod")
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, dp_axes) if jnp.issubdtype(
                    jnp.asarray(m).dtype, jnp.floating) else m, metrics)
            params, opt_state, om = opt_lib.update(
                opt_cfg, grads, opt_state, params)
        return params, opt_state, residuals, {**metrics, **om}

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(dp_axes), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    # state in, state out: donating (params, opt, residuals) lets XLA reuse
    # their buffers across steps (TrainConfig.donate was silently ignored
    # here before)
    return jax.jit(
        mapped, donate_argnums=(0, 1, 2) if train_cfg.donate else ())


# =========================================================================
# State initialization under a mesh
# =========================================================================

def init_train_state(init_fn, key, mesh: Mesh, *, fsdp: bool,
                     opt_cfg: Optional[opt_lib.OptimizerConfig] = None):
    """Shape-eval params, derive shardings, then materialize sharded.

    ``opt_cfg`` with ``state_bits > 0`` initializes QTensor moments (with
    matching shardings); omitted, the FP32 moment layout is unchanged.
    """
    shapes = jax.eval_shape(init_fn, key)
    pspecs = sharding.param_pspecs(shapes, mesh, fsdp=fsdp)
    params = jax.jit(init_fn, out_shardings=pspecs)(key)
    opt_init = functools.partial(opt_lib.init, cfg=opt_cfg)
    opt_like = jax.eval_shape(opt_init, params)
    opt_specs = opt_lib.OptState(
        step=NamedSharding(mesh, P()),
        m=sharding.qtensor_pspecs(opt_like.m, pspecs, mesh),
        v=sharding.qtensor_pspecs(opt_like.v, pspecs, mesh))
    opt_state = jax.jit(opt_init, out_shardings=opt_specs)(params)
    return params, opt_state, pspecs
