"""Shared utilities: the layer stacks' remat, jaxpr counters, and the
persistent compile cache."""
from __future__ import annotations

import dataclasses
import logging
import os
import pathlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import health

log = logging.getLogger(__name__)

#: Share of a device's memory (``memory_stats()["bytes_limit"]``) that the
#: saved residuals of one layer stack may fill.  The other half holds the
#: rest of a training step: parameters, AdamW moments, batches and XLA's
#: temporaries.  Under full remat, residuals included, the bert-base and
#: vit-base benchmark steps peak at 1.84 / 1.65 GB on a TPU v5e (16 GB).
RESIDUAL_SHARE = 0.5

#: Float elementwise arithmetic, which the saving policy recomputes in the
#: backward instead of keeping.  Each is one cheap pass over values already
#: kept; kept, the autodiff of a tanh GeLU alone would hold five float32
#: tensors of the MLP's width a layer.
_RECOMPUTED = frozenset({"tanh", "mul", "add", "sub", "pow", "integer_pow",
                         "exp", "logistic", "div"})


def _save_all_but_float_arithmetic(prim, *avals, **params) -> bool:
    """Remat policy: keep every value (kernel outputs, limb planes, norm
    statistics, relayouts) except the outputs of float elementwise
    arithmetic."""
    return not (prim.name in _RECOMPUTED
                and all(jnp.issubdtype(a.dtype, jnp.floating) for a in avals))


@dataclasses.dataclass(frozen=True)
class RematDecision:
    """What ``checkpoint`` chose for one stack: ``saved_bytes`` is one
    layer's residuals under the saving policy and ``budget`` the bytes the
    whole stack's may fill (both ``None`` where the device reports no
    memory); ``mode`` is ``"policy"`` or ``"full"``."""
    stack: str
    depth: int
    saved_bytes: Optional[int]
    budget: Optional[int]
    mode: str


_DECISIONS: Dict[str, RematDecision] = {}


def remat_decisions() -> Dict[str, RematDecision]:
    """The latest decision of each stack, by stack name."""
    return dict(_DECISIONS)


def remat_mode(saved_bytes: Optional[int], depth: int,
               bytes_limit: Optional[int]) -> str:
    """``"policy"`` when ``depth`` layers' saved residuals fit in
    ``RESIDUAL_SHARE`` of the device's ``bytes_limit``, else ``"full"``
    (also where the limit is unknown)."""
    if bytes_limit is None:
        return "full"
    return ("policy" if saved_bytes * depth <= RESIDUAL_SHARE * bytes_limit
            else "full")


def device_bytes_limit() -> Optional[int]:
    """The first local device's memory limit; ``None`` where it reports
    none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return None if stats is None else stats.get("bytes_limit")


def checkpoint(f, *, stack: str, depth: int):
    """The one place the layer stacks take their remat from.

    ``f`` is one layer of a ``depth``-layer stack named ``stack``.  At its
    first call (trace time) the wrapper measures one layer's residuals
    under a policy that keeps all but float elementwise arithmetic
    (``jax.eval_shape`` of ``jax.vjp``, leaf bytes summed), and keeps them
    when ``depth`` layers' worth fits the device's share (``remat_mode``);
    otherwise, and wherever the device reports no memory, it is a full
    ``jax.checkpoint`` (every activation recomputed in the backward).  Both
    give the same numbers: the backward reads the values it would have
    recomputed.  The decision is logged and kept for ``remat_decisions``.
    """
    full = jax.checkpoint(f)
    policy = jax.checkpoint(f, policy=_save_all_but_float_arithmetic)
    chosen = []

    def run(*args):
        if not chosen:
            limit = device_bytes_limit()
            saved = budget = None
            if limit is not None:
                with health.suspend():      # no probe escapes the measure
                    res = jax.eval_shape(
                        lambda *a: jax.vjp(policy, *a)[1], *args)
                saved = sum(r.size * r.dtype.itemsize
                            for r in jax.tree.leaves(res))
                budget = int(RESIDUAL_SHARE * limit)
            mode = remat_mode(saved, depth, limit)
            _DECISIONS[stack] = RematDecision(stack, depth, saved, budget,
                                              mode)
            log.info("remat %s: %d layers, %s saved bytes a layer, budget "
                     "%s, %s", stack, depth, saved, budget, mode)
            chosen.append(policy if mode == "policy" else full)
        return chosen[0](*args)
    return run


def count_eqns(jaxpr, name: str, *, recurse_pallas: bool = True) -> int:
    """Count ``name`` eqns in a (closed) jaxpr, recursing into sub-jaxprs
    (pjit bodies, custom_vjp calls, dict-valued params like cond branches).

    Thin wrapper over ``repro.analysis.walker.count_eqns`` (which also
    offers scan-effective counting); kept here for backward compatibility.

    ``recurse_pallas=False`` skips ``pallas_call`` bodies — used to assert
    that an op (e.g. the norm layers' rsqrt) happens only *inside* fused
    kernels, never as an XLA recompute.
    """
    from repro.analysis import walker
    return walker.count_eqns(jaxpr, name, recurse_pallas=recurse_pallas)


def count_pallas_calls(jaxpr) -> int:
    """Count ``pallas_call`` eqns in a (closed) jaxpr.

    Used by the MoE and norm dispatch-count acceptance tests and by
    ``benchmarks/check_dispatch.py``.  Thin wrapper over
    ``repro.analysis.walker.count_pallas_calls``.
    """
    from repro.analysis import walker
    return walker.count_pallas_calls(jaxpr)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, and
    no other directory is set).  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moved between runs would never hit.  Called at the top
    of the launchers' ``main()``, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        # src/repro/utils.py -> the checkout's root
        path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path

