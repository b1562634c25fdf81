"""Shared utilities: the layer stacks' remat, jaxpr counters, and the
persistent compile cache."""
from __future__ import annotations

import os
import pathlib

import jax


def checkpoint(f):
    """The one place the layer stacks take their remat from: a full
    ``jax.checkpoint`` (every activation recomputed in the backward).  A
    remat policy chosen from observed memory belongs here and nowhere
    else."""
    return jax.checkpoint(f)


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

