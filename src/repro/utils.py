"""Shared utilities.

``scan`` wraps ``jax.lax.scan`` with a global ANALYSIS_UNROLL switch: XLA's
``cost_analysis`` counts a while-loop body **once** regardless of trip count,
so the roofline pass lowers reduced-depth configs with every scan fully
unrolled and extrapolates per-layer costs (launch/dryrun.py).  Production
lowering keeps the rolled loops (small HLO, working activation memory).
"""
from __future__ import annotations

import os
import pathlib

import jax

ANALYSIS_UNROLL = False


def scan(body, carry, xs, length=None, unroll=None, analysis_unroll=True):
    """``analysis_unroll=False`` marks loops whose body is cheap/elementwise
    (e.g. the SSD inter-chunk state recurrence): their per-trip cost is
    negligible, and unrolling them would explode analysis-mode HLO."""
    if ANALYSIS_UNROLL and analysis_unroll:
        unroll = True
    return jax.lax.scan(body, carry, xs, length=length,
                        unroll=unroll if unroll is not None else 1)


#: activation-checkpoint policy for the per-layer remat:
#:   None      — full remat (recompute everything; min memory, +~2ND flops)
#:   "dots"    — save matmul outputs, recompute elementwise (perf variant)
#:   "nothing" — alias of full remat
CHECKPOINT_POLICY = None


def checkpoint(f):
    """jax.checkpoint wrapper honouring the global CHECKPOINT_POLICY."""
    if CHECKPOINT_POLICY == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
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
    ``benchmarks/backend_compare.py`` to measure the batched expert-axis
    kernels against the per-expert unrolled loop they replaced.  Thin
    wrapper over ``repro.analysis.walker.count_pallas_calls``.
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


class analysis_unroll:
    """Context manager enabling full scan unrolling (roofline analysis)."""

    def __enter__(self):
        global ANALYSIS_UNROLL
        self._prev = ANALYSIS_UNROLL
        ANALYSIS_UNROLL = True
        return self

    def __exit__(self, *exc):
        global ANALYSIS_UNROLL
        ANALYSIS_UNROLL = self._prev
        return False
