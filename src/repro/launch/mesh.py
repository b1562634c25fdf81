"""Mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

from repro.sharding import make_mesh as _mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``pod`` spans pods (data-parallel over DCN/cross-pod ICI),
    ``data`` is the intra-pod data/FSDP axis, ``model`` the tensor-parallel
    axis (kept innermost = fastest ICI neighbours).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, pods: int = 1):
    """Mesh over whatever devices exist (tests / examples on CPU).

    ``pods > 1`` prepends a ``pod`` axis so the compressed cross-pod train
    step (int8 gradient all-reduce) runs on the multi-host sim
    (``--xla_force_host_platform_device_count``).
    """
    n = len(jax.devices())
    assert n % (model_parallel * pods) == 0
    if pods > 1:
        return _mesh((pods, n // (model_parallel * pods), model_parallel),
                     ("pod", "data", "model"))
    return _mesh((n // model_parallel, model_parallel), ("data", "model"))
