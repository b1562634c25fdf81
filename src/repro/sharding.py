"""Rule-based parameter and activation partitioner.

Axes (DESIGN.md §5):

* ``pod``   — outer data-parallel axis spanning pods (multi-pod mesh only)
* ``data``  — inner data-parallel / FSDP axis
* ``model`` — tensor-parallel axis (heads / ffn / vocab / expert-inner dims)

Rules are keyed on parameter path suffixes and applied with a divisibility
check: if the preferred sharded dim is not divisible by the axis size the
rule falls back (TP -> FSDP-on-other-dim -> replicate), so irregular archs
(smollm's 9 heads, whisper's 20 heads, mamba vocab 50280 before padding)
still lower cleanly — the fallbacks are visible in the roofline table as
extra collective or compute bytes rather than as compile failures.
"""
from __future__ import annotations

import contextlib
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import qtensor

# ---------------------------------------------------------------------------
# Active-mesh context: models call ``constrain`` freely; it is a no-op until
# the launcher installs a mesh.
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Optional[Mesh] = None


def make_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis in auto mode: XLA propagates the
    shardings and ``constrain`` only hints at them."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


#: axes currently under manual (shard_map) control.  While any are active,
#: ``constrain`` is a no-op: a non-manual sharding annotation inside a
#: manual subgroup aborts XLA outright (``Check failed:
#: sharding.IsManualSubgroup()``), and even manual-subgroup-safe constraints
#: break on the *transpose* (grad) path in this jax line — so inside a
#: shard_map body the layout hints are dropped and XLA auto-shards the
#: non-manual axes.
_MANUAL_AXES: frozenset = frozenset()


@contextlib.contextmanager
def manual_axes_active(axes):
    """Mark ``axes`` manual while tracing a shard_map body, so the model's
    free ``constrain`` calls stay safe inside compressed/pod-mapped steps."""
    global _MANUAL_AXES
    prev = _MANUAL_AXES
    _MANUAL_AXES = prev | frozenset(axes)
    try:
        yield
    finally:
        _MANUAL_AXES = prev


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def batch_axes(mesh: Optional[Mesh] = None):
    mesh = mesh or _ACTIVE_MESH
    if mesh is None:
        return ("data",)
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def over_batch(fn, args, in_dims, out_dims):
    """Run ``fn(*args)`` once per chip on its share of the batch rows.

    Pallas kernels cannot be partitioned by XLA, so every kernel call runs
    under ``shard_map`` over the active mesh's batch axes.  ``in_dims`` and
    ``out_dims`` give, per argument and per output, the axis split across
    chips, or None for an operand every chip holds whole (weights, scale
    exponents).  An output marked ``"sum"`` is a per-chip partial sum over
    the split rows (the dW product, the norm parameter gradients) and is
    ``psum``'d over the batch axes.

    Without a mesh, or inside a region that is already manual (the
    compressed step), ``fn`` runs as is.  Where some split axis does not
    divide by the chip count, every chip runs the whole call.  Tensor
    parallelism over the kernels is not supported: a mesh whose ``model``
    axis is larger than 1 raises.
    """
    mesh = _ACTIVE_MESH
    if mesh is None or _MANUAL_AXES:
        return fn(*args)
    if mesh.shape.get("model", 1) > 1:
        raise NotImplementedError(
            "backend='pallas' on a mesh with a 'model' axis of size "
            f"{mesh.shape['model']}: the Pallas kernels shard over the batch "
            "axes only; tensor parallelism over them is not supported")
    axes = batch_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in axes]))
    split = all(d is None or a.shape[d] % n == 0
                for a, d in zip(args, in_dims))
    single = not isinstance(out_dims, (tuple, list))
    outs = (out_dims,) if single else tuple(out_dims)

    def spec(d):
        if d is None or d == "sum" or not split:
            return P()
        return P(*([None] * d), axes)

    def body(*local):
        res = fn(*local)
        res = (res,) if single else tuple(res)
        if split:
            res = tuple(jax.lax.psum(r, axes) if d == "sum" else r
                        for r, d in zip(res, outs))
        return res[0] if single else res

    out_specs = spec(out_dims) if single else tuple(spec(d) for d in outs)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=tuple(spec(d) for d in in_dims),
                         out_specs=out_specs, check_vma=False)(*args)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint against the active mesh (no-op without one).

    ``spec`` entries: None, an axis name, or a tuple of axis names; entries
    naming axes missing from the mesh are dropped; non-divisible dims fall
    back to None.
    """
    mesh = _ACTIVE_MESH
    if mesh is None or _MANUAL_AXES:
        return x
    clean = []
    for dim, s in zip(x.shape, spec):
        names = (s,) if isinstance(s, str) else tuple(s or ())
        names = tuple(n for n in names if n in mesh.axis_names)
        size = int(np.prod([mesh.shape[n] for n in names])) if names else 1
        if not names or dim % size != 0:
            clean.append(None)
        else:
            clean.append(names if len(names) > 1 else names[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*clean)))


def constrain_batch(x: jax.Array) -> jax.Array:
    """Shard the leading (batch) dim over all data-parallel axes."""
    return constrain(x, batch_axes(), *([None] * (x.ndim - 1)))


#: sequence-parallel residual sharding (Megatron-SP layout). Disable via the
#: dry-run "--variant no_sp" to measure its collective cost/benefit.
SEQUENCE_SHARDING = True


def constrain_tokens(x: jax.Array) -> jax.Array:
    """(B, S, D) residual stream: batch over DP, sequence over model (the
    Megatron-SP layout — XLA all-gathers S for attention and reduce-scatters
    after, halving activation memory per device)."""
    if x.ndim == 3:
        if SEQUENCE_SHARDING:
            return constrain(x, batch_axes(), "model", None)
        return constrain(x, batch_axes(), None, None)
    return constrain_batch(x)


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------
# (path-suffix regex, preferred spec per dim). "model" entries are checked
# for divisibility; "data" is the FSDP fallback dim.

_RULES = [
    # embeddings / unembedding
    (r"embed$", ("model", "data")),
    (r"lm_head$", ("data", "model")),
    (r"pos_embed$", (None, "data")),
    # attention
    (r"wq$", ("data", "model")),
    (r"wk$", ("data", "model")),
    (r"wv$", ("data", "model")),
    (r"wo$", ("model", "data")),
    (r"b[qkv]$", ("model",)),
    # dense MLP (SwiGLU + gelu variants)
    (r"wg$", ("data", "model")),
    (r"wu$", ("data", "model")),
    (r"wd$", ("model", "data")),
    (r"w1$", ("data", "model")),
    (r"w2$", ("model", "data")),
    (r"b1$", ("model",)),
    (r"b2$", (None,)),
    # MoE — expert weights shard on model ONLY (TP inside each expert): the
    # data axis is reserved for the dispatch buffer's token rows; putting
    # FSDP on expert D/F dims forces XLA to fully re-gather the experts and
    # replicate the row compute (found in §Perf iteration A.3).
    (r"router$", (None, None)),
    (r"(wg|wu)_e$", (None, None, "model")),
    (r"wd_e$", (None, "model", None)),
    # mamba2
    (r"wz$", ("data", "model")),
    (r"wx$", ("data", "model")),
    (r"wBC$", ("data", None)),
    (r"wdt$", ("data", "model")),
    (r"conv_x$", (None, "model")),
    (r"conv_BC$", (None, None)),
    (r"out_proj$", ("model", "data")),
    (r"norm_g$", ("model",)),
    (r"(A_log|dt_bias|D_skip)$", (None,)),
    # norms and misc small params
    (r"(^|/)g$", (None,)),
    (r"(^|/)b$", (None,)),
    (r"head$", ("data", "model")),
]


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
    return "/".join(parts)


def spec_for(path: str, shape, mesh: Mesh, fsdp: bool,
             stacked: bool) -> P:
    """PartitionSpec for one parameter.

    ``stacked``: leading layer axis from scan-stacking (never sharded).
    """
    dims = list(shape)
    lead = [None]
    if stacked:
        dims = dims[1:]
    rule = None
    for pat, spec in _RULES:
        if re.search(pat, path):
            rule = spec
            break
    if rule is None:
        rule = tuple([None] * len(dims))
    out = []
    used = set()
    for dim, want in zip(dims, rule):
        take = None
        for cand in ([want] if not isinstance(want, (list, tuple)) else list(want)):
            if cand is None:
                continue
            if cand == "data" and not fsdp:
                continue
            if cand in mesh.axis_names and cand not in used and dim % mesh.shape[cand] == 0:
                take = cand
                break
        out.append(take)
        if take:
            used.add(take)
    if stacked:
        out = lead + out
    return P(*out)


def param_pspecs(params: Any, mesh: Mesh, *, fsdp: bool) -> Any:
    """Pytree of NamedShardings matching ``params`` (also accepts a pytree of
    ShapeDtypeStructs)."""

    def one(path, leaf):
        ps = _path_str(path)
        stacked = "/blocks/" in "/" + ps or ps.startswith("blocks/") \
            or "/enc_blocks/" in "/" + ps or ps.startswith("enc_blocks/") \
            or "/dec_blocks/" in "/" + ps or ps.startswith("dec_blocks/")
        spec = spec_for(ps, leaf.shape, mesh, fsdp, stacked)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# QTensor state plane (DESIGN.md §7)
# ---------------------------------------------------------------------------

def qtensor_pspecs(like: Any, param_specs: Any, mesh: Mesh) -> Any:
    """Shardings for a state tree that may hold QTensor nodes.

    ``like`` mirrors the param tree with some leaves replaced by QTensors
    (e.g. quantized optimizer moments); ``param_specs`` is the matching
    pytree of NamedShardings.  A QTensor node inherits its parameter's spec
    shifted past the leading limb-plane axis (``m``: ``P(None, *spec)`` —
    the planes shard exactly like the logical tensor, so FSDP keeps slicing
    the moment bytes); the per-group exponent vector is tiny and replicated.
    Non-QTensor leaves keep their param spec, so this is safe to call on an
    FP32 state tree too.
    """

    def one(q, ns):
        if not qtensor.is_qtensor(q):
            return ns
        spec = ns.spec if isinstance(ns, NamedSharding) else ns
        return qtensor.QTensor(
            m=NamedSharding(mesh, P(None, *tuple(spec))),
            exp=NamedSharding(mesh, P()),
            bits=q.bits)

    return jax.tree.map(one, like, param_specs, is_leaf=qtensor.is_qtensor)


def _fsdp_dim(spec) -> Optional[int]:
    """Index of the dim sharded over the ``data`` axis, or None."""
    for i, s in enumerate(tuple(spec)):
        names = (s,) if isinstance(s, str) else tuple(s or ())
        if "data" in names:
            return i
    return None


def _gathered_leaf(mesh: Mesh, spec, d: int, bits: int):
    """shard_map'd int8 all-gather of one FSDP leaf along dim ``d``.

    Wire format per shard: ``L`` int8 limb planes + one int32 scalar step
    exponent (a *per-shard* scale — no cross-shard pmax round-trip needed,
    each shard dequantizes against its own exponent after the gather).

    Fully manual over every mesh axis (TP/pod placements stay explicit in
    the specs): the output keeps the leaf's ``model`` sharding and drops
    only the ``data`` entry that the gather materializes.
    """
    entries = tuple(spec)
    out_spec = P(*[None if i == d else s for i, s in enumerate(entries)])

    def body(x):
        t = qtensor.quantize(x, bits)                     # local shard, scalar exp
        m = jax.lax.all_gather(t.m, "data")               # (S, L, *local)
        e = jax.lax.all_gather(t.exp, "data")             # (S,)
        shards = jax.vmap(
            lambda mm, ee: qtensor.dequantize(qtensor.QTensor(mm, ee, bits))
        )(m, e)                                           # (S, *local)
        out = jnp.moveaxis(shards, 0, d)
        shape = list(x.shape)
        shape[d] = shape[d] * mesh.shape["data"]
        return out.reshape(shape)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(*entries),),
                         out_specs=out_spec, check_vma=False)


@jax.named_scope("gather")
def quantized_all_gather(params: Any, mesh: Mesh, *, bits: int,
                         pspecs: Any = None) -> Any:
    """FSDP param materialization that moves int8 instead of FP32.

    Each ``data``-sharded leaf is quantized ONCE per step on its home shard
    and all-gathered as limb planes + per-shard exponents — ``4/L`` fewer
    bytes over the FSDP link (4x at int8).  Leaves without a ``data`` dim
    never travel, so they pass through untouched (bit-exact FP32).

    The whole map is wrapped in a straight-through ``custom_vjp``: the
    cotangent of the gathered (quantized) params flows to the FP32 masters
    unchanged, so autodiff never enters the shard_map and XLA still
    reduce-scatters the gradient per the param out-shardings.
    """
    if pspecs is None:
        pspecs = param_pspecs(params, mesh, fsdp=True)
    if "data" not in mesh.axis_names:
        return jax.tree.map(lambda p: qtensor.fake_quant_ste(p, bits), params)

    def impl(ps):
        def one(p, ns):
            spec = ns.spec if isinstance(ns, NamedSharding) else ns
            d = _fsdp_dim(spec)
            if d is None:
                return p
            return _gathered_leaf(mesh, spec, d, bits)(p)
        return jax.tree.map(one, ps, pspecs)

    @jax.custom_vjp
    def qgather(ps):
        return impl(ps)

    qgather.defvjp(lambda ps: (impl(ps), None), lambda _, ct: (ct,))
    return qgather(params)
