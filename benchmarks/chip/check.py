"""What decides ``correct``: the program's first three training steps
against a plain float32 reference that follows them.

Three numbers are compared, each by the worst case:

* ``loss_gap``: over the three steps, |program loss - reference loss| /
  |reference loss|.
* ``grad_gap``: per leaf, the gap between the norm of the first gradient
  as the program's optimizer received it (read back from its first moment
  after one step, ``m / (1 - beta1)``) and the reference's (after the same
  global-norm clipping), over the larger of the reference leaf's norm and
  the median leaf's.
* ``update_gap``: the same for the norm of each leaf's change over the
  three steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's (unused heads, the token-type table) move by weight
  decay and round-off alone and are left out.

A "leaf" is one parameter array, and each layer's slice of the stacked
``blocks`` arrays is a leaf of its own, so a fault confined to one layer
shows.  The reference is this file's AdamW and the family's
``reference_loss``; it imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
STEPS = 3
DEAD_LEAF = 1e-3


def seed_key(seed: int):
    """A key from any whole number: the high bits are folded in, since
    ``PRNGKey`` keeps only the low 32."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def keys(seed: int):
    """(init key, batch key, step key) of a run."""
    k = seed_key(seed)
    return tuple(jax.random.fold_in(k, i) for i in range(3))


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------

def _path_name(path) -> str:
    return ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _stacked(path) -> bool:
    return _path_name(path).startswith("blocks.")


def leaf_names(tree) -> list:
    """Names in the order ``leaf_norms`` returns them."""
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = _path_name(path)
        if _stacked(path):
            names += [f"{n}[{i}]" for i in range(x.shape[0])]
        else:
            names.append(n)
    return names


def leaf_norms(tree):
    """float32 vector of per-leaf L2 norms (per layer for stacked leaves)."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = jnp.asarray(x, jnp.float32)
        if _stacked(path):
            out.append(jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(x.shape[0], -1)), axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def change_norms(new, old):
    return leaf_norms(jax.tree.map(jnp.subtract, new, old))


leaf_norms_jit = jax.jit(leaf_norms)
change_norms_jit = jax.jit(change_norms)


# ---------------------------------------------------------------------------
# the reference: float32 at full matmul precision, AdamW written out
# ---------------------------------------------------------------------------

def adamw(opt, params, grads, m, v, t):
    """One AdamW step with global-norm clipping and decoupled weight decay
    (Loshchilov & Hutter); returns (params, m, v, clipped grads)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        return p - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


class Reference:
    """The float32 reference of one cell, compiled once: family init, then
    three AdamW steps of the family's ``reference_loss``, every matmul at
    ``precision="highest"``."""

    def __init__(self, family, conf, traffic):
        opt = traffic["optimizer"]
        self._init = jax.jit(lambda k: family.init(k, conf))

        def step(params, m, v, t, batch):
            with jax.default_matmul_precision("highest"):
                loss, grads = jax.value_and_grad(family.reference_loss)(
                    params, batch, conf)
                params, m, v, clipped = adamw(opt, params, grads, m, v, t)
            return params, m, v, loss, leaf_norms(clipped)

        self._step = jax.jit(step, donate_argnums=(1, 2))

    def run(self, init_key, batches) -> dict:
        """Losses of the first three steps, the first clipped gradient's
        leaf norms and the leaf norms of the change over three steps."""
        p0 = self._init(init_key)
        params = p0
        m = jax.tree.map(jnp.zeros_like, p0)
        v = jax.tree.map(jnp.zeros_like, p0)
        losses, g1 = [], None
        for t, batch in enumerate(batches[:STEPS], start=1):
            params, m, v, loss, gn = self._step(params, m, v,
                                                jnp.float32(t), batch)
            losses.append(loss)
            g1 = gn if g1 is None else g1
        d3 = change_norms_jit(params, p0)
        losses, g1, d3 = jax.device_get((losses, g1, d3))
        return {"losses": np.asarray(losses, np.float64),
                "grad": np.asarray(g1, np.float64),
                "change": np.asarray(d3, np.float64)}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _moved(ref: dict):
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's; the others move by weight decay and round-off alone."""
    return ref["grad"] >= DEAD_LEAF * np.median(ref["grad"])


def _norm_gaps(prog, ref, keep):
    """Per-leaf |prog - ref| / max(ref, median ref) over the kept leaves,
    NaN elsewhere; a leaf the program made non-finite reads infinite."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = max(float(np.median(ref[keep])), 1e-30)
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    return np.where(keep, np.nan_to_num(gap, nan=np.inf), np.nan)


def _gaps(prog: dict, ref: dict) -> dict:
    everything = np.ones(ref["grad"].shape, bool)
    return {"grad": _norm_gaps(prog["grad"], ref["grad"], everything),
            "change": _norm_gaps(prog["change"], ref["change"], _moved(ref))}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers from the program's and the reference's readings
    (each a dict of ``losses``, ``grad``, ``change``)."""
    pl, rl = np.asarray(prog["losses"]), ref["losses"]
    gaps = _gaps(prog, ref)
    return {"loss_gap": float(np.max(np.abs(pl - rl) / np.abs(rl))),
            "grad_gap": float(np.nanmax(gaps["grad"])),
            "update_gap": float(np.nanmax(gaps["change"]))}


def worst_leaves(prog: dict, ref: dict, names: list, k: int = 3) -> dict:
    """The ``k`` leaves that set ``grad_gap`` and ``update_gap``, for the
    record."""
    out = {}
    for key, gap in _gaps(prog, ref).items():
        order = np.argsort(-np.nan_to_num(gap, nan=-1.0))[:k]
        out[key] = [[names[i], float(gap[i])] for i in order]
    return out


def judge(numbers: dict, limits: dict, failed: int):
    """(correct, {name: {"value", "limit"}}).  A non-finite number, a
    missing limit or a failed step is never correct."""
    checks = {n: {"value": numbers[n], "limit": limits.get(n)}
              for n in NUMBERS}
    ok = failed == 0 and all(
        c["limit"] is not None and np.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    checks["failed_steps"] = {"value": failed, "limit": 0}
    return ok, checks
