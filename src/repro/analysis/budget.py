"""Interval-arithmetic accumulator-overflow checker (quantlint QL006).

Propagates a worst-case **magnitude interval** for every integer-valued
tensor forward through the traced jaxpr — originating at quantizer clips
(``clamp`` with literal bounds), ``iota``, literals, comparison outputs and
Pallas quantize-kernel outputs, dying at any operation that destroys exact
integrality (e.g. the ``2^exp`` dequantize multiply, whose scale is a
runtime value) — and checks every accumulation site against the *exact*
capacity of its accumulator:

* integer accumulators hold their dtype range (int32: ``2^31 - 1``),
* float accumulators hold integers exactly only up to ``2^mantissa``
  (f32: ``2^24``, f64: ``2^53``) — beyond that an integer-valued sum
  silently rounds, which is precisely the failure mode of the pre-PR 3
  direct int16 ``Σx²`` at D = 768 (bit budget ``2(b-1) + log2 D`` ≈ 40).

Checked sites: ``reduce_sum`` / ``cumsum`` (bound × reduced extent) and
``dot_general`` (|lhs|·|rhs| × contracted extent), anywhere in the XLA
graph.  ``pallas_call`` kernels are checked **structurally** from the call
site instead of by descending into their Ref-based bodies: the kernel kind
(its pinned ``name=``), the operand shapes, the storage bit-width and
the limb split determine the worst case —

* limb matmul kernels accumulate balanced base-2⁷ digit products
  (|digit| ≤ 64) in int32: ``64² · K ≤ 2^31 - 1`` caps the contraction at
  K ≤ 524 287;
* norm kernels split the mantissa into balanced base-2⁸ digits
  (|digit| ≤ 128) so each ``Σ digit²`` partial needs ``14 + log2 D`` bits,
  and sum the raw mantissa (``Σx``: ``(b-1) + log2 D`` bits, ``Σg`` over a
  row block for dbeta) in int32;
* quantize kernels accumulate nothing.

``check_jaxpr`` returns plain ``OverflowSite`` records; ``rules.py`` turns
them into QL006 findings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis import walker

__all__ = ["Interval", "OverflowSite", "exact_capacity", "sum_bits_needed",
           "check_sum_site", "check_jaxpr"]

#: int32 range of the kernel accumulators.
_INT32_MAX = 2**31 - 1

#: balanced base-2⁷ limb digits of the matmul kernels (|digit| ≤ 64 — the
#: final plane's raw carry included; kernels/dfx_quant.py).
_MATMUL_DIGIT = 64

#: balanced base-2⁸ digits of the norm kernels' exact-moment split
#: (kernels/int_norm._exact_moments; |hi|, |lo| ≤ 128).
_NORM_DIGIT = 128


def _kind(dtype_or_aval) -> str:
    """numpy dtype kind char, or "" for extended dtypes (PRNG keys)."""
    dt = getattr(dtype_or_aval, "dtype", dtype_or_aval)
    try:
        return np.dtype(dt).kind
    except TypeError:
        return ""


def exact_capacity(dtype) -> Optional[int]:
    """Largest magnitude the dtype accumulates *exactly* (None: unbounded
    concern-free, e.g. bool)."""
    try:
        dt = np.dtype(dtype)
    except TypeError:
        return None
    if dt.kind in "iu":
        return int(np.iinfo(dt).max)
    if dt.kind == "f":
        return 1 << np.finfo(dt).nmant
    return None


@dataclasses.dataclass(frozen=True)
class Interval:
    """Inclusive bounds on an integer-valued tensor's elements.

    ``integral`` distinguishes exact integer-valued data (whose float
    accumulation can silently round past ``2^mantissa``) from merely
    bounded reals.
    """

    lo: int
    hi: int
    integral: bool = True

    @property
    def mag(self) -> int:
        return max(abs(self.lo), abs(self.hi))

    def hull(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi),
                        self.integral and other.integral)


def _dtype_interval(dtype) -> Optional[Interval]:
    try:
        dt = np.dtype(dtype)
    except TypeError:
        return None
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return Interval(int(info.min), int(info.max))
    if dt.kind == "b":
        return Interval(0, 1)
    return None


@dataclasses.dataclass(frozen=True)
class OverflowSite:
    """One accumulation whose worst case exceeds its accumulator."""

    kind: str         # "reduce_sum" | "cumsum" | "dot_general" | "kernel"
    where: str        # source location or kernel name
    bound: int        # worst-case |accumulated value|
    capacity: int     # exact capacity of the accumulator
    accum: str        # accumulator dtype name
    detail: str = ""

    @property
    def bits_needed(self) -> int:
        return max(1, int(np.ceil(np.log2(max(self.bound, 2)))))


def sum_bits_needed(bits: int, extent: int, *, squared: bool = False) -> int:
    """Bit budget of ``Σ m`` (or ``Σ m²``) over ``extent`` b-bit mantissas —
    the DESIGN.md §2 formula the interval model generalizes."""
    per = (2 * (bits - 1)) if squared else (bits - 1)
    return per + max(1, int(np.ceil(np.log2(max(extent, 2)))))


def check_sum_site(bits: int, extent: int, *, squared: bool = False,
                   accum="int32", where: str = "<site>"
                   ) -> Optional[OverflowSite]:
    """Direct-form check of one mantissa reduction (no jaxpr needed).

    This is the seed-style norm-moment site: ``check_sum_site(16, 768,
    squared=True)`` reproduces the PR 3 hole — a ~40-bit ``Σx²`` against
    int32's 31.
    """
    m = 2 ** (bits - 1) - 1
    bound = (m * m if squared else m) * extent
    cap = exact_capacity(np.dtype(accum))
    if cap is not None and bound > cap:
        return OverflowSite(kind="reduce_sum", where=where, bound=bound,
                            capacity=cap, accum=str(np.dtype(accum)),
                            detail=f"sum of {'squared ' if squared else ''}"
                                   f"{bits}-bit mantissas over {extent}")
    return None


def _src(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return eqn.primitive.name


# =========================================================================
# XLA-level interval propagation
# =========================================================================

_PROPAGATE = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "rev", "slice",
    "dynamic_slice", "gather", "expand_dims", "copy", "stop_gradient",
    "reduce_max", "reduce_min", "sort", "optimization_barrier",
    "reduce_and", "reduce_or",
})

_JOIN = frozenset({"concatenate", "select_n", "dynamic_update_slice", "pad",
                   "max", "min"})

_BOOLEAN = frozenset({"eq", "ne", "lt", "le", "gt", "ge", "is_finite",
                      "reduce_and", "reduce_or", "and", "or", "not", "xor"})


class IntervalSemantics(walker.Semantics):
    """Forward interval propagation; records overflow sites."""

    def __init__(self):
        self.sites: List[OverflowSite] = []

    # -- value sources ----------------------------------------------------
    def literal(self, lit):
        val = np.asarray(lit.val)
        if val.size == 0 or not np.issubdtype(val.dtype, np.number) \
                or not np.all(np.isfinite(val)):
            return None
        integral = bool(np.all(np.mod(val, 1) == 0))
        lo, hi = float(np.min(val)), float(np.max(val))
        return Interval(int(np.floor(lo)), int(np.ceil(hi)), integral)

    # top-level inputs/consts stay unknown: raw integer *data* (token ids)
    # is not mantissa arithmetic, and assuming its dtype range would flag
    # benign bookkeeping sums.  Mantissa chains originate at quantizer
    # clips and kernel outputs instead.

    # -- transfer ---------------------------------------------------------
    def eqn(self, eqn, in_vals, ctx):
        prim = eqn.primitive.name
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        a = in_vals[0] if in_vals else None
        b = in_vals[1] if len(in_vals) > 1 else None

        if prim == "iota":
            dim = eqn.params.get("dimension", 0)
            shape = eqn.params.get("shape", (1,))
            return [Interval(0, max(int(shape[dim]) - 1, 0))]

        if prim in _BOOLEAN:
            return [Interval(0, 1)]

        if prim == "convert_element_type":
            new = eqn.params.get("new_dtype")
            rng = _dtype_interval(new)
            if rng is not None:                        # -> integer dtype
                if a is None:
                    return [None]
                return [Interval(max(a.lo, rng.lo), min(a.hi, rng.hi))]
            return [a]                                 # -> float, keeps bound

        if prim == "clamp":
            lo_v, x, hi_v = in_vals[0], in_vals[1], in_vals[2]
            if lo_v is not None and hi_v is not None:
                integral = (lo_v.integral and hi_v.integral
                            and (x.integral if x is not None else True))
                lo = max(lo_v.lo, x.lo) if x is not None else lo_v.lo
                hi = min(hi_v.hi, x.hi) if x is not None else hi_v.hi
                return [Interval(min(lo, hi), max(lo, hi), integral)]
            return [x]

        if prim in ("add", "sub") and a is not None and b is not None:
            if prim == "add":
                return [Interval(a.lo + b.lo, a.hi + b.hi,
                                 a.integral and b.integral)]
            return [Interval(a.lo - b.hi, a.hi - b.lo,
                             a.integral and b.integral)]

        if prim == "mul" and a is not None and b is not None:
            prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
            return [Interval(min(prods), max(prods),
                             a.integral and b.integral)]

        if prim in ("neg", "abs", "sign", "floor", "ceil", "round",
                    "round_nearest_even"):
            if a is None:
                return [None]
            if prim == "neg":
                return [Interval(-a.hi, -a.lo, a.integral)]
            if prim == "abs":
                return [Interval(0, a.mag, a.integral)]
            if prim == "sign":
                return [Interval(-1, 1)]
            return [Interval(a.lo, a.hi, True)]        # floor/ceil/round

        if prim == "integer_pow":
            if a is None:
                return [None]
            p = int(eqn.params.get("y", 2))
            vals = [a.lo ** p, a.hi ** p] + ([0] if a.lo < 0 < a.hi else [])
            return [Interval(min(vals), max(vals), a.integral)]

        if prim == "rem" and b is not None and b.lo > 0:
            m = b.hi - 1
            lo = -m if (a is None or a.lo < 0) else 0
            return [Interval(lo, m)]

        if prim == "div" and a is not None and b is not None \
                and (b.lo > 0 or b.hi < 0):
            d = min(abs(b.lo), abs(b.hi))
            return [Interval(-(-a.lo // d) if a.lo < 0 else a.lo // d,
                             a.hi // d if a.hi >= 0 else -(-a.hi // d),
                             a.integral and b.integral)]

        if prim in ("shift_right_arithmetic", "shift_right_logical") \
                and a is not None and b is not None and b.lo >= 0:
            s = b.lo
            return [Interval(a.lo >> s, a.hi >> s)]

        if prim == "shift_left" and a is not None and b is not None \
                and b.lo == b.hi and b.lo >= 0:
            s = b.lo
            return [Interval(a.lo << s, a.hi << s)]

        if prim == "and" and out_aval is not None \
                and _kind(out_aval) in "iu":
            # bitwise mask: |result| bounded by the wider operand (used by
            # the digit-split idiom ``(x + 128) & 255``)
            if b is not None and b.lo >= 0:
                return [Interval(0, b.hi)]
            if a is not None and a.lo >= 0:
                return [Interval(0, a.hi)]
            return [None]

        if prim in ("reduce_sum", "cumsum", "cumlogsumexp", "cummax",
                    "cummin", "cumprod"):
            if prim in ("reduce_sum", "cumsum"):
                return [self._check_sum(eqn, a, ctx)]
            return [None]

        if prim == "dot_general":
            return [self._check_dot(eqn, a, b, ctx)]

        if prim in _PROPAGATE:
            return [a] + [None] * (len(eqn.outvars) - 1)

        if prim in _JOIN:
            vals = [v for v in in_vals if isinstance(v, Interval)]
            if len(vals) == len(in_vals) and vals:
                out = vals[0]
                for v in vals[1:]:
                    out = out.hull(v)
                return [out] + [None] * (len(eqn.outvars) - 1)
            return [None] * len(eqn.outvars)

        if walker.sub_jaxprs(eqn):
            return None                                # generic descent

        return [None] * len(eqn.outvars)

    # -- accumulation checks ----------------------------------------------
    def _record(self, kind, eqn, bound, out_dtype, detail):
        cap = exact_capacity(out_dtype)
        if cap is not None and bound > cap:
            self.sites.append(OverflowSite(
                kind=kind, where=_src(eqn), bound=int(bound), capacity=cap,
                accum=str(out_dtype), detail=detail))

    def _check_sum(self, eqn, a: Optional[Interval], ctx) -> Optional[Interval]:
        if a is None:
            return None
        operand = eqn.invars[0].aval
        if eqn.primitive.name == "reduce_sum":
            axes = eqn.params.get("axes", ())
            extent = int(np.prod([operand.shape[ax] for ax in axes])) or 1
        else:                                          # cumsum
            extent = int(operand.shape[eqn.params.get("axis", 0)])
        out_dtype = eqn.outvars[0].aval.dtype
        bound = a.mag * extent
        if a.integral or _kind(out_dtype) in "iu":
            self._record(eqn.primitive.name, eqn, bound, out_dtype,
                         f"|x| <= {a.mag} summed over {extent}")
        # covers both the full sum and every cumsum prefix
        return Interval(min(a.lo, 0) * extent, max(a.hi, 0) * extent,
                        a.integral)

    def _check_dot(self, eqn, a, b, ctx) -> Optional[Interval]:
        if a is None or b is None:
            return None
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        extent = int(np.prod([lhs.shape[ax] for ax in lhs_c])) or 1
        out_dtype = eqn.outvars[0].aval.dtype
        bound = a.mag * b.mag * extent
        if (a.integral and b.integral) or _kind(out_dtype) in "iu":
            self._record("dot_general", eqn, bound, out_dtype,
                         f"|lhs| <= {a.mag}, |rhs| <= {b.mag}, K = {extent}")
        if a.integral and b.integral:
            return Interval(-bound, bound)
        return None

    # -- kernel boundary --------------------------------------------------
    def pallas_call(self, eqn, in_vals, ctx):
        self.sites.extend(check_kernel_site(eqn))
        return [_kernel_out_interval(eqn, i) for i in range(len(eqn.outvars))]


def _kernel_name(eqn) -> str:
    """The ``name=`` each ``pallas_call`` in ``kernels/`` pins."""
    return eqn.params.get("name") or ""


def _kernel_out_interval(eqn, i: int) -> Optional[Interval]:
    aval = eqn.outvars[i].aval
    rng = _dtype_interval(aval.dtype)
    if rng is None:
        return None
    x = eqn.invars[0].aval
    if (_kernel_name(eqn).startswith("dfx_quantize")
            and len(aval.shape) == len(x.shape) + 1):
        # fused limb split: balanced base-2⁷ digit planes, |digit| <= 64
        return Interval(-_MATMUL_DIGIT, _MATMUL_DIGIT)
    return rng


def _storage_bits(dtype) -> int:
    return {np.dtype(np.int8): 8, np.dtype(np.int16): 16}.get(
        np.dtype(dtype), 24)


def check_kernel_site(eqn) -> List[OverflowSite]:
    """Structural worst-case check of one ``pallas_call`` accumulation."""
    name = _kernel_name(eqn)
    sites: List[OverflowSite] = []

    def add(bound, detail, kind="kernel"):
        if bound > _INT32_MAX:
            sites.append(OverflowSite(kind=kind, where=name, bound=int(bound),
                                      capacity=_INT32_MAX, accum="int32",
                                      detail=detail))

    if name.startswith("bfp_matmul"):
        # contraction extent: the axis the in-kernel dot contracts on the
        # lhs block maps to the trailing dims of the full lhs operand
        lhs = eqn.invars[0].aval
        lc = 1
        for site in walker.iter_eqns(eqn.params["jaxpr"]):
            if site.prim == "dot_general":
                lc = site.eqn.params["dimension_numbers"][0][0][0]
                break
        K = int(lhs.shape[-2 + lc])
        add(_MATMUL_DIGIT * _MATMUL_DIGIT * K,
            f"limb-pair int32 accumulator: 64² x K={K}")
    elif name.startswith("int_attn_"):
        # fused attention kernels: every in-kernel integer dot (QK^T digit
        # pairs, P·V planes, dS·K / dS^T·Q / P^T·dO in the backward)
        # accumulates balanced digit products in int32 over the block's
        # contraction extent.  The P/dS planes are ≤ 2^7 in magnitude
        # (single-plane mantissas ≤ 8 bits; multi-limb digits ≤ 64), the
        # limb side is ≤ 64 — bound each dot by 128·64·K.
        for site in walker.iter_eqns(eqn.params["jaxpr"]):
            if site.prim != "dot_general":
                continue
            sa = site.eqn.invars[0].aval
            if _kind(sa.dtype) not in "iu":
                continue
            lc = site.eqn.params["dimension_numbers"][0][0][0]
            K = int(sa.shape[lc])
            add(_NORM_DIGIT * _MATMUL_DIGIT * K,
                f"attention digit-pair int32 accumulator: 128·64 x K={K}")
    elif name.startswith(("int_layernorm_", "int_rmsnorm_")):
        xm = eqn.invars[0].aval
        bits = _storage_bits(xm.dtype)
        D = int(xm.shape[-1])
        m = 2 ** (bits - 1)
        add(m * D, f"Σx over D={D} of {bits}-bit mantissas")
        add(_NORM_DIGIT * _NORM_DIGIT * D,
            f"digit-split Σx² partial: 128² x D={D}")
        if "bwd" in name:
            R = int(xm.shape[0])
            add(m * R, f"dbeta Σg over row block (<= {R} rows)")
    return sites


def check_jaxpr(jaxpr) -> List[OverflowSite]:
    """All overflow sites of a (closed) jaxpr: XLA-level interval
    propagation plus structural Pallas-kernel checks."""
    sem = IntervalSemantics()
    walker.interpret(jaxpr, sem)
    return sem.sites
