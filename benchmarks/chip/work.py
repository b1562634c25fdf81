"""Needed work of one training step, from the shapes alone.

What the model requires, not what the program does: no recomputation of
the forward pass (remat), no limb passes, no padding of a sequence to the
kernel's block.  Every operation is priced at the chip's int8 peak, the
fastest integer rate its MXU has, so a share of a peak computed from these
counts cannot pass 100% whatever the implementation does.

A family module gives ``linears(conf, traffic)``, the forward matrix
products as (name, M, K, N, count), and ``attention(conf, traffic)``,
(batch, query length, key length, width, layers).
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]


def linear_ops(family, conf, traffic) -> int:
    """Forward, dX and dW: 3 products of 2*M*K*N operations each."""
    return sum(6 * m * k * n * c
               for _, m, k, n, c in family.linears(conf, traffic))


def attention_ops(family, conf, traffic) -> int:
    """QK^T and PV forward (2 products), dQ, dK, dV and dP backward (4): 6
    products of 2*B*Sq*Sk*d operations each, per layer."""
    b, sq, sk, d, layers = family.attention(conf, traffic)
    return 12 * b * sq * sk * d * layers


def step_ops(family, conf, traffic) -> int:
    return linear_ops(family, conf, traffic) + attention_ops(
        family, conf, traffic)


def matmul_products(family, conf, traffic, bits: dict) -> list:
    """Every needed product of the linears as (name, rows, contraction,
    cols, lhs bits, rhs bits, count): forward X.W, backward G.W^T (dX) and
    X^T.G (dW)."""
    a, w, g = bits["act"], bits["weight"], bits["grad"]
    out = []
    for name, m, k, n, c in family.linears(conf, traffic):
        out += [(f"{name}.fwd", m, k, n, a, w, c),
                (f"{name}.dx", m, n, k, g, w, c),
                (f"{name}.dw", k, m, n, a, g, c)]
    return out


def least_seconds(products, peak: dict) -> float:
    """Sum over products of the least time the chip could take for each:
    the larger of its operations at the int8 peak and its bytes at the HBM
    peak (operands at their bit-width read once, the float32 result
    written once)."""
    total = 0.0
    for _, r, k, c, lb, rb, count in products:
        ops = 2 * r * k * c
        nbytes = r * k * lb / 8 + k * c * rb / 8 + r * c * 4
        total += count * max(ops / peak["int8_ops_per_s"],
                             nbytes / peak["hbm_bytes_per_s"])
    return total
