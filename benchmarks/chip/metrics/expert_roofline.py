"""Grouped expert matmuls' share of their roofline: the least time of the
held experts' needed products at an expert's mean rows (the family's
``expert.*`` linears, forward, dX and dW, ``work.least_seconds``), over
the device time of the grouped kernels per steady step; nothing where no
grouped kernel ran.  Padding rows, rows of unused blocks and the remat
forward are not needed work and count against the share."""
from benchmarks.chip import work

LAYER = "expert layer"
UNIT = "%"
MOVES = "tokens_per_s"
PREFIXES = ("bfp_matmul_grouped",)


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    if not s:
        return None
    c = ctx.cell
    products = [p for p in work.matmul_products(c.family, c.conf, c.traffic,
                                                c.traffic["bits"])
                if p[0].startswith("expert.")]
    return 100.0 * work.least_seconds(products, ctx.peak) / s
