"""Limb matmuls' share of their roofline: the least time of every needed
product of the linears (forward, dX and dW, ``work.least_seconds``: the
larger of 2·M·K·N at the int8 peak and the operands at their bit-width read
once plus the float32 result written once, at the HBM peak), over the
device time of the matmul kernels per steady step.  The remat forward is
not needed work, so it counts against the share."""
LAYER = "limb matmul"
UNIT = "%"
MOVES = "tokens_per_s"
PREFIXES = ("bfp_matmul",)


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    return None if s is None else 100.0 * ctx.matmul_least_s / s
