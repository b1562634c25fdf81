"""Device milliseconds per steady step of the quantize kernels
(kernels/dfx_quant.py)."""
LAYER = "quantize"
UNIT = "ms"
MOVES = "tokens_per_s"
PREFIXES = ("dfx_quantize",)


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    return None if s is None else 1e3 * s
