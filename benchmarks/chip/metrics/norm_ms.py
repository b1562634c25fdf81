"""Device milliseconds per steady step of the fused layer-norm forward and
backward kernels (kernels/int_norm.py)."""
LAYER = "fused norm"
UNIT = "ms"
MOVES = "tokens_per_s"
PREFIXES = ("int_layernorm_", "int_rmsnorm_")


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    return None if s is None else 1e3 * s
