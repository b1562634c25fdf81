"""Device milliseconds per steady step of the limb matmuls
(kernels/bfp_matmul.py: NN, NT and TN together)."""
LAYER = "limb matmul"
UNIT = "ms"
MOVES = "tokens_per_s"
PREFIXES = ("bfp_matmul",)


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    return None if s is None else 1e3 * s
