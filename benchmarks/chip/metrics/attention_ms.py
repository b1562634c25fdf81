"""Device milliseconds per steady step of the integer attention forward, dq
and dkv kernels (kernels/int_attention.py)."""
LAYER = "integer attention"
UNIT = "ms"
MOVES = "tokens_per_s"
PREFIXES = ("int_attn_",)


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    return None if s is None else 1e3 * s
