"""Device milliseconds per steady step of the grouped limb matmuls that run
a share of experts (kernels/bfp_matmul.py: ``bfp_matmul_grouped``, ``_nt``
and ``_tn``); nothing where no grouped kernel ran."""
LAYER = "expert layer"
UNIT = "ms"
MOVES = "tokens_per_s"
PREFIXES = ("bfp_matmul_grouped",)


def read(ctx):
    s = ctx.op_seconds(PREFIXES)
    return 1e3 * s if s else None
