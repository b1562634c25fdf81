"""Share of the steady traced steps in which no op ran on the device."""
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    r = ctx.reduction
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
