"""Device memory: ``peak_bytes_in_use`` of the fullest chip after the
window, in GiB."""
LAYER = "device memory"
UNIT = "GiB"
MOVES = "tokens_per_s"


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 30 if ctx.memory_peak_bytes else None
