"""Whole-step utilization: the step's needed operations (``work.step_ops``:
6 x matmul parameters x positions plus 12·B·Sq·Sk·d per attention layer,
no recomputation, no limb passes) per steady traced step, over the step's
device time and the chips' int8 peak."""
LAYER = "train step"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    r = ctx.reduction
    if r is None or r.steps == 0:
        return None
    seconds_per_step = r.window_s / r.steps
    return 100.0 * ctx.step_ops / (
        seconds_per_step * ctx.chips * ctx.peak["int8_ops_per_s"])
