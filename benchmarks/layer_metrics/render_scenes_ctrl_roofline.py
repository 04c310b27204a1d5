"""Share of its roofline `render_scenes_ctrl` reaches: the least time
the chip could take for the tiles of the window (roofline.py, from the
stack depth in each `render_byte:((n, H, W), window)` dispatch key;
n is padded to a power of two, so the share is overstated by up to a
third) over the device time per execution in the trace.  Memory-bound."""

from benchmarks import roofline
from benchmarks.ctx import stack_depth


def read(ctx):
    made = ctx.module("render_scenes_ctrl")
    legs = {k: n for k, n in ctx.legs().items()
            if k.startswith("render_byte:") and stack_depth(k)}
    if not made or not legs:
        return None
    peak = ctx.peaks()
    least = sum(n * roofline.least_seconds(
        *roofline.render_scenes_ctrl(stack_depth(k)), peak)[0]
        for k, n in legs.items()) / sum(legs.values())
    return 100.0 * least / (made[0] / made[1])
