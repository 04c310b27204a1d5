"""Share of its roofline `render_rgba_ctrl` reaches: the least time the
chip could take for the bilinear tiles of the window (`roofline_rgb.py`,
from the granule count in each `render_rgba:((g, H, W, 3), window)`
dispatch key; g is padded to a power of two, which 1, 2 and 4 granules
are) over the device time per execution in the trace.  Memory-bound."""

from benchmarks import roofline, roofline_rgb
from benchmarks.ctx import stack_depth


def read(ctx):
    made = ctx.module("render_rgba_ctrl")
    legs = {k: n for k, n in ctx.legs().items()
            if k.startswith("render_rgba:") and stack_depth(k)}
    if not made or not legs:
        return None
    peak = ctx.peaks()
    least = sum(n * roofline.least_seconds(
        *roofline_rgb.render_rgba_ctrl(stack_depth(k)), peak)[0]
        for k, n in legs.items()) / sum(legs.values())
    return 100.0 * least / (made[0] / made[1])
