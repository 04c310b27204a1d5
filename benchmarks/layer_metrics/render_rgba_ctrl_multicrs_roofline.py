"""Share of its roofline `render_rgba_ctrl` reaches on a window whose
tiles read band sets in several CRSs: the least time the chip could take
for the window's three-band tiles over the device time per execution in
the trace.  A `render_rgba_mc:((G, K, C), window)` leg is counted by
`roofline_multicrs.py` (G sets in K CRSs, each on its own control
grid); the window's other `render_rgba_ctrl` legs, tiles over one zone,
run the same jitted function and are counted as `roofline_rgb.py` and
`roofline_multigrid.py` count them, so that the least time and the
trace's executions cover the same tiles.  Memory-bound.  None from a
program that counts no `render_rgba_mc:` dispatch."""

from benchmarks import (roofline, roofline_multicrs, roofline_multigrid,
                        roofline_rgb)
from benchmarks.ctx import stack_depth


def _count(key):
    """(ops, bytes) of one tile of dispatch ``key``, or None."""
    if key.startswith("render_rgba_mc:"):
        shape = roofline_multicrs.leg_shape(key)
        return shape and roofline_multicrs.render_rgba_ctrl(*shape)
    if key.startswith("render_rgba_mg:"):
        shape = roofline_multigrid.leg_shape(key)
        return shape and roofline_multigrid.render_rgba_ctrl(*shape)
    if key.startswith("render_rgba:"):
        g = stack_depth(key)
        return g and roofline_rgb.render_rgba_ctrl(g)
    return None


def read(ctx):
    made = ctx.module("render_rgba_ctrl")
    legs = {k: (n, _count(k)) for k, n in ctx.legs().items()}
    legs = {k: v for k, v in legs.items() if v[1]}
    if not made or not any(k.startswith("render_rgba_mc:") for k in legs):
        return None
    peak = ctx.peaks()
    least = sum(n * roofline.least_seconds(*c, peak)[0]
                for n, c in legs.values()) / sum(n for n, _ in legs.values())
    return 100.0 * least / (made[0] / made[1])
