"""Share of its roofline `render_rgba_ctrl` reaches on band sets over
several pixel grids: the least time the chip could take for the
three-band tiles of the window (`roofline_multigrid.py`, from the
granule, grid and band counts in each `render_rgba_mg:((G, R, C),
windows)` dispatch key; G is padded to a power of two, which 1, 2 and 4
granules are) over the device time per execution in the trace.
Memory-bound.  None from a program that counts no such dispatch."""

from benchmarks import roofline, roofline_multigrid


def read(ctx):
    made = ctx.module("render_rgba_ctrl")
    legs = {k: n for k, n in ctx.legs().items()
            if k.startswith("render_rgba_mg:")
            and roofline_multigrid.leg_shape(k)}
    if not made or not legs:
        return None
    peak = ctx.peaks()
    least = sum(n * roofline.least_seconds(*roofline_multigrid.render_rgba_ctrl(
        *roofline_multigrid.leg_shape(k)), peak)[0]
        for k, n in legs.items()) / sum(legs.values())
    return 100.0 * least / (made[0] / made[1])
