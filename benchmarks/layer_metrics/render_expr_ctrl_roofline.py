"""Share of its roofline `render_expr_ctrl` reaches: the least time the
chip could take for the band-expression tiles of the window
(`roofline_expr.py`, from the granule-set and band counts in each
`render_expr:((g, H, W, c), window)` dispatch key and the operations of
the configuration's expressions; g is padded to a power of two, which
1, 2 and 4 granules are) over the device time per execution in the
trace.  Memory-bound.  None from a program that has no such kernel."""

from benchmarks import roofline, roofline_expr


def read(ctx):
    made = ctx.module("render_expr_ctrl")
    legs = {k: n for k, n in ctx.legs().items()
            if k.startswith("render_expr:") and roofline_expr.leg_shape(k)}
    if not made or not legs:
        return None
    peak = ctx.peaks()
    expr_ops = roofline_expr.ops_by_bands(ctx.cell.config["layers"])
    least = 0.0
    for k, n in legs.items():
        g, c = roofline_expr.leg_shape(k)
        least += n * roofline.least_seconds(
            *roofline_expr.render_expr_ctrl(g, c, expr_ops.get(c, 0)),
            peak)[0]
    return 100.0 * least / sum(legs.values()) / (made[0] / made[1])
