"""Device time of one band-expression tile: the XLA module of
`render_expr_ctrl` (warp, per-band mosaic, expression, byte scale in
one program) in the trace, over its executions.  None from a program
that has no such kernel."""


def read(ctx):
    made = ctx.module("render_expr_ctrl")
    if not made:
        return None
    return made[0] / made[1] * 1e3
