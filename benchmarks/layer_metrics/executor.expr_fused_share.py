"""Share of band-expression tiles served by the fused kernel of the
staged path (`/debug` expr.paths: `bucketed` over `bucketed` +
`unfused`; an unfused tile is indexed twice and rendered by separate
mosaic, evaluate and scale dispatches over a stacked copy of its
rasters).  None from a program whose `/debug` has no `expr`."""

from benchmarks.ctx import dig

PATHS = ["expr.paths.bucketed", "expr.paths.unfused"]


def read(ctx):
    if dig(ctx.debug1, "expr", None) is None:
        return None
    return ctx.ratio(PATHS[:1], PATHS, 100.0)
