"""Share of the band sets the channel-packed kernels took that lie in a
tile whose sets span several CRSs (`/debug` band_crs: `sets_multi_crs`
over `sets_one_crs` + `sets_multi_crs`, counted in
`pipeline/executor.py` where `render_rgba_byte` and `render_expr_byte`
form their sets).  None from a program whose `/debug` has no
`band_crs`, or with no set in the window."""

from benchmarks.ctx import dig

SETS = ["band_crs.sets_multi_crs", "band_crs.sets_one_crs"]


def read(ctx):
    if dig(ctx.debug1, "band_crs", None) is None:
        return None
    return ctx.ratio(SETS[:1], SETS, 100.0)
