"""Share of the band sets the channel-packed kernels took that span
several pixel grids (`/debug` band_grids: `sets_multi_grid` over
`sets_one_grid` + `sets_multi_grid`, counted in `pipeline/executor.py`
where `render_rgba_byte` and `render_expr_byte` form their sets).  None
from a program whose `/debug` has no `band_grids`, or with no set in
the window."""

from benchmarks.ctx import dig

SETS = ["band_grids.sets_multi_grid", "band_grids.sets_one_grid"]


def read(ctx):
    if dig(ctx.debug1, "band_grids", None) is None:
        return None
    return ctx.ratio(SETS[:1], SETS, 100.0)
