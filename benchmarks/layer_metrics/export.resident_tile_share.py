"""Share of the window's export tiles rendered by the fused kernel from
scenes resident on the device (`/debug` export_pipeline: `tiles_resident`
over `tiles_resident` + `tiles_fallback`, counted in
`ExportPipeline._render_tile`; a fallback tile is decoded on the host
and uploaded as a window).  None from a program whose `/debug` has no
such counters."""

from benchmarks.ctx import dig

PATHS = ["export_pipeline.tiles_resident", "export_pipeline.tiles_fallback"]


def read(ctx):
    if dig(ctx.debug1, PATHS[0], None) is None:
        return None
    return ctx.ratio(PATHS[:1], PATHS, 100.0)
