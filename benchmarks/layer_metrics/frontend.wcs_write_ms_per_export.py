"""The assembly after the engine per export: nodata into the canvas,
the GeoTIFF deflated in 256-px blocks and written, the file read back
into the response (`export.write` span in `server/ows.py`, folded into
`/debug` export_pipeline.write_s over exports).  None from a program
whose `/debug` does not keep it."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "export_pipeline.write_s", None) is None:
        return None
    return ctx.ratio(["export_pipeline.write_s"],
                     ["export_pipeline.exports"], 1e3)
