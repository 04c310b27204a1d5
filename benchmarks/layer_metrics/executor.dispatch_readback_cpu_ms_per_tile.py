"""The CPU a tile's dispatch and readback ran on their worker thread:
`/debug` tile_stages.dispatch_cpu_s + readback_cpu_s Δ over tiles, the
thread CPU of the `tile.dispatch` and `tile.readback` spans.  Beside
`executor.dispatch_readback_ms_per_tile` (their wall) it says how much
of that wall is running and how much waiting.  None where the spans
carry no CPU (a program without it, or `GSKY_TRACE=0`)."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "tile_stages.dispatch_cpu_s", None) is None:
        return None
    return ctx.ratio(["tile_stages.dispatch_cpu_s",
                      "tile_stages.readback_cpu_s"],
                     ["tile_stages.tiles"], 1e3)
