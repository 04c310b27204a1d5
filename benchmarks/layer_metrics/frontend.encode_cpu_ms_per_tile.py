"""The CPU of a tile's PNG encode on its pool thread: `/debug`
tile_stages.encode_cpu_s Δ over tiles (the encode job's thread CPU,
measured by the job whether or not the request is traced).  Beside
`frontend.encode_ms_per_tile` (the encode's wall) it says how much of
that wall is running.  None where `/debug` has no `encode_cpu_s`."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "tile_stages.encode_cpu_s", None) is None:
        return None
    return ctx.ratio(["tile_stages.encode_cpu_s"], ["tile_stages.tiles"], 1e3)
