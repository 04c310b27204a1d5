"""Share of candidate rows a window's index queries refined against a
footprint they found prepared (parsed, reprojected to EPSG:4326, split
at the dateline, laid out as arrays) under the store's generation
(`/debug` cache.mas_footprints hits over hits + misses).  None from a
program whose `/debug` has no `mas_footprints`."""

from benchmarks.ctx import dig

ROWS = ["cache.mas_footprints.hits", "cache.mas_footprints.misses"]


def read(ctx):
    if dig(ctx.debug1, "cache.mas_footprints", None) is None:
        return None
    return ctx.ratio(ROWS[:1], ROWS, 100.0)
