"""Share of dataset rows a `gdal` index query handed out that it found
already decoded (JSON columns loaded, timestamps parsed) under the
store's generation (`/debug` cache.mas_rows hits over hits + misses).
None from a program whose `/debug` has no `mas_rows`."""

from benchmarks.ctx import dig

ROWS = ["cache.mas_rows.hits", "cache.mas_rows.misses"]


def read(ctx):
    if dig(ctx.debug1, "cache.mas_rows", None) is None:
        return None
    return ctx.ratio(ROWS[:1], ROWS, 100.0)
