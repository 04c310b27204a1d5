"""Windows (polygon window + rasterised mask) made per 100 files
drilled: files of one request on one grid share one, so three bands of
one product read 33.3 (`/debug` drill_stages.windows over
drill_stages.files).  None from a program whose `drill_stages` has no
`windows` (absent keys read 0, which would pass for the best share)."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "drill_stages.windows", None) is None:
        return None
    return ctx.ratio(["drill_stages.windows"], ["drill_stages.files"], 100.0)
