"""The engine's plan per export: the one index query over the whole
bbox and the granules' assignment to tiles (`export.plan` span, folded
into `/debug` export_pipeline.plan_s over exports).  None from a program
whose `/debug` does not keep it."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "export_pipeline.plan_s", None) is None:
        return None
    return ctx.ratio(["export_pipeline.plan_s"],
                     ["export_pipeline.exports"], 1e3)
