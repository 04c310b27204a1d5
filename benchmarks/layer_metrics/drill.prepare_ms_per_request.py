"""Host preparation per Execute, summed over its files: header open,
CRS, the polygon's window and its rasterised mask, strides
(`drill.prepare` spans, folded into `/debug` drill_stages.prepare_s over
requests)."""


def read(ctx):
    return ctx.ratio(["drill_stages.prepare_s"], ["drill_stages.requests"], 1e3)
