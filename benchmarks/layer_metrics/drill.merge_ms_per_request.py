"""Merging per Execute: the per-(namespace, date) accumulation, the
weighted means and the band expressions (`drill.merge` spans, folded
into `/debug` drill_stages.merge_s over requests)."""


def read(ctx):
    return ctx.ratio(["drill_stages.merge_s"], ["drill_stages.requests"], 1e3)
