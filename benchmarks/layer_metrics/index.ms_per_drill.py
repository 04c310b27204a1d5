"""MAS query time per Execute, with the parsing of what it returns into
datasets (`drill.index` spans, folded into `/debug` drill_stages.index_s
over requests)."""


def read(ctx):
    return ctx.ratio(["drill_stages.index_s"], ["drill_stages.requests"], 1e3)
