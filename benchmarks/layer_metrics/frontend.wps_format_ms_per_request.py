"""Answer formatting per Execute: the CSV rows, the WPS response
template and the XML body (`wps.format` span, folded into `/debug`
drill_stages.format_s over requests)."""


def read(ctx):
    return ctx.ratio(["drill_stages.format_s"], ["drill_stages.requests"], 1e3)
