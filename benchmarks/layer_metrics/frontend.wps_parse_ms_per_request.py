"""WPS request parsing per answered Execute: the XML or KVP body, the
GeoJSON geometry, the area check and its WKT (`wps.parse` spans, folded
into `/debug` drill_stages.parse_s over requests)."""


def read(ctx):
    return ctx.ratio(["drill_stages.parse_s"], ["drill_stages.requests"], 1e3)
