"""Median latency of a cell's requests on the client's
clock: from when a request was first sent, through any Retry-After, to
the last byte."""


def read(ctx):
    return ctx.latency_percentile_ms(50)
