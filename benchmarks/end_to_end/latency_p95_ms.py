"""95th percentile of the same latencies, where the window holds at
least 200 requests: then ten or more lie beyond it.  A failed
request counts as infinitely late."""


def read(ctx):
    return ctx.latency_percentile_ms(95, min_requests=200)
