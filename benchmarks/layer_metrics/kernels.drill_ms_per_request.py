"""Device time of one request's drills: `window_gather` plus the
reduction that follows it (`masked_mean` where XLA serves,
`masked_stats` where the Pallas kernel does), per Execute of the window's
band count."""

REDUCERS = ("masked_mean", "masked_stats_pallas")


def read(ctx):
    gather = ctx.module("window_gather")
    if not gather:
        return None
    secs = gather[0] + sum((ctx.module(f) or (0.0, 0))[0] for f in REDUCERS)
    bands = len(ctx.cell.config["archive"]["variables"])
    return secs / gather[1] * bands * 1e3
