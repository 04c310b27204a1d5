"""Share of its roofline `window_gather` reaches: the least time for the
mean polygon window of the window's requests (roofline.py; the true
bounding window, not the padded bucket) over the device time per
execution in the trace.  Memory-bound."""

from benchmarks import roofline


def read(ctx):
    made = ctx.module("window_gather")
    wins = [r.req.meta["window_px"] for r in ctx.results
            if r.ok and "window_px" in r.req.meta]
    if not made or not wins:
        return None
    steps = ctx.cell.config["archive"]["steps"]
    peak = ctx.peaks()
    least = sum(roofline.least_seconds(*roofline.window_gather(steps, w),
                                       peak)[0] for w in wins) / len(wins)
    return 100.0 * least / (made[0] / made[1])
