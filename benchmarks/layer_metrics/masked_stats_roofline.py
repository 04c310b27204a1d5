"""Share of its roofline the drill's reduction reaches, whichever side
of the race serves (`masked_stats_pallas` or XLA's `masked_mean`): the
least time for the mean polygon window of the window's requests
(roofline.py; the true bounding window, not the padded bucket) over the
device time per execution in the trace.  Memory-bound."""

from benchmarks import roofline

REDUCERS = ("masked_mean", "masked_stats_pallas")


def read(ctx):
    made = [m for m in (ctx.module(f) for f in REDUCERS) if m]
    wins = [r.req.meta["window_px"] for r in ctx.results
            if r.ok and "window_px" in r.req.meta]
    if not made or not wins:
        return None
    steps = ctx.cell.config["archive"]["steps"]
    peak = ctx.peaks()
    least = sum(roofline.least_seconds(*roofline.masked_stats(steps, w),
                                       peak)[0] for w in wins) / len(wins)
    return 100.0 * least / (sum(s for s, _ in made) / sum(n for _, n in made))
