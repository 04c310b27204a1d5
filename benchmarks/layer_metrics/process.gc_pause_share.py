"""Share of the time the cyclic collector held the process, in %:
`/debug` process.gc.pause_s Δ, summed over the three generations, over
the time between the two `/debug` reads (Δ uptime_s; the window and
the drain of its last requests).  While a collection runs no other
Python thread moves.  None where `/debug` has no `process.gc`."""

from benchmarks.ctx import dig


def read(ctx):
    p0 = dig(ctx.debug0, "process.gc.pause_s", None)
    p1 = dig(ctx.debug1, "process.gc.pause_s", None)
    span_s = ctx.delta("uptime_s") or ctx.window_s
    if p0 is None or p1 is None or span_s <= 0:
        return None
    return 100.0 * (sum(p1) - sum(p0)) / span_s
