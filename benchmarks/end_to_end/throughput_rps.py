"""Requests answered correctly inside the window, per second of
window (closed-loop cells: what the clients got out of the server)."""


def read(ctx):
    end = ctx.t0 + ctx.window_s
    done = sum(1 for r in ctx.results if r.ok and r.done <= end)
    return done / ctx.window_s if done else None
