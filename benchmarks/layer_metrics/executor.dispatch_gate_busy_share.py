"""How full the dispatch gate was: its busy seconds over the window
times its slots (`/debug` tile_stages.gates.dispatch)."""

from benchmarks.ctx import dig


def read(ctx):
    limit = dig(ctx.debug1, "tile_stages.gates.dispatch.limit", 0)
    if not limit:
        return None
    return 100.0 * ctx.delta("tile_stages.gates.dispatch.busy_s") \
        / (ctx.window_s * limit)
