"""How long a tile waited for a slot of the dispatch gate: `/debug`
tile_stages.gates.dispatch.wait_s Δ over tiles (timed round the gate's
semaphore, traced or not; part of `tile.dispatch`'s wall).  None where
the gate does not count its wait."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "tile_stages.gates.dispatch.wait_s", None) is None:
        return None
    return ctx.ratio(["tile_stages.gates.dispatch.wait_s"],
                     ["tile_stages.tiles"], 1e3)
