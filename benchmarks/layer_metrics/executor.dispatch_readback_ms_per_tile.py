"""Dispatch plus readback time per rendered tile on the host's clock:
waiting for the dispatch gate, issuing the program, and blocking until
the bytes are back (`/debug` tile_stages.dispatch_s + readback_s over
tiles)."""


def read(ctx):
    return ctx.ratio(["tile_stages.dispatch_s", "tile_stages.readback_s"],
                     ["tile_stages.tiles"], 1e3)
