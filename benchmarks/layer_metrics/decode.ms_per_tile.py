"""Decode-stage time per rendered tile: scene warm into the device
cache, under its gate (`/debug` tile_stages.decode_s over tiles)."""


def read(ctx):
    return ctx.ratio(["tile_stages.decode_s"], ["tile_stages.tiles"], 1e3)
