"""PNG encode time per rendered tile (`/debug` tile_stages.encode_s
over tiles, over the window)."""


def read(ctx):
    return ctx.ratio(["tile_stages.encode_s"], ["tile_stages.tiles"], 1e3)
