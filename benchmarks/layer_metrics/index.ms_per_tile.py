"""MAS query time per rendered tile (`/debug` tile_stages.index_s over
tiles)."""


def read(ctx):
    return ctx.ratio(["tile_stages.index_s"], ["tile_stages.tiles"], 1e3)
