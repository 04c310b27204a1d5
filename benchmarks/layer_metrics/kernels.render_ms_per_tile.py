"""Device time of one `render_scenes_ctrl` program (the whole tile:
warp, mosaic, scale), from the XLA modules of that name in the trace."""


def read(ctx):
    made = ctx.module("render_scenes_ctrl")
    return made[0] / made[1] * 1e3 if made else None
