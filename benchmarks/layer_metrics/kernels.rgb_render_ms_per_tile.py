"""Device time of one three-band tile: the XLA modules of
`render_rgba_ctrl` (true-colour triples over one or several granules)
and `render_scenes_bands_ctrl` (any other band set) in the trace, over
their executions."""


def read(ctx):
    made = [m for m in (ctx.module("render_rgba_ctrl"),
                        ctx.module("render_scenes_bands_ctrl")) if m]
    if not made:
        return None
    return sum(m[0] for m in made) / sum(m[1] for m in made) * 1e3
