"""Device time of one export tile: the XLA module of
`warp_scenes_ctrl_scored` (control grid, cubic warp, validity, score in
one program) in the trace, over its executions."""


def read(ctx):
    made = ctx.module("warp_scenes_ctrl_scored")
    return made[0] / made[1] * 1e3 if made else None
