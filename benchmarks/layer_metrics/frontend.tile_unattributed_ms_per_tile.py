"""Server time per tile that no tile stage covers: the request root
span's age when the encode lands (`/debug` tile_stages.wall_s) less
plan, index, decode, dispatch, readback and encode, over tiles.  What is
left is the gateway, the hop to the render thread and back, the event
loop and the code between the stages.  None where `/debug` has no
`wall_s` (a program without it, or `GSKY_TRACE=0`)."""

from benchmarks.ctx import dig

STAGES = ("plan_s", "index_s", "decode_s", "dispatch_s", "readback_s",
          "encode_s")


def read(ctx):
    if dig(ctx.debug1, "tile_stages.wall_s", None) is None:
        return None
    tiles = ctx.delta("tile_stages.tiles")
    if not tiles:
        return None
    named = sum(ctx.delta(f"tile_stages.{k}") for k in STAGES)
    return 1e3 * (ctx.delta("tile_stages.wall_s") - named) / tiles
