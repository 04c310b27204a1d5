"""The warp stage's busy time per export: its tiles' renders, enqueued
on the device one after another, and the hand-over to the encoders
(inside the `export.warp_stage` span, less the waits for the decode
stage; `/debug` export_pipeline.warp_s over exports)."""


def read(ctx):
    return ctx.ratio(["export_pipeline.warp_s"],
                     ["export_pipeline.exports"], 1e3)
