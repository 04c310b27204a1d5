"""The encode stage's busy time per export, summed over its workers:
each tile's planes pulled off the device and copied into the export's
canvas (inside the `export.encode_stage` spans; `/debug`
export_pipeline.encode_s over exports).  It waits for the device, so it
holds the kernel's time where the device is the pace."""


def read(ctx):
    return ctx.ratio(["export_pipeline.encode_s"],
                     ["export_pipeline.exports"], 1e3)
