"""Process start to the first request of the window: build, archive,
boot, prewarm, scene and stack loads, warm-up; in a first run of a
checkout, compilation too."""


def read(ctx):
    return ctx.setup_s
