"""Share of scene look-ups that found the scene on the device (`/debug`
cache.scene hits over hits + misses)."""


def read(ctx):
    return ctx.ratio(["cache.scene.hits"],
                     ["cache.scene.hits", "cache.scene.misses"], 100.0)
