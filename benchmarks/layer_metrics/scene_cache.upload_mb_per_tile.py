"""Bytes the scene cache put on the device inside the window, per tile
answered (`/debug` cache.scene.upload_bytes): 0 where every raster the
window touched was resident.  None from a program that does not count
its uploads."""

from benchmarks.ctx import dig


def read(ctx):
    tiles = sum(1 for r in ctx.results if r.ok)
    if dig(ctx.debug1, "cache.scene.upload_bytes", None) is None or not tiles:
        return None
    return ctx.delta("cache.scene.upload_bytes") / tiles / 1e6
