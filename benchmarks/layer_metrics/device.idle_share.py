"""Share of the profiled slice in which no operation ran on the device:
1 - union of device-op intervals / slice."""


def read(ctx):
    if ctx.busy_s is None or not ctx.traced_s:
        return None
    return 100.0 * max(0.0, 1.0 - ctx.busy_s / ctx.traced_s)
