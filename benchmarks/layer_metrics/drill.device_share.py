"""Share of per-band drills answered from the device-resident stack
(`/debug` executor.dispatches drill_device over drill_device +
drill_host)."""


def read(ctx):
    return ctx.ratio(["executor.dispatches.drill_device"],
                     ["executor.dispatches.drill_device",
                      "executor.dispatches.drill_host"], 100.0)
