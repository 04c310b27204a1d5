"""The CPU a WPS Execute's device row ran on its worker thread:
`/debug` drill_stages.device_cpu_s Δ over requests, the thread CPU of
the `drill.device` spans (stack look-ups, enqueues, the readback).
Beside `executor.drill_device_ms_per_request` (their wall) it says how
much of that wall is running.  None where the spans carry no CPU."""

from benchmarks.ctx import dig


def read(ctx):
    if dig(ctx.debug1, "drill_stages.device_cpu_s", None) is None:
        return None
    return ctx.ratio(["drill_stages.device_cpu_s"],
                     ["drill_stages.requests"], 1e3)
