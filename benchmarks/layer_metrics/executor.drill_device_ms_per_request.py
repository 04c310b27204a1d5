"""Host time round the device path per Execute, summed over its files:
the resident stack's lookup, mask upload, `window_gather` and the
reduction until the values are host arrays (`drill.device` spans, folded
into `/debug` drill_stages.device_s over requests).  Less
`kernels.drill_ms_per_request` it is dispatch overhead and waiting."""


def read(ctx):
    return ctx.ratio(["drill_stages.device_s"], ["drill_stages.requests"], 1e3)
