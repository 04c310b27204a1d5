"""Fresh compiles plus loads from the persistent compile cache inside
the window, from JAX's monitoring events: what warm-up did not reach.
Should be 0; every one is a stall some request felt, any compile and
more than `run.py::STRAY_LOADS` loads make the run not `correct`."""


def read(ctx):
    return sum(ctx.compiles_in_window)
