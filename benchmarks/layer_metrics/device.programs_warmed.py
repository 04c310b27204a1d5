"""Programs the warm-up compiled or loaded from the compile cache before
the window could be measured (JAX's monitoring events over
`run.py::warm_up`'s passes): the part of the program lattice this
traffic walks.  Each costs 0.3 to 3 s of every run's set-up, and of a
deployment's first minutes after every restart."""


def read(ctx):
    return sum(p["fresh"] + p["loads"] for p in ctx.warmup)
