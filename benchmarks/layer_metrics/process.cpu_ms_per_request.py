"""CPU of the whole serving process per request answered in the window:
`/debug` process.cpu_s (`time.process_time()` when /debug is read, every
thread's user and system time) Δ over the requests the window answered.
The benchmark's client runs in the same process, so its CPU is in here
too; in a `--trace 1` run so is the profiler's.  None where `/debug` has
no `process` block."""


def read(ctx):
    if "process" not in ctx.debug0 or "process" not in ctx.debug1:
        return None
    answered = sum(1 for r in ctx.results if r.ok)
    if not answered:
        return None
    return 1e3 * ctx.delta("process.cpu_s") / answered
