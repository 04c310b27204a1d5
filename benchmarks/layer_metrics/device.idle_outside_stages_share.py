"""Share of the device's idle time, between the first and the last
operation of the profiled slice, during which no stage of the program
was open on any host thread.  The program's spans are annotations on the
profile's `/host:CPU` plane (`gsky_tpu/obs/trace.py::span`), in the same
nanoseconds as `XLA Ops`, so no wall-clock stamp is involved.  What is
left outside is the time nothing names yet: the event loop, the client,
hand-offs between threads.  A program without such annotations reads
None.

    PYTHONPATH=. python3 benchmarks/layer_metrics/device.idle_outside_stages_share.py <file.xplane.pb>

prints `by_stage`, the device-idle seconds under each stage name, and how
many executions of the two main programs began inside their stage.
"""

import os
import re
import sys

import numpy as np

from benchmarks import reduce

HOST = "/host:CPU"
STAGE = re.compile(r"(tile|drill|wps)\..+|encode")


def events_of(trace):
    """(name, start_ns, dur_ns) of every host event a `reduce.Trace`
    holds."""
    for line in trace.planes.get(HOST, {}).values():
        yield from zip(line.names, line.start_ns, line.dur_ns)


def events_in(path):
    """The same from the file, every line of it: `reduce.load` keys a
    plane's lines by name and each Python thread's line is called
    `python`, so it keeps one thread of the program and loses the rest."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST:
            for line in plane.lines:
                for e in line.events:
                    yield e.name, e.start_ns, e.duration_ns


def stages(events):
    """{stage name: merged (k, 2) ns intervals in which a span of that
    name was open on some thread}."""
    found = {}
    for name, start, dur in events:
        if STAGE.fullmatch(name):
            found.setdefault(name, []).append((start, dur))
    return {name: reduce.union(*np.array(sd, np.float64).T)
            for name, sd in found.items()}


def idle(trace):
    """(k, 2) ns intervals without a device operation, between the
    first and the last one; None where no operation ran."""
    made = reduce.busy(trace)
    if not made:
        return None
    intervals = made[1]
    return reduce.gaps(intervals, intervals[0, 0], intervals[-1, 1])


def length(intervals):
    return float((intervals[:, 1] - intervals[:, 0]).sum())


def merged(sets):
    """One merged (k, 2) set out of several."""
    iv = np.concatenate([np.zeros((0, 2)), *sets])
    return reduce.union(iv[:, 0], iv[:, 1] - iv[:, 0])


def overlap(a, b):
    """ns in which an interval of `a` and one of `b` are both open (each
    merged): |a| + |b| - |a or b|."""
    return length(a) + length(b) - length(merged([a, b]))


def by_stage(trace, found=None):
    """{stage name: device-idle seconds while a span of that name was
    open}.  An instant counts under every stage open then, so the sums
    may exceed the idle total."""
    gaps = idle(trace)
    if gaps is None:
        return {}
    if found is None:
        found = stages(events_of(trace))
    return {name: overlap(gaps, iv) / 1e9 for name, iv in found.items()}


def outside_share(trace, found):
    """Percent of the idle time under no stage at all."""
    gaps = idle(trace)
    if gaps is None or not length(gaps) or not found:
        return None
    covered = overlap(gaps, merged(found.values()))
    return 100.0 * (1.0 - covered / length(gaps))


def begin_inside(trace, function, intervals):
    """(executions of `jit_<function>` that began while one of
    `intervals` was open, all its executions) on the devices."""
    want = re.compile(rf"jit_{re.escape(function)}(\(\d+\))?$")
    starts = []
    for dev in trace.devices():
        line = trace.planes[dev].get(reduce.MODULES_LINE)
        if line is not None:
            starts += [s for name, s in zip(line.names, line.start_ns)
                       if want.match(name)]
    starts = np.array(starts)
    if not len(starts) or not len(intervals):
        return 0, len(starts)
    at = np.searchsorted(intervals[:, 0], starts, side="right") - 1
    inside = (at >= 0) & (starts < intervals[np.maximum(at, 0), 1])
    return int(inside.sum()), len(starts)


def kept_slice(ctx):
    """`run.py` keeps the slice as <out>/<cell>.xplane.pb beside the
    span file it names in GSKY_TRACE_FILE."""
    spans = os.environ.get("GSKY_TRACE_FILE", "")
    path = os.path.join(os.path.dirname(spans), f"{ctx.cell.name}.xplane.pb")
    return path if spans and os.path.isfile(path) else None


def read(ctx):
    if ctx.trace is None:
        return None
    path = kept_slice(ctx)
    return outside_share(ctx.trace, stages(
        events_in(path) if path else events_of(ctx.trace)))


def describe(path):
    trace = reduce.load(path)
    found = stages(events_in(path))
    gaps = idle(trace)
    if gaps is None:
        print("no operation ran on a device")
        return
    print(f"device idle {length(gaps) / 1e9:.6f} s between the first and "
          f"the last operation; outside every stage: "
          f"{outside_share(trace, found)} %")
    for name, s in sorted(by_stage(trace, found).items(),
                          key=lambda kv: -kv[1]):
        print(f"  {s:10.6f} s idle under {name} "
              f"(open {length(found[name]) / 1e9:.6f} s)")
    for function, names in (("window_gather", ("drill.device",)),
                            ("render_scenes_ctrl", ("tile.dispatch",
                                                    "tile.readback"))):
        n, of = begin_inside(trace, function, merged(
            found[name] for name in names if name in found))
        if of:
            print(f"  {n} of {of} jit_{function} executions began inside "
                  f"{' or '.join(names)}")


if __name__ == "__main__":
    describe(sys.argv[1])
