"""Share of the device's idle time, between the first and the last
operation of the profiled slice, during which a full collection of the
cyclic collector held the process: a `gc.collect` event on any line of
the profile's `/host:CPU` plane (`gsky_tpu/obs/process.py` annotates
every generation-2 collection), in %.  0 where the program counts
collections (`/debug` process.gc) and none fell in the slice's idle
time; None where it does not, or without a device trace.

    PYTHONPATH=. python3 benchmarks/layer_metrics/device.idle_under_gc_share.py <file.xplane.pb>

prints the share, the collections in the slice and how many of the ten
longest idle gaps a collection overlaps.
"""

import sys

import numpy as np

from benchmarks import reduce, spec
from benchmarks.ctx import dig

NAME = "gc.collect"
_IDLE = spec.reader("layer_metrics", "device.idle_outside_stages_share")


def collections(events):
    """Merged (k, 2) ns intervals in which a `gc.collect` event was
    open on some thread."""
    found = [(s, d) for name, s, d in events if name == NAME]
    if not found:
        return np.zeros((0, 2))
    return reduce.union(*np.array(found, np.float64).T)


def share(trace, gc_intervals):
    """Percent of the slice's device-idle time under a collection, or
    None where no operation ran on a device."""
    gaps = _IDLE.idle(trace)
    if gaps is None or not _IDLE.length(gaps):
        return None
    return 100.0 * _IDLE.overlap(gaps, gc_intervals) / _IDLE.length(gaps)


def read(ctx):
    if ctx.trace is None or dig(ctx.debug1, "process.gc", None) is None:
        return None
    path = _IDLE.kept_slice(ctx)
    events = _IDLE.events_in(path) if path else _IDLE.events_of(ctx.trace)
    return share(ctx.trace, collections(events))


def describe(path):
    trace = reduce.load(path)
    found = collections(_IDLE.events_in(path))
    gaps = _IDLE.idle(trace)
    if gaps is None:
        print("no operation ran on a device")
        return
    print(f"{len(found)} full collection(s) in the slice, "
          f"{_IDLE.length(found) / 1e9:.6f} s; device idle under them: "
          f"{share(trace, found)} %")
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])[:10]]
    under = [g for g in longest if _IDLE.overlap(g[None], found) > 0]
    print(f"  {len(under)} of the {len(longest)} longest idle gaps "
          f"overlap a collection")
    for g in longest:
        print(f"  gap {(g[1] - g[0]) / 1e6:9.3f} ms, under a collection "
              f"{_IDLE.overlap(g[None], found) / 1e6:9.3f} ms")


if __name__ == "__main__":
    describe(sys.argv[1])
