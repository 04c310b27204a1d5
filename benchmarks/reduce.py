"""From a profiler trace (`.xplane.pb`) and the program's span file to
numbers: device busy time, time per operation and per XLA module, and
the longest idle gaps with what the host was doing in each.

Read with nothing but JAX (`jax.profiler.ProfileData`).  A TPU's plane
is named `/device:TPU:<n>`; its line `XLA Ops` holds one event per
executed HLO operation and `XLA Modules` one per executed program
(`jit_<function>(<fingerprint>)`).  Times in a plane are nanoseconds
from the start of the profiling session.

    python benchmarks/reduce.py <file.xplane.pb>      # look at one by hand
"""

import json
import re
import sys
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Line:
    names: list             # one per event
    start_ns: np.ndarray
    dur_ns: np.ndarray


@dataclass
class Trace:
    planes: dict = field(default_factory=dict)      # plane -> {line: Line}

    def devices(self):
        return sorted(p for p in self.planes
                      if re.fullmatch(r"/device:TPU:\d+", p))


def load(path):
    from jax.profiler import ProfileData
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        lines = trace.planes.setdefault(plane.name, {})
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            lines[line.name] = Line(
                [e[0] for e in ev],
                np.array([e[1] for e in ev], np.float64),
                np.array([e[2] for e in ev], np.float64))
    return trace


def union(start, dur):
    """Merged, sorted (k, 2) intervals of possibly overlapping events."""
    if not len(start):
        return np.zeros((0, 2))
    order = np.argsort(start)
    s, e = start[order], (start + dur)[order]
    e = np.maximum.accumulate(e)
    # a new interval begins where an event starts after all before it ended
    new = np.concatenate([[True], s[1:] > e[:-1]])
    firsts = np.flatnonzero(new)
    lasts = np.concatenate([firsts[1:] - 1, [len(s) - 1]])
    return np.stack([s[firsts], e[lasts]], 1)


def busy(trace):
    """(busy seconds averaged over the devices, device 0's busy
    intervals in ns): the union of the intervals in which an operation
    ran.  None where the trace holds no device operation."""
    per_device, first = [], None
    for dev in trace.devices():
        line = trace.planes[dev].get(OPS_LINE)
        if line is None or not len(line.start_ns):
            continue
        iv = union(line.start_ns, line.dur_ns)
        per_device.append(float((iv[:, 1] - iv[:, 0]).sum()) / 1e9)
        if first is None:
            first = iv
    if not per_device:
        return None
    return float(np.mean(per_device)), first


def time_by_name(trace, line_name=OPS_LINE):
    """{event name: (seconds, count)} summed over the devices."""
    out = {}
    for dev in trace.devices():
        line = trace.planes[dev].get(line_name)
        if line is None:
            continue
        for name, d in zip(line.names, line.dur_ns):
            s, n = out.get(name, (0.0, 0))
            out[name] = (s + d / 1e9, n + 1)
    return out


def module_time(trace, function):
    """(seconds, executions) of the XLA modules of one jitted function:
    `jit_<function>` with or without the `(<fingerprint>)` suffix."""
    want = re.compile(rf"jit_{re.escape(function)}(\(\d+\))?$")
    secs = count = 0
    for name, (s, n) in time_by_name(trace, MODULES_LINE).items():
        if want.match(name):
            secs += s
            count += n
    return secs, count


def short_op(hlo):
    """`%fusion.7 = f32[65536]{...} fusion(...), kind=kCustom, calls=...`
    -> `fusion.7 fusion:kCustom f32[65536]`: the operation's name, what
    it is and the (first) shape it makes."""
    name, eq, rest = hlo.partition(" = ")
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    op = re.search(r"[ )]([a-z][\w\-]*)\(", rest)
    if not (eq and shape and op):
        return hlo[:80]
    kind = re.search(r"kind=(\w+)", rest)
    what = op.group(1) + (":" + kind.group(1) if kind else "")
    return f"{name.lstrip('%')} {what} {shape.group(0)}"


def top_ops(trace, n=10):
    """[[name, seconds], ...]: the operations that took most time, each
    named `<module>/<operation>` by the XLA module that was running when
    it ran, the module's fingerprint taken off so that reruns agree."""
    agg = {}
    for dev in trace.devices():
        ops = trace.planes[dev].get(OPS_LINE)
        mods = trace.planes[dev].get(MODULES_LINE)
        if ops is None:
            continue
        if mods is not None and len(mods.start_ns):
            order = np.argsort(mods.start_ns)
            starts = mods.start_ns[order]
            ends = (mods.start_ns + mods.dur_ns)[order]
            at = np.searchsorted(starts, ops.start_ns, side="right") - 1
            inside = (at >= 0) & (ops.start_ns < ends[np.maximum(at, 0)])
            names = [re.sub(r"\(\d+\)$", "", mods.names[order[i]]) if ok
                     else "?" for i, ok in zip(at, inside)]
        else:
            names = ["?"] * len(ops.names)
        for mod, op, d in zip(names, ops.names, ops.dur_ns):
            key = f"{mod}/{short_op(op)}"
            agg[key] = agg.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(agg.items(),
                                      key=lambda kv: -kv[1])[:n]]


def gaps(intervals, t0_ns, t1_ns):
    """(k, 2) idle intervals inside [t0, t1] between busy `intervals`."""
    edges = np.concatenate([[t0_ns], intervals.reshape(-1), [t1_ns]])
    g = edges.reshape(-1, 2)
    g = np.clip(g, t0_ns, t1_ns)
    return g[g[:, 1] > g[:, 0]]


def read_spans(path):
    """Flat [(name, t0, t1), ...] on the wall clock from the program's
    GSKY_TRACE_FILE (one trace with its spans per line)."""
    out = []
    try:
        with open(path) as fp:
            for raw in fp:
                try:
                    doc = json.loads(raw)
                except ValueError:
                    continue
                for sp in doc.get("spans") or ():
                    if sp.get("dur_s") is not None:
                        out.append((sp["name"], sp["t0"],
                                    sp["t0"] + sp["dur_s"]))
    except OSError:
        pass
    return out


def label_gaps(idle, spans, wall0, n=10, nothing="no request in flight"):
    """[[label, seconds], ...] for the n longest idle gaps: the program
    span open at the gap's middle that began last (the innermost), by a
    wall-clock stamp `wall0` taken when the profiling session began —
    millisecond alignment, which is enough to name a stage."""
    out = []
    order = np.argsort(idle[:, 0] - idle[:, 1])[:n]
    for a, b in idle[order]:
        mid = wall0 + (a + b) / 2e9
        open_ = [s for s in spans if s[1] <= mid <= s[2]]
        label = max(open_, key=lambda s: s[1])[0] if open_ else nothing
        out.append([label, float(b - a) / 1e9])
    return out


def describe(path, top=12):
    """A trace by hand: planes, lines, their spans and busiest names."""
    trace = load(path)
    for pname, lines in trace.planes.items():
        print(f"PLANE {pname}")
        for lname, line in lines.items():
            if not len(line.start_ns):
                print(f"  LINE {lname!r}: empty")
                continue
            iv = union(line.start_ns, line.dur_ns)
            print(f"  LINE {lname!r}: {len(line.names)} events, "
                  f"{line.start_ns.min() / 1e9:.6f}.."
                  f"{(line.start_ns + line.dur_ns).max() / 1e9:.6f} s, "
                  f"union {(iv[:, 1] - iv[:, 0]).sum() / 1e9:.6f} s")
            agg = {}
            for name, d in zip(line.names, line.dur_ns):
                s, n = agg.get(name, (0.0, 0))
                agg[name] = (s + d / 1e9, n + 1)
            for name, (s, n) in sorted(agg.items(),
                                       key=lambda kv: -kv[1][0])[:top]:
                print(f"      {s:10.6f} s  x{n:<6d} {name[:100]}")


if __name__ == "__main__":
    describe(sys.argv[1])
