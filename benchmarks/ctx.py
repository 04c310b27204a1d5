"""What a metric reader is given: `read(ctx)` returns a number, or
None where there is nothing to read (the harness then leaves the metric
out of the line)."""

import re
from dataclasses import dataclass
from typing import Optional

import numpy as np

from . import reduce, roofline


def dig(doc, path, default=0):
    """doc["a"]["b"]["c"] for "a.b.c"; `default` where a key is absent."""
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return default
        doc = doc[key]
    return doc


@dataclass
class Ctx:
    cell: object                # spec.Cell
    results: list               # plan.Result of every request of the window
    t0: float                   # perf_counter at the window's start
    window_s: float             # its length: what is sent later is not sent
    setup_s: float
    warmup: list                # run.py::warm_up's passes
    warmed: Optional[int]       # window requests that had a twin, or None
    debug0: dict                # /debug at the window's start ...
    debug1: dict                # ... and at its end (cumulative counters)
    compiles_in_window: tuple   # (fresh compiles, persistent-cache loads)
    device_kind: str
    hbm_peak_bytes: Optional[int]
    trace: Optional[reduce.Trace] = None        # --trace 1 only
    traced_s: float = 0.0       # length of the profiled slice
    busy_s: Optional[float] = None

    # -- the client's side -------------------------------------------------------

    def latencies_ms(self):
        """Latency of every request; one that failed misses every
        latency, so it counts as infinitely late."""
        return np.array([r.latency_s * 1e3 if r.ok else np.inf
                         for r in self.results])

    def halves(self):
        """Each half of the window by itself (the requests sent in it):
        says whether a run drifted, and how much of the spread between
        runs a longer window would average away."""
        out = []
        for k in (0, 1):
            a = self.t0 + k * self.window_s / 2
            b = a + self.window_s / 2
            lat = np.array([r.latency_s * 1e3 for r in self.results
                            if r.ok and a <= r.sent < b])
            done = sum(1 for r in self.results if r.ok and a < r.done <= b)
            out.append({"sent": len(lat), "throughput_rps":
                        done / (self.window_s / 2),
                        "latency_ms": {q: float(np.percentile(lat, q))
                                       for q in (50, 95)} if len(lat) else {}})
        return out

    def latency_percentile_ms(self, q, min_requests=1):
        """The q-th percentile (an observed value, not an interpolated
        one), or None where fewer requests were sent or the
        percentile falls on a failed one."""
        lat = self.latencies_ms()
        if len(lat) < min_requests:
            return None
        v = np.percentile(lat, q, method="lower")
        return v if np.isfinite(v) else None

    # -- the program's counters ---------------------------------------------------

    def delta(self, path):
        """How far a cumulative /debug number moved over the window."""
        return dig(self.debug1, path) - dig(self.debug0, path)

    def ratio(self, num_paths, den_paths, scale=1.0):
        den = sum(self.delta(p) for p in den_paths)
        if not den:
            return None
        return scale * sum(self.delta(p) for p in num_paths) / den

    def legs(self):
        """{dispatch key: count in the window} of executor.dispatches."""
        d0 = dig(self.debug0, "executor.dispatches", {})
        d1 = dig(self.debug1, "executor.dispatches", {})
        return {k: v - d0.get(k, 0) for k, v in d1.items()
                if v - d0.get(k, 0)}

    def first_used(self):
        """Dispatch keys that the process first ran inside the window."""
        d0 = dig(self.debug0, "executor.dispatches", {})
        return sorted(k for k in self.legs() if not d0.get(k))

    # -- the device's side ----------------------------------------------------------

    def peaks(self):
        return roofline.peaks(self.device_kind)

    def module(self, function):
        """(seconds, executions) of a jitted function in the profiled
        slice, or None without a device trace or without executions."""
        if self.trace is None:
            return None
        secs, n = reduce.module_time(self.trace, function)
        return (secs, n) if n else None


def stack_depth(leg_key):
    """Scenes in the stack of a `leg:((n, H, W), window)` dispatch key."""
    m = re.search(r":\(\((\d+),", leg_key)
    return int(m.group(1)) if m else None
