"""The process's CPU clock and the cyclic collector's pauses.

CPython runs a collection on whichever thread allocates, with the
interpreter lock held, so while it runs no other Python thread of the
process moves.  `GCWatch` is a `gc.callbacks` hook that counts the
collections of each generation, sums their seconds and keeps the
longest.  A full collection (generation 2) is also a
`jax.profiler.TraceAnnotation` called `gc.collect` for its length, so
that while a profile runs it is an event on the `/host:CPU` plane, on
the device trace's clock, beside the spans of `obs/trace.py`.

`install()` is called once when the server starts; `/debug`'s `process`
block and the `gsky_gc_*` series read the same object.  The hook does
not depend on `GSKY_TRACE`.  It never raises: a collection runs inside
whatever allocation triggered it.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, Optional


def _annotation_class():
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # absent JAX = no annotation; the counts work without it
        return None
    return TraceAnnotation


class GCWatch:
    """Counts and times the collector's runs.  Lock-free: CPython runs
    one collection at a time, under the interpreter lock, and calls the
    hook on the collecting thread at its start and its stop."""

    def __init__(self, annotation=None):
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self.longest_s = 0.0
        self._annotation = annotation   # a TraceAnnotation-like class
        self._t0: Optional[float] = None
        self._open = None               # the full collection's annotation

    def __call__(self, phase: str, info: Dict) -> None:
        try:
            if phase == "start":
                self._t0 = time.perf_counter()
                if info["generation"] == 2 and self._annotation is not None:
                    self._enter()
                return
            t0, self._t0 = self._t0, None
            if t0 is None:      # installed while a collection ran
                return
            dt = time.perf_counter() - t0
            gen = info["generation"]
            self.collections[gen] += 1
            self.pause_s[gen] += dt
            if dt > self.longest_s:
                self.longest_s = dt
            if self._open is not None:
                self._leave()
        except Exception:  # a collection must never fail the code it interrupted
            pass

    def _enter(self) -> None:
        try:
            ann = self._annotation("gc.collect")
            ann.__enter__()
            self._open = ann
        except Exception:  # a broken profiler costs the event, not the count
            self._open = None

    def _leave(self) -> None:
        ann, self._open = self._open, None
        try:
            ann.__exit__(None, None, None)
        except Exception:  # a broken profiler costs the event, not the count
            pass

    def install(self) -> "GCWatch":
        """Add the hook to `gc.callbacks`, once however often called."""
        if not any(cb is self for cb in gc.callbacks):
            gc.callbacks.append(self)
        return self

    def uninstall(self) -> None:
        gc.callbacks[:] = [cb for cb in gc.callbacks if cb is not self]

    @property
    def installed(self) -> bool:
        return any(cb is self for cb in gc.callbacks)

    def stats(self) -> Dict:
        return {"collections": list(self.collections),
                "pause_s": [round(s, 6) for s in self.pause_s],
                "longest_s": round(self.longest_s, 6)}


_WATCH: Optional[GCWatch] = None


def install() -> GCWatch:
    """The process's watch, hooked into the collector (idempotent).
    Called when the server starts, after JAX is imported."""
    global _WATCH
    if _WATCH is None:
        _WATCH = GCWatch(_annotation_class())
    return _WATCH.install()


def gc_stats() -> Optional[Dict]:
    """The watch's counters, or None where no watch is installed."""
    w = _WATCH
    return w.stats() if w is not None and w.installed else None


def process_stats() -> Dict:
    """/debug `process`: `cpu_s`, the CPU seconds of every thread of
    the process since it started (`time.process_time`), read now; and
    `gc` (`GCWatch.stats`) where the watch is installed."""
    out: Dict = {"cpu_s": round(time.process_time(), 6)}
    st = gc_stats()
    if st is not None:
        out["gc"] = st
    return out
