"""Ingest accounting: one thread-safe ledger for both decode paths.

Every byte the process pulls from a granule funnels through here, on
both sides of the ``GSKY_INGEST`` escape hatch:

* the **ranged** path (`ingest.source.fetch_ranges`) records one entry
  per coalesced range request plus the exact bytes fetched — these are
  COMPRESSED on-disk/on-wire bytes, the number an object store bills;
* the **whole** path (scene-cache full-scene loads and the plain
  window decode that `GSKY_INGEST=0` restores) records the logical
  bytes it materialised, so the ingest soak
  can state the reduction as ranged-vs-whole on the same ledger.

Overlap: the dispatch stages (`tile_stages._dispatch_stage`,
`export.py`'s dispatch) mark themselves in flight here; a ranged read
that completes while any dispatch is in flight counts its wall seconds
as *overlapped* — hidden behind device compute rather than serialized
in front of it.  ``gsky_ingest_overlap_ratio`` is overlapped/total.

Prefetch outcomes (`hit`/`miss`/`wasted`) are recorded by the
`PrefetchPlanner`; the ledger just counts them so `/metrics` exposes
one `gsky_prefetch_total{outcome}` family.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict

_lock = threading.Lock()


class _Ledger:
    def __init__(self) -> None:
        self.ranged_reads = 0          # coalesced range requests issued
        self.ranged_read_bytes = 0     # bytes fetched by those requests
        self.ranged_windows = 0        # logical windows served ranged
        self.whole_reads = 0           # whole-path reads (scene/window)
        self.whole_read_bytes = 0      # logical bytes those materialised
        self.read_s = 0.0              # wall seconds in ranged fetches
        self.overlap_s = 0.0           # ... of which dispatch-overlapped
        self.dispatch_inflight = 0     # device dispatches in flight now
        self.prefetch = {"hit": 0, "miss": 0, "wasted": 0}
        self.fallbacks = 0             # ranged attempt fell back to plain


_L = _Ledger()


def record_ranged(requests: int, nbytes: int, seconds: float = 0.0) -> None:
    with _lock:
        _L.ranged_reads += int(requests)
        _L.ranged_read_bytes += int(nbytes)
        _L.read_s += float(seconds)
        if _L.dispatch_inflight > 0:
            _L.overlap_s += float(seconds)


def record_ranged_window() -> None:
    with _lock:
        _L.ranged_windows += 1


def record_whole(nbytes: int) -> None:
    with _lock:
        _L.whole_reads += 1
        _L.whole_read_bytes += int(nbytes)


def record_fallback() -> None:
    with _lock:
        _L.fallbacks += 1


def record_prefetch(outcome: str, n: int = 1) -> None:
    with _lock:
        if outcome in _L.prefetch:
            _L.prefetch[outcome] += int(n)


@contextlib.contextmanager
def dispatch_inflight():
    """Mark one device dispatch in flight for the overlap accounting —
    wrapped around the dispatch gates by `tile_stages` and the export
    engine, so concurrent ranged reads know their wall time is hidden
    behind compute rather than ahead of it."""
    with _lock:
        _L.dispatch_inflight += 1
    try:
        yield
    finally:
        with _lock:
            _L.dispatch_inflight -= 1


def overlap_ratio() -> float:
    with _lock:
        return (_L.overlap_s / _L.read_s) if _L.read_s > 0 else 0.0


def snapshot() -> Dict:
    with _lock:
        return {
            "ranged_reads": _L.ranged_reads,
            "ranged_read_bytes": _L.ranged_read_bytes,
            "ranged_windows": _L.ranged_windows,
            "whole_reads": _L.whole_reads,
            "whole_read_bytes": _L.whole_read_bytes,
            "read_s": round(_L.read_s, 6),
            "overlap_s": round(_L.overlap_s, 6),
            "overlap_ratio": round(
                (_L.overlap_s / _L.read_s) if _L.read_s > 0 else 0.0, 6),
            "dispatch_inflight": _L.dispatch_inflight,
            "prefetch": dict(_L.prefetch),
            "fallbacks": _L.fallbacks,
        }


def reset() -> None:
    """Test/bench hook: zero the ledger (the in-flight dispatch count
    survives — it tracks live context managers, not history)."""
    global _L
    with _lock:
        inflight = _L.dispatch_inflight
        _L = _Ledger()
        _L.dispatch_inflight = inflight
