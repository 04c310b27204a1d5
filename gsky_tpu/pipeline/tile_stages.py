"""Stage-overlapped GetMap/GetTile hot path.

`pipeline/export.py` showed that a bounded decode -> warp -> encode
pipeline keeps every stage busy on different tiles; this module applies
the same architecture to single-tile GetMap requests, where the unit of
overlap is the REQUEST: instead of one opaque worker-thread blob per
request (index + decode + dispatch + blocking readback serialized
end-to-end), each request's render decomposes into

    plan -> index -> decode -> dispatch -> readback

stages with bounded per-stage concurrency (module-level gates sized by
GSKY_TILE_* knobs).  Concurrent requests then overlap like export
tiles do: request A's device output is in flight to the host
(`copy_to_host_async`, issued by the executor's `_prefetch` before the
dispatch gate releases) while request B occupies the dispatch slot and
request C decodes scenes — double-buffering across the request stream.
PNG/JPEG encode runs on `io/png.py`'s sized pool, off the event loop.

The stages call the prep/dispatch halves of `TilePipeline`'s own
render methods (`composite_prep`/`composite_dispatch`, `_bands_prep`/
`_rgba_try`/`_bands_dispatch`); what `render_staged` declines takes the
modular route (`OWSServer._render_with_fusion`), which shares neither
with it and is what tests/test_tile_pipeline.py holds the staged
answer to.

Per-request stage spans land in the ``spans`` dict (seconds per stage +
queue high-water marks) and are folded into /debug's ``tile_stages``
block via `server/metrics.py::record_tile`, mirroring `record_export`.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Optional

import numpy as np

from ..obs import span as obs_span
from ..resilience import check_cancel


def _env_int(name: str, default: int, lo: int = 1, hi: int = 64) -> int:
    try:
        v = int(os.environ.get(name, default))
    except ValueError:
        return default
    return max(lo, min(hi, v))


class StageGate:
    """Bounded stage admission: a semaphore plus the telemetry the
    /debug `tile_stages` block needs — occupancy high-water (how many
    requests were at the gate when one arrived), cumulative busy
    seconds, cumulative seconds requests waited for a slot, entry
    count.  One gate per stage, shared by every request in the process,
    so the bounds hold across concurrent handlers.  The wait is a
    `tile.<name>_gate` span, so that a request queued at the gate is an
    annotation on the profiler's clock."""

    def __init__(self, name: str, limit: int):
        self.name = name
        self.limit = limit
        self._sem = threading.Semaphore(limit)
        self._lock = threading.Lock()
        self._span = f"tile.{name}_gate"
        self.waiting = 0          # requests at the gate right now
        self.queue_max = 0        # high-water of `waiting`
        self.busy_s = 0.0
        self.wait_s = 0.0
        self.entries = 0

    @contextlib.contextmanager
    def enter(self, spans: Optional[Dict] = None,
              qkey: Optional[str] = None):
        with self._lock:
            self.waiting += 1
            occupancy = self.waiting
            if occupancy > self.queue_max:
                self.queue_max = occupancy
        if spans is not None and qkey:
            # occupancy INCLUDING self, like export's qsize()+1 marks:
            # 1 means uncontended, >1 means the stage actually queued
            spans[qkey] = max(spans.get(qkey, 0), occupancy)
        with obs_span(self._span):
            t0 = time.perf_counter()
            self._sem.acquire()
            waited = time.perf_counter() - t0
        with self._lock:
            self.waiting -= 1
            self.entries += 1
            self.wait_s += waited
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._sem.release()
            with self._lock:
                self.busy_s += dt

    def stats(self) -> Dict:
        with self._lock:
            return {"limit": self.limit, "waiting": self.waiting,
                    "queue_max": self.queue_max, "entries": self.entries,
                    "busy_s": round(self.busy_s, 6),
                    "wait_s": round(self.wait_s, 6)}


_gates: Dict[str, StageGate] = {}
_gates_lock = threading.Lock()

# stage -> (env knob, default limit).  Decode admits several requests
# (scene loads are IO + host work and the scene cache latches dedup
# concurrent loads of one scene); dispatch stays narrow — the device
# stream is one queue, and two slots give exactly the double-buffer:
# one request's dispatch issues while the previous one's output
# transfer (started under the gate via _prefetch) drains.
_STAGES = {"decode": ("GSKY_TILE_DECODE_WORKERS", 4),
           "dispatch": ("GSKY_TILE_DISPATCH_SLOTS", 2)}


def _gate(name: str) -> StageGate:
    g = _gates.get(name)
    if g is None:
        with _gates_lock:
            g = _gates.get(name)
            if g is None:
                env, default = _STAGES[name]
                g = _gates[name] = StageGate(name, _env_int(env, default))
    return g


def reset_gates() -> None:
    """Drop the process gates so the next request re-reads the sizing
    knobs (tests; never needed on a serving path)."""
    with _gates_lock:
        _gates.clear()


def gate_stats() -> Dict:
    with _gates_lock:
        return {n: g.stats() for n, g in _gates.items()}


def _decode_stage(pipe, req, granules, spans: Dict) -> None:
    """Warm every distinct scene into the device cache under the decode
    gate.  Purely a prefetch: failures are swallowed here because the
    dispatch stage re-resolves each scene through the same cache and
    surfaces (or degrades) errors itself — identical outcomes, just
    earlier, bounded, and overlapped."""
    from .export import _scene_key
    gate = _gate("decode")
    check_cancel("decode")
    t0 = time.perf_counter()
    with gate.enter(spans, "decode_queue_max"):
        seen = set()
        dst_gt = req.dst_gt()
        for g in granules:
            # per-granule: an abandoned request stops warming scenes
            # and releases the decode slot within one granule
            check_cancel("decode")
            k = _scene_key(g)
            if k in seen:
                continue
            seen.add(k)
            try:
                pipe.executor.warm_scene(g, dst_gt, req.crs,
                                         req.height, req.width)
            except Exception:  # prewarm is advisory - the render path decodes on miss
                pass
    spans["decode_s"] = spans.get("decode_s", 0.0) \
        + time.perf_counter() - t0


def _dispatch_stage(dispatch, spans: Dict):
    """Run one device dispatch under the dispatch gate.  The executor's
    render functions `_prefetch` their outputs (copy_to_host_async)
    before returning, so by the time the gate releases the
    device->host transfer is already in flight — the next request's
    dispatch overlaps this one's readback."""
    from .waves import waves_enabled
    from ..ingest import stats as ingest_stats
    check_cancel("dispatch")
    t0 = time.perf_counter()
    try:
        # mark the device-busy window: ranged reads running while ANY
        # dispatch is in flight count as overlapped IO in the
        # gsky_ingest_overlap_ratio gauge
        with ingest_stats.dispatch_inflight(), obs_span("tile.dispatch") as sp:
            try:
                from ..server.prewarm import compile_count
                c0 = compile_count()
            except Exception:
                compile_count, c0 = None, 0
            try:
                if waves_enabled():
                    # the wave scheduler NEEDS concurrent arrivals to
                    # coalesce into one dispatch; a narrow gate here
                    # would serialize them and defeat it, so it keeps
                    # its own admission (wave size + brownout clamp)
                    sp.set(waved=True)
                    return dispatch()
                with _gate("dispatch").enter(spans, "dispatch_queue_max"):
                    # re-check AFTER the gate wait: the client may have
                    # gone away while this request queued for the slot
                    check_cancel("dispatch")
                    return dispatch()
            finally:
                if compile_count is not None:
                    sp.set(fresh_compile=compile_count() > c0)
                sp.set(queue_max=spans.get("dispatch_queue_max", 0))
    finally:
        spans["dispatch_s"] = spans.get("dispatch_s", 0.0) \
            + time.perf_counter() - t0


def _readback(dev, spans: Dict) -> np.ndarray:
    """Complete the in-flight device->host copy.  No gate: the transfer
    was started under the dispatch gate; this just blocks until the
    bytes land, which is exactly the overlap window other requests use.
    The sync runs under the device guard: hang watchdog
    (GSKY_DEVICE_HANG_S), incident classification, and the output
    integrity probe (docs/RESILIENCE.md "Device failures")."""
    check_cancel("readback")
    t0 = time.perf_counter()
    with obs_span("tile.readback") as sp:
        from .. import device_guard
        arr = device_guard.guarded_readback(
            "tile.readback", lambda: np.asarray(dev))
        sp.set(bytes=int(arr.nbytes))
    spans["readback_s"] = spans.get("readback_s", 0.0) \
        + time.perf_counter() - t0
    return arr


def render_staged(pipe, req, n_exprs: int,
                  offset: float = 0.0, scale: float = 0.0,
                  clip: float = 0.0, colour_scale: int = 0,
                  auto: bool = True,
                  stats: Optional[Dict[str, int]] = None,
                  spans: Optional[Dict] = None):
    """The staged GetMap fast path, run inside the request's worker
    thread.  Returns (kind, host_array) with kind in {"composite",
    "rgba", "planes"}, or None when the request doesn't qualify for the
    fused path — callers then fall back to the modular render.

    Stage structure per request:
      plan      qualification + namespace/selection resolution (host)
      index     the MAS query (timed inside the prep via _timed_index)
      decode    scene warm into the device cache, bounded by the gate
      dispatch  ONE fused device dispatch, bounded; output prefetched
      readback  np.asarray completing the in-flight transfer
    """
    spans = spans if spans is not None else {}
    t0 = time.perf_counter()
    with obs_span("tile.plan") as psp:
        if n_exprs == 1:
            made = pipe.composite_prep(req, stats, spans)
        elif n_exprs == 3:
            made = pipe._bands_prep(req, n_bands=3, stats=stats,
                                    spans=spans)
        else:
            made = pipe._bands_prep(req, stats=stats, spans=spans)
        psp.set(qualified=made is not None)
        if made is not None and len(made) == 5:     # `_expr_prep` form
            psp.set(expr=made[4].hash, slots=made[3])
    # "plan" is the prep minus the index query it contains
    spans["plan_s"] = spans.get("plan_s", 0.0) \
        + max(0.0, time.perf_counter() - t0 - spans.get("index_s", 0.0))
    if made is None:
        return None

    granules = made[0]
    with obs_span("tile.decode") as dsp:
        _decode_stage(pipe, req, granules, spans)
        dsp.set(granules=len(granules),
                queue_max=spans.get("decode_queue_max", 0))

    if n_exprs == 1:
        dev = _dispatch_stage(
            lambda: pipe.composite_dispatch(req, made, offset, scale,
                                            clip, colour_scale, auto),
            spans)
        kind = "composite"
    elif n_exprs == 3:
        granules, ns_index, out_sel = made
        dev = _dispatch_stage(
            lambda: pipe._rgba_try(req, granules, ns_index, out_sel,
                                   offset, scale, clip, colour_scale,
                                   auto),
            spans)
        kind = "rgba"
        if dev is None:
            dev = _dispatch_stage(
                lambda: pipe._bands_dispatch(req, granules, ns_index,
                                             out_sel, offset, scale,
                                             clip, colour_scale, auto),
                spans)
            kind = "planes"
    else:
        granules, ns_index, out_sel = made
        dev = _dispatch_stage(
            lambda: pipe._bands_dispatch(req, granules, ns_index,
                                         out_sel, offset, scale, clip,
                                         colour_scale, auto),
            spans)
        kind = "planes"
    if dev is None:
        return None
    return kind, _readback(dev, spans)
