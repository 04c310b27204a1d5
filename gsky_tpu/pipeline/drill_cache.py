"""Device-resident drill stack cache.

The drill hot loop (`worker/gdalprocess/drill.go:128-220`) reads the
polygon window of every selected timestep from disk per request; served
from the host, every request would also ship that (B, window) block to
the device — ~64 MB for the 1000-step benchmark.  The TPU-native answer
mirrors `pipeline.scene_cache`: the
WHOLE variable stack (T, H, W) uploads once in its native dtype and
stays in HBM; each drill request then ships only a rasterized polygon
mask and a timestep index vector (KBs), and the window slice + masked
reductions run on device (`ops.drill.window_gather`).

Eviction is LRU by device bytes.  Stacks above ``max_item_bytes`` are
not cached (one-off window reads through the host path are cheaper than
pinning HBM on them); 64-bit stacks are not cached either, because the
upload would silently downcast (x64 is off in production) and break
nodata parity with the host path.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..device import DRILL_STACK_BYTES

_stack_serial = itertools.count(1)


@dataclass
class DeviceStack:
    dev: object               # jax (T, H, W) native dtype
    nodata: float             # NaN when absent
    serial: int = field(default_factory=lambda: next(_stack_serial))

    @property
    def shape(self):
        return self.dev.shape

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.dev.shape)) * self.dev.dtype.itemsize


class DrillStackCache:
    def __init__(self, max_bytes: int = DRILL_STACK_BYTES,
                 max_item_bytes: int = 1 << 30,
                 max_negative: int = 4096,
                 max_background_loads: int = 2):
        self._lock = threading.Lock()
        # bound on concurrent get_async loader threads: a cold drill
        # over a many-file collection must not fan out one full-raster
        # load (+ host buffer + upload) per file at once — unscheduled
        # misses stay on the host path and retry on a later request
        self._bg_slots = threading.BoundedSemaphore(max_background_loads)
        self._stacks: Dict[tuple, DeviceStack] = {}
        self._order: List[tuple] = []
        self._bytes = 0
        self._max_bytes = max_bytes
        self._max_item = max_item_bytes
        # permanently-uncacheable keys (too big / wrong dtype), bounded;
        # transient load errors are NOT recorded, so they retry
        self._neg: Dict[tuple, None] = {}
        self._max_neg = max_negative
        self._inflight: Dict[tuple, threading.Event] = {}
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _key(path: str, var_name: str, band0: int,
             nodata: Optional[float]):
        """(key, mtime) or None when the file can't be stat'd.  NaN
        can't be a dict-key component (NaN != NaN would miss every
        hit); absent/NaN nodata normalises to a sentinel."""
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            return None
        nd_key = "nan" if nodata is None or \
            (isinstance(nodata, float) and np.isnan(nodata)) \
            else float(nodata)
        return (path, mtime, var_name, band0, nd_key), mtime

    def get(self, path: str, is_nc: bool, var_name: str, band0: int,
            nodata: Optional[float]) -> Optional[DeviceStack]:
        """Cached (T, H, W) stack for one file variable/band, uploading
        on first use (BLOCKING until the upload lands).  None when
        uncacheable (too big, 64-bit, or unreadable — unreadable retries
        next request).  Concurrent first requests load once.  ``nodata``
        is part of the identity: two collections indexing the same file
        with different overrides get distinct (correct) masks."""
        made = self._key(path, var_name, band0, nodata)
        if made is None:
            return None
        key, mtime = made
        while True:
            with self._lock:
                hit = self._stacks.get(key)
                if hit is not None:
                    self.hits += 1
                    self._order.remove(key)
                    self._order.append(key)
                    return hit
                if key in self._neg:
                    # a cached negative answer is a hit of the cache's
                    # decision, not an uncounted branch
                    self.hits += 1
                    return None
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                    self.misses += 1      # under _lock: exact counts
                    break
            ev.wait()
        return self._load_into(key, mtime, path, is_nc, var_name, band0,
                               nodata)

    def get_async(self, path: str, is_nc: bool, var_name: str,
                  band0: int,
                  nodata: Optional[float]) -> Optional[DeviceStack]:
        """Resident stack, or None immediately — scheduling a
        background load on a first miss so a LATER request hits.  The
        cold request then runs at host-read speed instead of blocking
        on a multi-second stack upload through the device link (the
        cfg5 cold-path fix): first drill ~= the CPU baseline, steady
        state on-device."""
        made = self._key(path, var_name, band0, nodata)
        if made is None:
            return None
        key, mtime = made
        with self._lock:
            hit = self._stacks.get(key)
            if hit is not None:
                self.hits += 1
                self._order.remove(key)
                self._order.append(key)
                return hit
            if key in self._neg:
                self.hits += 1
                return None
            if key in self._inflight:
                return None          # load already on its way
            if not self._bg_slots.acquire(blocking=False):
                return None          # loader pool saturated: retry later
            self._inflight[key] = threading.Event()
            self.misses += 1

        def load_and_release():
            try:
                self._load_into(key, mtime, path, is_nc, var_name,
                                band0, nodata)
            finally:
                self._bg_slots.release()

        threading.Thread(target=load_and_release,
                         name="gsky-drill-upload", daemon=True).start()
        return None

    def wait_idle(self, timeout_s: float = 60.0) -> bool:
        """Block until no loads are in flight (benches/tests separating
        cold from warm).  True when idle within the timeout."""
        deadline = time.monotonic() + timeout_s
        while True:
            with self._lock:
                evs = list(self._inflight.values())
            if not evs:
                return True
            for ev in evs:
                if not ev.wait(max(deadline - time.monotonic(), 0.0)):
                    return False

    def clear(self) -> None:
        """Drop every resident stack (bench cold-path measurement)."""
        with self._lock:
            self._stacks.clear()
            self._order.clear()
            self._neg.clear()
            self._bytes = 0

    def _load_into(self, key, mtime, path, is_nc, var_name, band0,
                   nodata) -> Optional[DeviceStack]:
        """Load + insert under the inflight latch taken by the caller."""
        stack = None
        permanent_no = False
        try:
            stack, permanent_no = self._load(path, is_nc, var_name,
                                             band0, nodata)
            with self._lock:
                if stack is not None:
                    # a new mtime supersedes older entries for the file
                    for old in [k for k in self._order
                                if k[0] == path and k[1] != mtime]:
                        self._order.remove(old)
                        self._bytes -= self._stacks.pop(old).nbytes
                    self._stacks[key] = stack
                    self._order.append(key)
                    self._bytes += stack.nbytes
                    while self._bytes > self._max_bytes and \
                            len(self._order) > 1:
                        old = self._order.pop(0)
                        self._bytes -= self._stacks.pop(old).nbytes
                elif permanent_no:
                    if len(self._neg) >= self._max_neg:
                        self._neg.pop(next(iter(self._neg)))
                    self._neg[key] = None
        finally:
            with self._lock:
                self._inflight.pop(key).set()
        return stack

    def _load(self, path: str, is_nc: bool, var_name: str, band0: int,
              nodata: Optional[float]):
        """(stack or None, permanently_uncacheable)."""
        import jax.numpy as jnp

        from .decode import _handles
        try:
            h = _handles.get(path, is_nc)
            if is_nc:
                v = h.variables.get(var_name)
                if v is None:
                    return None, True
                itemsize = np.dtype(v.dtype).itemsize
                if itemsize > 4:
                    return None, True   # would downcast on upload
                if len(v.shape) == 2:
                    T, (H, W) = 1, v.shape
                else:
                    T, H, W = v.shape[0], v.shape[-2], v.shape[-1]
                nd = nodata if nodata is not None else v.nodata
                if T * H * W * itemsize > self._max_item:
                    return None, True
                if len(v.shape) <= 3:
                    data = np.asarray(v[:])
                    if data.ndim == 2:
                        data = data[None]
                else:   # rank 4: (t, level0, y, x) per-timestep reads
                    data = np.stack([
                        h.read_slice(var_name, t, (0, 0, W, H))
                        for t in range(T)])
            else:
                W, H = h.width, h.height
                ifd = getattr(h, "ifd", None)
                if ifd is not None:
                    from ..io.geotiff import T_BITS
                    bits = ifd.arr(T_BITS) or (32,)
                    itemsize = max(int(bits[0]) // 8, 1)
                else:       # registry handle (GMT/adapter)
                    itemsize = np.dtype(
                        getattr(h, "dtype", np.float32)).itemsize
                if itemsize > 4:
                    return None, True
                nd = nodata if nodata is not None else h.nodata
                if H * W * itemsize > self._max_item:
                    return None, True
                data = h.read(band0, (0, 0, W, H))[None]
            if data.dtype.itemsize > 4:
                return None, True
            # the device upload itself stays inside the try: a full HBM
            # (RESOURCE_EXHAUSTED) must degrade to host reads, not kill
            # the request — and must retry later (transient)
            dev = jnp.asarray(data)
        except Exception:
            return None, False
        return DeviceStack(dev=dev,
                           nodata=float(nd) if nd is not None
                           else float("nan")), False


# module-level default (shared across requests); anything CPU-bound can
# disable via GSKY_DRILL_CACHE=0; GSKY_DRILL_CACHE=sync restores the
# blocking first-request upload (deterministic paths for tests)
def enabled() -> bool:
    return os.environ.get("GSKY_DRILL_CACHE", "1") != "0"


def sync_mode() -> bool:
    return os.environ.get("GSKY_DRILL_CACHE", "1") == "sync"


default_drill_cache = DrillStackCache()
