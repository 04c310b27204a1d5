"""Device-resident source-scene cache.

The reference amortises IO with a per-process GDAL block cache
(`worker/gdalprocess/warp.go:278-332`); the TPU-native analogue keeps whole
decoded scenes in HBM.  Decode and host->device upload are paid per
byte while HBM is plentiful — so each (path, band) source raster is
decoded and shipped ONCE — NaN-encoded f32, invalid
pixels pre-baked to NaN so per-dispatch validity is one isnan on the
gathered tap — and every subsequent tile request warps from the cached
device array (`ops.warp.warp_scenes_batch`) with only a ~2 KB
control-grid upload.

One byte budget (`device.residency_budget`, from the device's memory)
covers the scenes and the executor's stacks of them (`stack`): a stack
is a copy of scenes that are resident, so stacks go first, least
recently used first, then scenes.  A scene too large for the budget is
not cached (a one-off window read is cheaper than shipping the raster).
"""

from __future__ import annotations

import itertools
import logging
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..geo.crs import CRS, parse_crs
from ..geo.transform import GeoTransform
from .types import Granule


_scene_serial = itertools.count(1)
_log = logging.getLogger("gsky.scene_cache")


@dataclass
class DeviceScene:
    dev: jax.Array            # (bh, bw) f32, invalid=NaN, bucket-padded
    height: int               # true rows
    width: int                # true cols
    nodata: float             # NaN when absent
    gt: GeoTransform
    crs: CRS
    # monotonic identity: downstream caches key on this instead of
    # id(dev), which can be reused after eviction/GC (stale-stack hazard)
    serial: int = field(default_factory=lambda: next(_scene_serial))

    @property
    def bucket(self) -> Tuple[int, int]:
        return self.dev.shape

    @property
    def dtype(self):
        return self.dev.dtype


def _bucket(n: int, step: int = 256) -> int:
    return max(step, (n + step - 1) // step * step)


def _put_scene(data, serial: int):
    """Shard-aware host->device upload: under mesh per-chip placement
    (GSKY_MESH_PLACE=1) the scene ships straight to its owning chip —
    the chip whose page pool will stage its pages — instead of to
    device 0 and letting jit re-shard.  Single-chip / placement-off
    keeps the plain async `device_put` unchanged."""
    try:
        from ..mesh.pools import staging_device
        dev = staging_device(serial)
    except Exception:   # pragma: no cover - mesh optional at runtime
        dev = None
    if dev is None:
        return jax.device_put(data)
    return jax.device_put(data, dev)


def _nbytes(dev) -> int:
    """The committed device allocation: bucket dims x itemsize."""
    return int(np.prod(dev.shape)) * dev.dtype.itemsize


class SceneCache:
    def __init__(self, max_bytes: Optional[int] = None):
        """``max_bytes``: the budget scenes and stacks share; None takes
        the device's (`device.residency_budget`) at the first load."""
        self._lock = threading.Lock()
        self._scenes: Dict[tuple, DeviceScene] = {}
        self._order: List[tuple] = []
        self._bytes = 0
        self._stacks: OrderedDict = OrderedDict()   # key -> (array, bytes)
        self._stack_bytes = 0
        self._max_bytes = max_bytes
        self._inflight: Dict[tuple, threading.Event] = {}
        self.hits = 0
        self.misses = 0
        self.upload_bytes = 0
        self.evictions = 0
        self.stack_evictions = 0
        self._told: set = set()         # (path, why) already warned of
        # ranged-window routing: decline counts per key (promote-to-
        # residency once a "cold" scene turns out to be hot), plus the
        # running total of requests served through the window path
        self._route_counts: Dict[tuple, int] = {}
        self.window_routed = 0
        self.staged_loads = 0

    def _key(self, g: Granule) -> tuple:
        return (g.path, g.band, g.var_name, g.time_index)

    @property
    def max_bytes(self) -> int:
        if self._max_bytes is None:
            from ..device import residency_budget
            self._max_bytes = residency_budget()["budget"]
        return self._max_bytes

    @property
    def max_scene_px(self) -> int:
        """A scene is cacheable if eight of its size fit the budget: its
        sibling bands and the neighbours a tile on its edge touches stay
        resident beside it."""
        return self.max_bytes // (8 * 4)

    def stats(self) -> Dict:
        """The /debug `cache.scene` row: cumulative counters, and every
        resident byte of scenes and stacks beside the budget."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "upload_bytes": self.upload_bytes,
                    "evictions": self.evictions,
                    "resident_bytes": self._bytes,
                    "stacks": len(self._stacks),
                    "stack_bytes": self._stack_bytes,
                    "stack_evictions": self.stack_evictions,
                    "budget_bytes": self.max_bytes}

    def _make_room(self) -> None:  # gskylint: holds-lock
        """Evict until scenes + stacks fit the budget: stacks first (a
        copy of resident scenes is rebuilt by one device copy), then
        scenes, each least recently used first; the newest scene stays."""
        while self._bytes + self._stack_bytes > self.max_bytes:
            if self._stacks:
                _, (_, n) = self._stacks.popitem(last=False)
                self._stack_bytes -= n
                self.stack_evictions += 1
            elif len(self._order) > 1:
                old = self._scenes.pop(self._order.pop(0))
                self._bytes -= _nbytes(old.dev)
                self.evictions += 1
            else:
                break

    def stack(self, key: tuple, make: Callable[[], jax.Array]) -> jax.Array:
        """The executor's stacked copy of resident scenes under ``key``
        (their serials, so a reloaded scene never meets a stale stack),
        built by ``make()`` on a miss and charged its bytes to the
        budget.  One that does not fit beside the resident scenes, once
        every older stack has gone, serves its request and is not kept."""
        with self._lock:
            hit = self._stacks.get(key)
            if hit is not None:
                self._stacks.move_to_end(key)
                return hit[0]
        arr = make()
        n = _nbytes(arr)
        with self._lock:
            if key not in self._stacks and self._bytes + n <= self.max_bytes:
                self._stacks[key] = (arr, n)
                self._stack_bytes += n
                self._make_room()
        return arr

    def _pick_level(self, g: Granule, stride: float) -> int:
        """Decimation level to cache for a request stepping ``stride``
        source pixels per dst pixel: the coarsest GeoTIFF overview that
        fits, or a power-of-two read stride for NetCDF (quantised so a
        zoom sweep shares cache entries instead of one per stride)."""
        if stride < 2.0:
            return 1
        try:
            from .decode import _handles
            h = _handles.get(g.path, g.is_netcdf)
            if g.is_netcdf:
                v = h.variables.get(g.var_name)
                H, W = (v.shape[-2], v.shape[-1]) if v is not None \
                    else (2, 2)
                lv = 1
                while lv * 2 <= stride and H // (lv * 2) >= 2 \
                        and W // (lv * 2) >= 2:
                    lv *= 2
                return lv
            best = 1
            for f, _ in h.overviews:
                if f <= stride:
                    best = f
            return best
        except Exception:
            return 1

    @staticmethod
    def _route_promote() -> int:
        import os
        try:
            return int(os.environ.get("GSKY_INGEST_WINDOW_PROMOTE", 4))
        except (TypeError, ValueError):
            return 4

    def _route_window(self, key: tuple, g: Granule, dst_bbox,
                      dst_crs) -> bool:
        """True when this request should stream through the ranged
        window path instead of forcing whole-scene residency: ingest is
        on, the scene is not (and is not becoming) resident, and the
        request footprint covers less than ``GSKY_INGEST_WINDOW_FRAC``
        of the raster.  After ``GSKY_INGEST_WINDOW_PROMOTE`` declines of
        one key the scene has proven hot and is promoted to residency."""
        try:
            from ..ingest import ingest_enabled, window_route_frac
            if not ingest_enabled():
                return False
            lim = window_route_frac()
            if lim <= 0.0:
                return False
            with self._lock:
                if key in self._scenes or key in self._inflight:
                    return False      # resident scenes always serve
            from .decode import granule_footprint_frac
            frac = granule_footprint_frac(g, dst_bbox, dst_crs)
            if frac is None or frac >= lim:
                return False
            promote = self._route_promote()
            with self._lock:
                n = self._route_counts.get(key, 0) + 1
                self._route_counts[key] = n
                if len(self._route_counts) > 4096:
                    self._route_counts.pop(next(iter(self._route_counts)))
                if 0 < promote <= n:
                    del self._route_counts[key]
                    return False      # hot after all: load it
                self.window_routed += 1
            return True
        except Exception:
            return False

    def get(self, g: Granule, stride: float = 1.0,
            dst_bbox=None, dst_crs=None) -> Optional[DeviceScene]:
        """Cached scene for a granule, decoding + uploading on first use.
        Returns None when the scene is uncacheable (over budget /
        unreadable / no CRS; `_uncacheable` logs which).
        Concurrent requests for the same scene decode once (per-key
        latch), not once per tile.

        ``stride`` (source px per dst px) selects the cached resolution:
        zoomed-out requests get the overview/decimated level — which also
        makes scenes over the budget cacheable once the level fits
        (`worker/gdalprocess/warp.go:156-198`).

        ``dst_bbox``/``dst_crs`` (optional) describe the request
        footprint; with ingest on, a non-resident scene barely touched
        by the request is declined (None) so the caller's existing
        uncacheable-scene fallback serves it through ranged window
        decode instead of paying a whole-scene read + upload."""
        level = self._pick_level(g, stride)
        key = self._key(g) + (level,)
        if dst_bbox is not None and dst_crs is not None and \
                self._route_window(key, g, dst_bbox, dst_crs):
            return None
        while True:
            with self._lock:
                hit = self._scenes.get(key)
                if hit is not None:
                    self.hits += 1
                    self._order.remove(key)
                    self._order.append(key)
                    return hit
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                    self.misses += 1      # under _lock: exact counts
                    break
            ev.wait()

        scene = None
        try:
            scene = self._load(g, level)
            if scene is not None:
                nbytes = _nbytes(scene.dev)
                with self._lock:
                    self._scenes[key] = scene
                    self._order.append(key)
                    self._bytes += nbytes
                    self.upload_bytes += nbytes
                    self._make_room()
        finally:
            with self._lock:
                self._inflight.pop(key).set()
        return scene

    def clear(self) -> None:
        """Drop every resident scene (chaos/ops hook — forces the next
        request through the full decode path again).  In-flight loads
        are untouched: they re-insert under the lock when they finish."""
        with self._lock:
            self._scenes.clear()
            self._order.clear()
            self._bytes = 0
            self._stacks.clear()
            self._stack_bytes = 0

    def _staging_read(self, h, band: int, W: int, H: int, ovr,
                      nodata):
        """Decode a whole GeoTIFF scene straight into a pooled,
        page-grid-padded f32 staging buffer: one allocation, in-place
        NaN-encode, and `device_put` ships the same memory (zero
        intermediate copies).  Returns (buf, pool) or (None, None) for
        the classic path.  Only sources whose f32 cast is value-exact
        (f32, and int/uint ≤ 16 bit with an f32-exact nodata) stage —
        anything else would change the nodata compare and break the
        GSKY_INGEST=0 byte-identity contract."""
        try:
            from ..ingest import ingest_enabled
            from ..io.geotiff import GeoTIFF
            if not ingest_enabled() or not isinstance(h, GeoTIFF):
                return None, None
            dt = h.dtype
            exact = (dt.kind == "f" and dt.itemsize == 4) or \
                (dt.kind in "iu" and dt.itemsize <= 2)
            if not exact:
                return None, None
            if nodata is not None:
                ndf = float(nodata)
                if not (np.isnan(ndf) or float(np.float32(ndf)) == ndf):
                    return None, None
            from ..ingest.staging import default_staging_pool
            pool = default_staging_pool()
            buf = pool.acquire(_bucket(H), _bucket(W))
            try:
                h.read(band, (0, 0, W, H), ifd=ovr, out=buf[:H, :W])
            except Exception:
                pool.release(buf)
                return None, None
            return buf, pool
        except Exception:
            return None, None

    def _uncacheable(self, g: Granule, why: str) -> None:
        """Say why a scene is served by window decode instead (over
        budget, unreadable, no CRS): once per file and reason, since a
        deployment that falls there does so on every tile."""
        with self._lock:
            new = (g.path, why) not in self._told
            if new and len(self._told) < 4096:
                self._told.add((g.path, why))
        if new:
            _log.warning("scene uncacheable, window-path fallback: %s (%s)",
                         g.path, why)
        return None

    def _over_budget(self, g: Granule, H: int, W: int) -> None:
        return self._uncacheable(
            g, f"over budget: {H} x {W} px, and a scene may hold "
               f"{self.max_scene_px} px of a {self.max_bytes}-byte budget")

    def _load(self, g: Granule, level: int = 1) -> Optional[DeviceScene]:
        from .decode import _handles
        gt = GeoTransform.from_gdal(g.geo_transform)
        crs = parse_crs(g.srs) if g.srs else None
        if crs is None:
            return self._uncacheable(g, "no CRS")
        sbuf = spool = None
        try:
            from ..resilience import faults
            faults.inject("decode")
            h = _handles.get(g.path, g.is_netcdf)
            if g.is_netcdf:
                v = h.variables.get(g.var_name)
                if v is None:
                    return self._uncacheable(
                        g, f"unreadable: no variable {g.var_name!r}")
                H, W = v.shape[-2], v.shape[-1]
                st = level if level > 1 and H // level >= 2 \
                    and W // level >= 2 else 1
                if (H // st) * (W // st) > self.max_scene_px:
                    return self._over_budget(g, H // st, W // st)
                Ho, Wo = H // st, W // st
                data = h.read_slice(g.var_name, g.time_index,
                                    (0, 0, Wo * st, Ho * st), step=st)
                if st > 1:
                    gt = gt.decimated(st)
                nodata = g.nodata if g.nodata is not None else v.nodata
            else:
                W, H = h.width, h.height
                ovr = None
                if level > 1 and getattr(h, "overviews", ()):
                    fx, fy, ovr = h.pick_overview(float(level))
                if ovr is not None:
                    gt = gt.scaled(fx, fy)
                    W, H = ovr.width, ovr.height
                if H * W > self.max_scene_px:
                    return self._over_budget(g, H, W)
                nodata = g.nodata if g.nodata is not None else h.nodata
                sbuf, spool = self._staging_read(h, g.band, W, H, ovr,
                                                 nodata)
                if sbuf is not None:
                    data = None
                elif ovr is not None:
                    data = h.read(g.band, (0, 0, W, H), ifd=ovr)
                else:
                    # no ifd kwarg here: the registry read contract is
                    # plain read(band, window) — handles that don't
                    # declare an ifd kwarg (HDF4) raised TypeError into
                    # the except below and were silently uncacheable,
                    # falling back to the window path every render
                    data = h.read(g.band, (0, 0, W, H))
        except Exception as e:
            # "uncacheable" must stay a degradation, never a crash — but
            # it must also be VISIBLE: a signature drift in a handle's
            # read() once hid here as a silent slow path for the format
            return self._uncacheable(
                g, f"unreadable: {type(e).__name__}: {e}")
        nd = float(nodata) if nodata is not None else float("nan")
        from ..ingest import stats as _istats
        if sbuf is not None:
            # staged load: the buffer IS the scene — encode in place,
            # ship it, and cool it in the pool until the async upload
            # completes (recycling under an in-flight DMA would corrupt
            # the resident scene)
            from ..ops.raster import nodata_mask
            view = sbuf[:H, :W]
            if not np.isnan(nd):
                valid = nodata_mask(view, nd)
                valid &= np.isfinite(view)
                view[~valid] = np.nan
            serial = next(_scene_serial)
            dev = _put_scene(sbuf, serial)
            spool.release(sbuf, dev)
            _istats.record_whole(H * W * h.dtype.itemsize)
            with self._lock:
                self.staged_loads += 1
            return DeviceScene(dev=dev, height=H, width=W,
                               nodata=float("nan"), gt=gt, crs=crs,
                               serial=serial)
        _istats.record_whole(data.nbytes)
        true_h, true_w = data.shape
        # NaN-encode ONCE at load: invalid pixels (nodata / non-finite)
        # become NaN in an f32 scene, so every later dispatch's validity
        # is a single isnan on the gathered tap — no per-dispatch
        # full-scene dtype cast or nodata compare on any backend.  The
        # f32 precision equals what the kernels always computed in
        # (the old path cast per dispatch); memory is 2x an int16 scene,
        # paid from the same LRU byte budget.
        from ..ops.raster import nodata_mask
        if data.dtype != np.float32 or not np.isnan(nd):
            # (f32 + NaN-nodata sources are already in encoded form —
            # skip three full-scene host passes on that common case)
            valid = nodata_mask(data, nd if not np.isnan(nd) else None)
            data = data.astype(np.float32)
            # inf (incl. f64 overflowing the f32 cast) is invalid too,
            # so the documented "validity == ~isnan" invariant holds
            valid &= np.isfinite(data)
            data[~valid] = np.nan
        bh, bw = _bucket(true_h), _bucket(true_w)
        if (bh, bw) != data.shape:
            pad = np.full((bh, bw), np.nan, np.float32)
            pad[:true_h, :true_w] = data
            data = pad
        # device_put, not jnp.asarray: the async host->device upload
        # returns immediately with the transfer in flight, so the
        # loading thread (the staged tile path's decode stage) moves on
        # to the next scene while DMA drains; the first kernel that
        # consumes the scene synchronizes.  nbytes accounting is exact
        # either way: the cache charges bucket dims x itemsize, which
        # is precisely the committed device allocation.
        serial = next(_scene_serial)
        dev = _put_scene(data, serial)
        return DeviceScene(dev=dev, height=true_h, width=true_w,
                           nodata=float("nan"), gt=gt, crs=crs,
                           serial=serial)


# module-level default (shared across pipelines/requests)
default_scene_cache = SceneCache()
