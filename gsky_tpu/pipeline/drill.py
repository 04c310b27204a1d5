"""The drill pipeline: polygon time-series statistics (WPS Execute).

Reference dataflow: DrillIndexer -> GeoDrillGRPC -> DrillMerger
(`processor/drill_pipeline.go`).  Here:

1. index: MAS ?intersects with the polygon WKT
2. fast path: crawler-precomputed means/sample_counts answer without
   touching files (`processor/drill_grpc.go:70-93`)
3. else per file: rasterize the polygon into the file grid (the
   GDALRasterizeGeometries burn, `worker/gdalprocess/drill.go:275-327`),
   read the masked window, run the banded reductions on device
   (`gsky_tpu.ops.drill`), optionally strided + interpolated
4. merge: per-date weighted means across files (weights = pixel counts,
   `processor/drill_merger.go:54-93`), then band expressions per date
   (`drill_merger.go:110-155`); decile columns become `ns_d1..9`
   namespaces (`drill_pipeline.go:72-83`)
"""

from __future__ import annotations

import contextlib
import logging
import math
import xml.etree.ElementTree as ET
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..geo import geometry as geom
from ..geo.crs import EPSG4326, parse_crs
from ..geo.transform import GeoTransform
from ..index.client import Dataset, MASClient
from ..index.store import fmt_time
from ..io.geotiff import GeoTIFF
from ..io.netcdf import NetCDF
from ..obs import span as obs_span
from ..ops import drill as D
from ..ops.raster import nodata_mask
from .types import DrillResult, GeoDrillRequest

log = logging.getLogger("gsky.drill")

_BIG = 3.0e38


def split_by_years(req: "GeoDrillRequest", year_step: int):
    """Year-stepped request splitting — the TimeSplitter stage
    (`processor/date_splitter.go:19-31`): yields copies of ``req``
    covering consecutive ``year_step``-year windows of its time range
    (the last window may extend past end_time, as the reference's
    AddDate loop does).  ``year_step <= 0`` yields the request as is."""
    import dataclasses
    import datetime as _dt

    if year_step <= 0 or req.start_time is None or req.end_time is None:
        yield req
        return

    def add_years(ts: float, n: int) -> float:
        d = _dt.datetime.fromtimestamp(ts, _dt.timezone.utc)
        try:
            d = d.replace(year=d.year + n)
        except ValueError:      # Feb 29 -> Mar 1, Go AddDate behaviour
            d = d.replace(year=d.year + n, month=3, day=1)
        return d.timestamp()

    if req.start_time >= req.end_time:
        # point-in-time query: splitting has nothing to window
        yield req
        return
    t = req.start_time
    while t < req.end_time:
        # clamp: unlike the reference (which chunks an already-filtered
        # timestamp list), each window here widens a MAS query, so an
        # unclamped last window would return rows past end_time
        nxt = add_years(t, year_step)
        yield dataclasses.replace(req, start_time=t,
                                  end_time=min(nxt, req.end_time))
        t = nxt


def merge_results(parts: List["DrillResult"]) -> "DrillResult":
    """Concatenate per-window DrillResults (windows from
    `split_by_years` are disjoint, so rows merge by date sort)."""
    parts = [p for p in parts if p.dates]
    if not parts:
        return DrillResult([], {}, {}, [])
    if len(parts) == 1:
        return parts[0]
    names: List[str] = []
    for p in parts:
        for n in p.values:
            if n not in names:
                names.append(n)
    rows = {}
    counts_rows = {}
    for p in parts:
        for i, d in enumerate(p.dates):
            row = rows.setdefault(d, {})
            crow = counts_rows.setdefault(d, {})
            for n in p.values:
                row[n] = p.values[n][i]
                crow[n] = p.counts.get(n, [0] * len(p.dates))[i]
    dates = sorted(rows)
    values = {n: [rows[d].get(n, float("nan")) for d in dates]
              for n in names}
    counts = {n: [counts_rows[d].get(n, 0) for d in dates] for n in names}
    raw = sorted({n for p in parts for n in p.raw_namespaces})
    return DrillResult(dates, values, counts, raw)


class DrillPipeline:
    def __init__(self, mas: MASClient):
        self.mas = mas

    def process_split(self, req: GeoDrillRequest,
                      year_step: int = 0) -> DrillResult:
        """TimeSplitter-wired entry: split the request into year-stepped
        windows, drill each, and merge (`processor/date_splitter.go`)."""
        parts = [self.process(w) for w in split_by_years(req, year_step)]
        with obs_span("drill.merge") as sp:
            res = merge_results(parts)
            sp.set(dates=len(res.dates), namespaces=len(res.values))
        return res

    def index(self, req: GeoDrillRequest) -> List[Dataset]:
        namespaces = list(req.band_exprs.var_list) \
            + [n for n in req.mask_namespaces
               if n not in req.band_exprs.var_list]
        kw = dict(srs="EPSG:4326", wkt=req.geometry_wkt,
                  namespaces=",".join(namespaces))
        if req.start_time is not None:
            kw["time"] = fmt_time(req.start_time)
        if req.end_time is not None:
            kw["until"] = fmt_time(req.end_time)
        with obs_span("drill.index") as sp:
            datasets = self.mas.intersects(req.collection, **kw)
            sp.set(datasets=len(datasets),
                   timestamps=sum(len(d.timestamps) for d in datasets))
        return datasets

    def process(self, req: GeoDrillRequest) -> DrillResult:
        # large-polygon tiling (`drill_indexer.go:115-137`): each tiled
        # sub-geometry runs the index + per-file reductions separately,
        # and the (namespace, date) accumulator merges them count-
        # weighted, so memory stays bounded by one tile's window.
        # Known deviation from the untiled result (shared with the
        # reference): adjacent clipped sub-polygons both ALL_TOUCHED-burn
        # the shared boundary row, so edge pixels count in two tiles and
        # the merged mean skews by O(perimeter/area)
        tiles = tiled_geometries(req.geometry_wkt,
                                 req.index_tile_x_size,
                                 req.index_tile_y_size)
        if len(tiles) > 1:
            import dataclasses
            acc: Dict[Tuple[str, float],
                      List[Tuple[float, int]]] = defaultdict(list)
            approx_seen: set = set()
            for wkt in tiles:
                sub = dataclasses.replace(req, geometry_wkt=wkt,
                                          index_tile_x_size=0.0,
                                          index_tile_y_size=0.0)
                self._drill_into(sub, acc, approx_seen)
        else:
            acc = defaultdict(list)
            self._drill_into(req, acc)
        with obs_span("drill.merge") as sp:
            res = _merge(acc, req)
            sp.set(dates=len(res.dates), namespaces=len(res.raw_namespaces))
        return res

    def _drill_into(self, req: GeoDrillRequest, acc,
                    approx_seen: Optional[set] = None) -> None:
        datasets = self.index(req)
        g4326 = geom.from_wkt(req.geometry_wkt)

        mask_ds = [d for d in datasets
                   if d.namespace in set(req.mask_namespaces)]
        data_ds = [d for d in datasets if d not in mask_ds]

        # the files of one answer share what depends only on the polygon
        # and the grid, and are all enqueued before the one readback; what
        # each adds to `acc` is kept in data_ds order, the order in which
        # weighted means add up
        group = _DrillGroup(g4326, req)
        drilled: List[Tuple[Dataset, List[int],
                            Optional["_FileDrill"]]] = []
        for ds in data_ds:
            sel = _selected_times(ds, req)
            if not sel:
                continue
            vrt_xml = None
            if req.vrt_xml:
                # per-granule VRT rendering (`drill_indexer.go:318-346`):
                # exactly ONE temporally co-registered mask granule per
                # requested mask namespace, placed at that namespace's
                # position in req.mask_namespaces so in_ar band order is
                # stable for asymmetric pixel functions
                # (`drill_indexer.go:355-380` places maskGrans[iv] and
                # errors on duplicates)
                from ..io.vrt import render_vrt
                masks = []
                for ns in req.mask_namespaces:
                    cands = [m for m in mask_ds
                             if m.namespace == ns and _times_match(ds, m)]
                    if len(cands) > 1:
                        # the reference's group key is (polygon,
                        # timestamps): spatially tiled mask collections
                        # produce several temporal matches, of which the
                        # co-located tile is the right one
                        same_tile = [m for m in cands
                                     if m.polygon == ds.polygon]
                        if len(same_tile) == 1:
                            cands = same_tile
                    if len(cands) > 1:
                        raise ValueError(
                            f"multiple mask granules for namespace {ns!r} "
                            f"co-registered with {ds.file_path}")
                    if not cands:
                        # count mismatch is an indexer error in the
                        # reference (`drill_indexer.go:309-315`)
                        raise ValueError(
                            f"no mask granule for namespace {ns!r} "
                            f"co-registered with {ds.file_path}")
                    masks.append(cands[0].file_path)
                vrt_xml = render_vrt(req.vrt_xml, ds.file_path, masks)
            elif req.approx and ds.means and ds.sample_counts \
                    and len(ds.means) >= len(ds.timestamps):
                # crawler-stats fast path: no file IO at all.  The stats
                # are WHOLE-FILE aggregates, so under polygon tiling a
                # file spanning several tiles must contribute exactly
                # once or merged means skew toward multi-tile files
                if approx_seen is not None:
                    k = (ds.file_path, ds.ds_name, ds.namespace)
                    if k in approx_seen:
                        continue
                    approx_seen.add(k)
                drilled.append((ds, sel, None))
                continue
            drilled.append((ds, sel, group.start(ds, sel, vrt_xml)))
        group.collect()

        for ds, sel, f in drilled:
            if f is None:       # the crawler's statistics answer
                with obs_span("drill.merge", dates=len(sel), namespaces=1):
                    for ti in sel:
                        date = ds.timestamps[ti] if ds.timestamps else 0.0
                        acc[(ds.namespace, date)].append(
                            (float(ds.means[min(ti, len(ds.means) - 1)]),
                             int(ds.sample_counts[
                                 min(ti, len(ds.sample_counts) - 1)])))
                continue
            if f.stats is None:
                continue
            values, counts, deciles = f.stats
            # `files`: one file answered, by the device or by host reads
            # (/debug drill_stages.files sums it)
            with obs_span("drill.merge", files=1, dates=len(sel),
                          namespaces=1 + req.deciles):
                for k, ti in enumerate(sel):
                    date = ds.timestamps[ti] if ds.timestamps else 0.0
                    acc[(ds.namespace, date)].append(
                        (float(values[k]), int(counts[k])))
                    for d in range(req.deciles):
                        acc[(f"{ds.namespace}_d{d + 1}", date)].append(
                            (float(deciles[k, d]), 1))


def _geoloc_drill_mask(ds: Dataset, g4326: geom.Geometry, H: int,
                       W: int):
    """Polygon membership over a CURVILINEAR swath: every sample carries
    its own coordinates, so membership is a vectorised containment test
    on the geolocation arrays — the swath analogue of the affine
    ALL_TOUCHED burn.  Returns (mask (uint8, window-shaped), window
    (c0, r0, c1, r1) in RASTER pixels) or None when nothing matches.

    Handles the details the naive test misses: the geometry is taken in
    the geo_loc record's OWN srs (not ds.srs, which rulesets may
    override); antimeridian-crossing swaths compare on the grid's
    unwrapped longitude branch; a bbox prefilter crops the grid before
    the O(edges x samples) ray cast; geoloc line/pixel offsets+steps map
    grid indices to raster pixels (subsampled geolocation grids); and
    point/line/sub-sample-size geometries fall back to marking the
    samples nearest their vertices, so a tiny drill doesn't silently
    report "no data"."""
    from ..geo.geoloc import load_geoloc_grid
    grid = load_geoloc_grid(ds.file_path, ds.geo_loc)
    if grid is None:
        return None
    gl_srs = ds.geo_loc.get("srs") or "EPSG:4326"
    try:
        gl_crs = parse_crs(gl_srs)
        g = g4326 if gl_crs == EPSG4326 else g4326.transform(
            lambda x, y: EPSG4326.transform_to(gl_crs, x, y))
    except ValueError:
        return None
    if grid._wraps:
        # the grid longitudes live on the unwrapped [180, 360) branch
        g = g.transform(lambda x, y: (np.where(np.asarray(x) < 0.0,
                                               np.asarray(x) + 360.0,
                                               np.asarray(x)), y))

    gh, gw = grid.gx.shape
    inpoly = np.zeros((gh, gw), bool)
    if g.polys:
        b = g.bbox()
        with np.errstate(invalid="ignore"):
            box = ((grid.gx >= b.xmin) & (grid.gx <= b.xmax)
                   & (grid.gy >= b.ymin) & (grid.gy <= b.ymax))
        if box.any():
            rr = np.nonzero(box.any(axis=1))[0]
            cc = np.nonzero(box.any(axis=0))[0]
            sr, er = int(rr[0]), int(rr[-1]) + 1
            sc, ec = int(cc[0]), int(cc[-1]) + 1
            inpoly[sr:er, sc:ec] = geom.contains_mask(
                g, grid.gx[sr:er, sc:ec], grid.gy[sr:er, sc:ec])
    if not inpoly.any():
        # point/line drills and polygons smaller than sample spacing:
        # nearest-sample marking (the ALL_TOUCHED-style floor)
        pts = []
        if g.points is not None:
            pts.append(np.asarray(g.points, np.float64))
        for poly in g.polys:
            for ring in poly:
                if len(ring):
                    pts.append(np.asarray(ring, np.float64))
        if not pts:
            return None
        pts_a = np.concatenate(pts, axis=0)
        col, row = grid.invert(pts_a[:, 0], pts_a[:, 1])
        # invert() returns RASTER pixel coords; back to grid indices
        gj = np.rint((col - 0.5 - grid.pixel_offset)
                     / grid.pixel_step).astype(np.int64)
        gi = np.rint((row - 0.5 - grid.line_offset)
                     / grid.line_step).astype(np.int64)
        ok = (gi >= 0) & (gi < gh) & (gj >= 0) & (gj < gw)
        if not ok.any():
            return None
        inpoly[gi[ok], gj[ok]] = True

    rr = np.nonzero(inpoly.any(axis=1))[0]
    cc = np.nonzero(inpoly.any(axis=0))[0]
    gr0, gr1 = int(rr[0]), int(rr[-1]) + 1
    gc0, gc1 = int(cc[0]), int(cc[-1]) + 1
    # grid indices -> raster pixels via the geoloc offsets/steps; a
    # subsampled geolocation grid (pixel_step > 1) expands each sample
    # to its step-sized block of raster pixels
    ls = max(int(grid.line_step), 1)
    ps = max(int(grid.pixel_step), 1)
    r0 = int(grid.line_offset + ls * gr0)
    c0 = int(grid.pixel_offset + ps * gc0)
    sub = inpoly[gr0:gr1, gc0:gc1]
    mask = np.repeat(np.repeat(sub, ls, axis=0), ps, axis=1)
    r1 = min(r0 + mask.shape[0], H)
    c1 = min(c0 + mask.shape[1], W)
    if r0 >= r1 or c0 >= c1:
        return None
    mask = mask[:r1 - r0, :c1 - c0].astype(np.uint8)
    if not mask.any():
        return None
    return mask, (c0, r0, c1, r1)


def tiled_geometries(wkt: str, step_x: float,
                     step_y: float) -> List[str]:
    """Split an area geometry into index-tile intersections
    (`drill_indexer.go:386-520` getTiledGeometries): a grid of
    (step_x, step_y)-degree tiles over the envelope, each clipped
    against the polygon; non-area geometries and disabled steps pass
    through whole.  Degenerate output falls back to the whole
    geometry (reference behaviour on getTiledGeometries error)."""
    if step_x <= 0.0 and step_y <= 0.0:
        return [wkt]
    try:
        g = geom.from_wkt(wkt)
        if g.kind not in ("Polygon", "MultiPolygon") or g.is_empty:
            return [wkt]
        b = g.bbox()
        sx = step_x if step_x > 0 else (b.xmax - b.xmin) or 1.0
        sy = step_y if step_y > 0 else (b.ymax - b.ymin) or 1.0
        if b.xmax - b.xmin <= sx and b.ymax - b.ymin <= sy:
            return [wkt]
        from ..geo.transform import BBox as _BBox
        # integer tile counts, not float accumulation: stepping x += sx
        # emits ~1e-16-wide sliver tiles when the extent divides evenly,
        # and ALL_TOUCHED burns re-count the whole edge row for them
        nx = max(int(math.ceil((b.xmax - b.xmin) / sx - 1e-9)), 1)
        ny = max(int(math.ceil((b.ymax - b.ymin) / sy - 1e-9)), 1)
        out = []
        for iy in range(ny):
            y1 = b.ymax - iy * sy
            y0 = max(y1 - sy, b.ymin)
            for ix in range(nx):
                x0 = b.xmin + ix * sx
                x1 = min(x0 + sx, b.xmax)
                c = g.clip_bbox(_BBox(x0, y0, x1, y1))
                if not c.is_empty:
                    out.append(c.to_wkt())
        return out or [wkt]
    except Exception:
        return [wkt]


def _times_match(data: Dataset, mask: Dataset) -> bool:
    """A mask granule rides with a data granule when their timestamp
    sets overlap (or either carries none)."""
    if not data.timestamps or not mask.timestamps:
        return True
    return bool(set(data.timestamps) & set(mask.timestamps))


def _selected_times(ds: Dataset, req: GeoDrillRequest) -> List[int]:
    if not ds.timestamps:
        return [0]
    out = []
    for i, t in enumerate(ds.timestamps):
        if req.start_time is not None and t < req.start_time - 1:
            continue
        if req.end_time is not None and t > req.end_time + 1:
            continue
        out.append(i)
    return out


def _drill_window(ds: Dataset, g4326: geom.Geometry, h, H: int, W: int,
                  is_vrt: bool):
    """The window of an open file that a geometry covers and its mask
    there: (mask (uint8, window-shaped), (c0, r0, c1, r1) in raster
    pixels), or None where the geometry misses the file or the file's
    SRS cannot be used."""
    try:
        if is_vrt and h.crs is not None:
            src_crs = h.crs
        else:
            src_crs = parse_crs(ds.srs) if ds.srs else EPSG4326
        gt = h.gt if is_vrt else \
            GeoTransform.from_gdal(ds.geo_transform)
        g = g4326 if src_crs == EPSG4326 else g4326.transform(
            lambda x, y: EPSG4326.transform_to(src_crs, x, y))
    except ValueError:  # unparseable SRS / out-of-domain projection
        return None

    if getattr(ds, "geo_loc", None) and not is_vrt:
        return _geoloc_drill_mask(ds, g4326, H, W)
    # envelope intersect + ALL_TOUCHED mask burn
    b = g.bbox()
    c0, r0 = gt.geo_to_pixel(b.xmin, b.ymax)
    c1, r1 = gt.geo_to_pixel(b.xmax, b.ymin)
    c0, c1 = sorted((c0, c1))
    r0, r1 = sorted((r0, r1))
    c0 = max(int(math.floor(c0)), 0)
    r0 = max(int(math.floor(r0)), 0)
    c1 = min(int(math.ceil(c1)), W)
    r1 = min(int(math.ceil(r1)), H)
    if c0 >= c1 or r0 >= r1:
        return None
    wgt = gt.window(c0, r0)
    mask = geom.rasterize(g, c1 - c0, r1 - r0,
                          lambda x, y: wgt.geo_to_pixel(x, y),
                          all_touched=True)
    if not mask.any():
        return None
    return mask, (c0, r0, c1, r1)


def _read_positions(n: int, stride: int) -> List[int]:
    """Positions among ``n`` selected timesteps that a strided drill
    reads: both ends of every ``stride``-long run; the rest are
    interpolated (`drill.go:119-214`)."""
    read_idx: List[int] = []
    for s in range(0, n, stride):
        e = min(s + stride, n)
        read_idx.append(s)
        if e - 1 != s:
            read_idx.append(e - 1)
    return sorted(set(read_idx))


_UNOPENABLE = (OSError, ValueError, KeyError, ET.ParseError)


class _FileDrill:
    """One file of a drill: what its name says of it, the window and
    mask it is read through, and its answer."""

    __slots__ = ("ds", "sel", "vrt_xml", "is_nc", "var", "band0", "stride",
                 "read_idx", "mask", "win", "stats")

    def __init__(self, ds: Dataset, sel: List[int], vrt_xml: Optional[str],
                 stride: int, read_idx: List[int]):
        self.ds, self.sel, self.vrt_xml = ds, sel, vrt_xml
        self.is_nc = not vrt_xml \
            and not ds.ds_name.upper().startswith("GMT:") \
            and (ds.file_path.lower().endswith((".nc", ".nc4"))
                 or ds.ds_name.upper().startswith("NETCDF:"))
        self.var = ds.ds_name.split(":")[-1].strip('"') if self.is_nc else ""
        self.band0 = 1
        if not self.is_nc and ":" in ds.ds_name \
                and ds.ds_name.rsplit(":", 1)[-1].isdigit():
            self.band0 = int(ds.ds_name.rsplit(":", 1)[-1])
        # strided band reads with interpolation (`drill.go:119-214`)
        self.stride, self.read_idx = stride, read_idx
        self.mask = self.win = None
        # (values, counts, deciles) per selected timestep; None where the
        # geometry misses the file or the file cannot be opened
        self.stats = None

    @property
    def kind(self) -> str:
        return "vrt" if self.vrt_xml else "nc" if self.is_nc else "tiff"

    def open(self):
        """(handle, H, W) of the file, or of the VRT rendered round it."""
        if self.vrt_xml:
            from ..io.vrt import VRTRaster
            h = VRTRaster(self.vrt_xml)
            return h, h.height, h.width
        if self.is_nc:
            h = NetCDF(self.ds.file_path)
            try:
                v = h.variables[self.var]
            except KeyError:
                h.close()
                raise
            return h, v.shape[-2], v.shape[-1]
        from ..io.registry import open_raster
        h = open_raster(self.ds.file_path)
        return h, h.height, h.width


class _Upload(NamedTuple):
    """What a drill ships to the device for one window and one choice
    of timesteps, whichever stack of that grid it is then read from."""
    mask: jax.Array         # (bh, bw) bool, shifted to the clamped origin
    tsel: jax.Array         # (Bp,) int32, the last index repeated
    hw: Tuple[int, int]     # the window's bucket (bh, bw)
    r0c: jax.Array          # () int32: its origin, clamped so that the
    c0c: jax.Array          # bucket fits

    @property
    def gather_bytes(self) -> int:
        """Size of the f32 (Bp, bh * bw) block `window_gather` writes."""
        return 4 * int(self.tsel.shape[0]) * self.hw[0] * self.hw[1]


# Gathered bytes one request keeps enqueued before it reads back.  The
# runtime holds an enqueued program's buffers until it has run, so three
# 1 GiB windows enqueued at once by each of four requests would not fit
# beside the resident stacks; past this much (the largest stack the
# cache keeps) a group reads back before it enqueues more, which for
# the largest windows is file by file.
_GATHER_BYTES_IN_FLIGHT = 1 << 30


class _DrillGroup:
    """The files one drill request reads, drilled together.  What
    depends only on the polygon and the grid (the window, its mask, what
    is uploaded for them) is made once per grid; every device-resident
    file's work is enqueued before any result is read back, and one
    readback brings them all.  Lives for one `_drill_into` call: nothing
    here outlasts the request that made it."""

    def __init__(self, g4326: geom.Geometry, req: GeoDrillRequest):
        self.g4326, self.req = g4326, req
        self.stride = max(req.band_strides, 1)
        self._read_idx: Dict[int, List[int]] = {}
        # (srs, geo_transform, H, W) -> `_drill_window`'s answer, None too
        self._windows: Dict[tuple, Optional[tuple]] = {}
        # (grid, timesteps) -> _Upload, or None where no bucket fits
        self._uploads: Dict[tuple, Optional[_Upload]] = {}
        # (file, what `_stats_enqueue` left on the device), in file order
        self._enqueued: List[Tuple[_FileDrill, tuple]] = []
        self._in_flight = 0     # gathered bytes of those

    # -- one file ------------------------------------------------------
    def start(self, ds: Dataset, sel: List[int],
              vrt_xml: Optional[str] = None) -> _FileDrill:
        """Begin one file (or the rendered VRT wrapping it,
        `drill.go:363-423`).  A device-resident stack has its window
        gather and reductions enqueued and is answered by `collect`;
        any other file is answered here, from host reads."""
        n = len(sel)
        if n not in self._read_idx:
            self._read_idx[n] = _read_positions(n, self.stride)
        f = _FileDrill(ds, sel, vrt_xml, self.stride, self._read_idx[n])
        # the stack first: a resident one carries the raster's size, so
        # no file is opened for it
        st = self._stack(f)
        H, W = (None, None) if st is None else st.shape[-2:]
        grid = None if st is None else self._grid(f, H, W)
        with contextlib.ExitStack() as opened:
            h = None
            if grid in self._windows:
                made = self._windows[grid]
            else:
                # opened only where a window is computed or a header
                # read: /debug drill_stages.windows counts these spans
                with obs_span("drill.prepare", kind=f.kind) as psp:
                    if st is None:
                        try:
                            h, H, W = f.open()
                        except _UNOPENABLE:
                            return f
                        opened.callback(h.close)
                        grid = self._grid(f, H, W)
                    if grid in self._windows:
                        made = self._windows[grid]
                    else:
                        made = _drill_window(ds, self.g4326, h, H, W,
                                             bool(vrt_xml))
                        if grid is not None:
                            self._windows[grid] = made
                    if made is not None:
                        c0, r0, c1, r1 = made[1]
                        psp.set(window=(r1 - r0, c1 - c0))
            if made is None:
                return f
            f.mask, f.win = made
            if st is None or not self._enqueue(f, st, grid):
                f.stats = _drill_host(f, self.req, h)
        if self._in_flight >= _GATHER_BYTES_IN_FLIGHT:
            self.collect()
        return f

    @staticmethod
    def _grid(f: _FileDrill, H: int, W: int) -> Optional[tuple]:
        """What files must have in common to share a window; None for a
        file that shares with nobody: a VRT rendering has a grid of its
        own, a swath's mask comes from its geolocation arrays."""
        ds = f.ds
        if f.vrt_xml or getattr(ds, "geo_loc", None) \
                or not ds.geo_transform:
            return None
        return (ds.srs, tuple(ds.geo_transform), H, W)

    @staticmethod
    def _device_failed(f: _FileDrill) -> None:
        # any device-path failure (upload OOM, compile) degrades that
        # file to host reads, not a failed request — but loudly, and
        # counted
        from .executor import default_executor
        log.exception("drill device path failed for %s; answering from "
                      "host reads", f.ds.file_path)
        default_executor._count("drill_device_error")

    def _stack(self, f: _FileDrill):
        """The file's device-resident stack (the whole variable in HBM,
        uploaded once per file), or None: a VRT, the cache off, not
        resident yet."""
        from . import drill_cache as DC
        if f.vrt_xml or not DC.enabled():
            return None
        # async by default: a cold request answers from host reads while
        # the stack uploads in the background
        getter = DC.default_drill_cache.get if DC.sync_mode() \
            else DC.default_drill_cache.get_async
        try:
            with obs_span("drill.device") as dsp:
                st = getter(f.ds.file_path, f.is_nc, f.var, f.band0,
                            f.ds.nodata)
                if st is None:
                    dsp.set(resident=False)
                return st
        except Exception:
            self._device_failed(f)
            return None

    def _enqueue(self, f: _FileDrill, st, grid: Optional[tuple]) -> bool:
        """Put the file's window gather and reductions on the device's
        queue: the request ships only the polygon mask + timestep
        indices (KBs, once per grid), never the (B, window) raster.
        False where host reads have to answer instead."""
        try:
            with obs_span("drill.device") as dsp:
                key = None if grid is None else (grid, tuple(f.sel))
                if key in self._uploads:
                    up = self._uploads[key]
                else:
                    up = _device_upload(st.shape, f.sel, f.read_idx, f.mask,
                                        f.win)
                    if key is not None:
                        self._uploads[key] = up
                if up is None:
                    return False
                dsp.set(bucket=up.hw, bands=len(f.read_idx),
                        bands_padded=int(up.tsel.shape[0]))
                self._enqueued.append((f, _device_enqueue(st, up, self.req)))
                self._in_flight += up.gather_bytes
                return True
        except Exception:
            self._device_failed(f)
            return False

    # -- all of them -----------------------------------------------------
    def collect(self) -> None:
        """The readback: every enqueued file's statistics come to
        the host together, then each file's answer is finished in the
        order the files were begun.  A file whose result cannot be read
        is answered from host reads; the others keep theirs."""
        if not self._enqueued:
            return
        from .executor import default_executor
        queued, self._enqueued, self._in_flight = self._enqueued, [], 0
        # the span ends where the values are host arrays, sums divided
        with obs_span("drill.device", queued=len(queued)):
            try:
                got = jax.device_get([dev for _, dev in queued])
            except Exception:
                # file by file, to find the one at fault
                got = []
                for f, dev in queued:
                    try:
                        got.append(jax.device_get(dev))
                    except Exception:
                        self._device_failed(f)
                        got.append(None)
            got = [host and _stats_finish(*host) for host in got]
        # which leg answered lands in /debug executor.dispatches, beside
        # the render legs
        for (f, _), host in zip(queued, got):
            if host is None:
                f.stats = _drill_host(f, self.req)
                continue
            default_executor._count("drill_device")
            B = len(f.read_idx)
            vals, counts, dec = host
            f.stats = _maybe_interp(vals[:B], counts[:B], dec[:B],
                                    f.read_idx, f.sel, f.stride, self.req)


def _drill_file(ds: Dataset, sel: List[int], g4326: geom.Geometry,
                req: GeoDrillRequest, vrt_xml: Optional[str] = None):
    """Masked reductions for the selected bands of one file (or of a
    rendered VRT wrapping it, `drill.go:363-423`): the group of one."""
    group = _DrillGroup(g4326, req)
    f = group.start(ds, sel, vrt_xml)
    group.collect()
    return f.stats


def _drill_host(f: _FileDrill, req: GeoDrillRequest, h=None):
    """Answer one file from host reads of its window.  ``h`` is its open
    handle where the caller has one; else the file is opened here, and
    closed."""
    from .executor import default_executor
    default_executor._count("drill_host")
    ds = f.ds
    c0, r0, c1, r1 = f.win
    with contextlib.ExitStack() as opened, \
            obs_span("drill.host_read", bands=len(f.read_idx)):
        if h is None:
            try:
                h = f.open()[0]
            except _UNOPENABLE:
                return None
            opened.callback(h.close)
        v = h.variables[f.var] if f.is_nc else None
        bands_data = []
        for k in f.read_idx:
            ti = f.sel[k]
            if f.vrt_xml:
                data = h.read(1, (c0, r0, c1 - c0, r1 - r0),
                              time_index=ti)
                nodata = h.nodata
            elif f.is_nc:
                data = h.read_slice(
                    f.var, ti if len(v.shape) > 2 else None,
                    (c0, r0, c1 - c0, r1 - r0))
                nodata = ds.nodata if ds.nodata is not None \
                    else v.nodata
            else:
                # GeoTIFF granules carry one timestamp per file; the
                # band index comes from the crawler's ds_name suffix
                data = h.read(f.band0, (c0, r0, c1 - c0, r1 - r0))
                nodata = ds.nodata if ds.nodata is not None \
                    else h.nodata
            bands_data.append((data.astype(np.float32),
                               nodata_mask(data, nodata)))

        data = np.stack([d for d, _ in bands_data])
        valid = np.stack([m for _, m in bands_data]) & (f.mask[None] > 0)
        B = data.shape[0]
        vals, counts, dec = _stats_host(data.reshape(B, -1),
                                        valid.reshape(B, -1), req)
    return _maybe_interp(vals, counts, dec, f.read_idx, f.sel, f.stride,
                         req)


def _stats_host(dataf: np.ndarray, validf: np.ndarray,
                req: GeoDrillRequest):
    """The device reductions run in NUMPY for HOST-read window data:
    a cold drill (stack not yet device-resident) must not ship the
    (B, window) block through the device link just to reduce it — the
    reference's reductions are host-side too (`drill.go:128-220`).
    Steady-state requests still reduce on device from the resident
    stack (`_device_enqueue`).  Same implementation bodies as the device
    path (`ops.drill.*_impl` parameterised on the array namespace), so
    cold and warm responses cannot drift."""
    vals, counts = D.masked_mean_impl(
        dataf, validf, req.clip_lower, req.clip_upper, req.pixel_count,
        np)
    if req.deciles:
        dec = D.deciles_impl(dataf, validf, req.deciles,
                             np).astype(np.float32)
    else:
        dec = np.zeros((dataf.shape[0], 0), np.float32)
    return vals.astype(np.float32), counts.astype(np.int32), dec


def _stats_tail(dataf, validf, req: GeoDrillRequest):
    """Masked mean + deciles over (B, N) data/valid, as host arrays —
    from device or host arrays (numpy inputs reduce in numpy, see
    `_stats_host`; device inputs are enqueued and read back at once)."""
    if isinstance(dataf, np.ndarray):
        return _stats_host(dataf, validf, req)
    return _stats_finish(*jax.device_get(_stats_enqueue(dataf, validf, req)))


def _stats_finish(kind: str, a, counts, dec):
    """`_stats_enqueue`'s answer, once it is on the host, as (values,
    counts, deciles): a leg that left sums divides them here."""
    counts = np.asarray(counts)
    if kind == "sum":
        a = np.where(counts > 0, np.asarray(a) / np.maximum(counts, 1),
                     0.0).astype(np.float32)
    return np.asarray(a), counts, np.asarray(dec)


def _stats_enqueue(dataf, validf, req: GeoDrillRequest):
    """Enqueue masked mean + deciles over device-resident (B, N)
    data/valid (jnp.asarray is a no-op for resident device buffers) and
    return without waiting: (kind, a, counts, deciles), where ``a`` holds
    means (kind "mean") or sums still to be divided by the counts
    ("sum": the Pallas leg), for `_stats_finish` after the readback.
    The mesh and wave legs block by nature and hand back host arrays."""
    no_dec = np.zeros((dataf.shape[0], 0), np.float32)
    from ..mesh.dispatch import compat_spmd
    spmd = compat_spmd()
    if spmd is not None and not req.deciles:
        # mesh path (GSKY_SPMD=1 compat routing): bands over
        # `granule`, pixels over `x` + psum (deciles need a global
        # sort — those requests stay single-device)
        v, c = spmd.masked_stats(dataf, validf, req.clip_lower,
                                 req.clip_upper, req.pixel_count)
        return "mean", np.asarray(v), np.asarray(c), no_dec
    from ..ops.pallas_tpu import (masked_stats_pallas, pallas_interpret,
                                  run_with_fallback)

    # the two legs leave different things on the device, a sum and a
    # mean: each says which, so the race may still hand back either
    def _via_pallas():
        # VMEM-streamed reduction kernel on TPU backends
        s, c = masked_stats_pallas(
            jnp.asarray(dataf), jnp.asarray(validf),
            req.clip_lower, req.clip_upper,
            interpret=pallas_interpret())
        return "sum", s, c

    def _via_xla():
        v, c = D.masked_mean(
            jnp.asarray(dataf), jnp.asarray(validf),
            clip_lower=req.clip_lower, clip_upper=req.clip_upper,
            pixel_count=req.pixel_count)
        return "mean", v, c

    from .waves import default_waves, waves_enabled
    if waves_enabled():
        # wave path: concurrent drills over the same bucketed shape
        # stack into ONE (K, B, N) device reduction per scheduler tick
        # (the reduction is per-row independent, so the stacked result
        # is bit-identical to per-call); the per-call XLA leg is the
        # incident failover
        kind = "mean"
        vals, counts = default_waves().drill_stats(
            dataf, validf, float(req.clip_lower),
            float(req.clip_upper), bool(req.pixel_count),
            lambda: tuple(np.asarray(x) for x in _via_xla()[1:]))
    elif not req.pixel_count:
        # sync_token engages the fallback guard's first-call speed race
        # too: at deep-stack shapes (1000, 16k) the pallas reduction is
        # the prime suspect for the r5 on-chip warm-drill outlier, and
        # the race demotes it automatically wherever XLA measures
        # faster.  The shape is BUCKETED (`_device_upload` pads the band
        # axis to pow2 and the window to shape buckets), so the token
        # cardinality — and with it the number of races — is bounded
        # plain-int token: the durable ledger round-trips tokens through
        # repr/literal_eval, so numpy ints must not leak in
        kind, vals, counts = run_with_fallback(
            "masked_stats", _via_pallas, _via_xla,
            sync_token=tuple(int(d) for d in dataf.shape))
    else:
        kind, vals, counts = _via_xla()
    dec = D.deciles(jnp.asarray(dataf), jnp.asarray(validf), req.deciles) \
        if req.deciles else no_dec
    return kind, vals, counts, dec


def _maybe_interp(vals, counts, dec, read_idx, sel, stride,
                  req: GeoDrillRequest):
    """Strided-endpoint interpolation of statistics (`drill.go:119-214`)."""
    if stride > 1 and len(read_idx) < len(sel):
        cols = np.concatenate([vals[:, None], dec], axis=1)
        vi, ci = D.interp_strided(cols, np.tile(counts[:, None],
                                                (1, cols.shape[1])),
                                  np.asarray(read_idx), len(sel))
        vals = vi[:, 0]
        dec = vi[:, 1:]
        counts = ci[:, 0]
    return vals, counts, dec


def _device_upload(shape, sel: List[int], read_idx: List[int],
                   mask: np.ndarray, win) -> Optional[_Upload]:
    """Pad the rasterized polygon mask and the timestep indices to their
    buckets and put them on the device (KBs).  None when the window
    doesn't fit a padded bucket (the caller falls back to host reads)."""
    from .executor import _bucket, _bucket_pow2

    c0, r0, c1, r1 = win
    H, W = shape[-2:]
    wh, ww = r1 - r0, c1 - c0
    bh = min(_bucket(wh), H)
    bw = min(_bucket(ww), W)
    if bh < wh or bw < ww:
        return None
    # clamp the origin so the padded window stays in bounds; the mask
    # shifts by the clamp offset so pixels keep their identity
    r0c = min(r0, H - bh)
    c0c = min(c0, W - bw)
    mask_p = np.zeros((bh, bw), bool)
    mask_p[r0 - r0c:r0 - r0c + wh, c0 - c0c:c0 - c0c + ww] = mask > 0
    tsel = np.asarray([sel[k] for k in read_idx], np.int32)
    B = len(tsel)
    tsel_p = np.pad(tsel, (0, _bucket_pow2(B) - B), mode="edge")
    # 0-d arrays, not numpy scalars: jnp.asarray runs a program to
    # convert a scalar and only uploads an array
    return _Upload(jnp.asarray(mask_p), jnp.asarray(tsel_p), (bh, bw),
                   jnp.asarray(np.asarray(r0c, np.int32)),
                   jnp.asarray(np.asarray(c0c, np.int32)))


def _device_enqueue(st, up: _Upload, req: GeoDrillRequest):
    """Drill one file from its DEVICE-RESIDENT stack: slice the window
    on device (`ops.drill.window_gather`) and reduce it in place, both
    enqueued and neither waited for.  Returns `_stats_enqueue`'s answer
    for the padded bands, still on the device."""
    # nodata compares in the stack's NATIVE dtype (parity with
    # ops.raster.nodata_mask); a nodata not representable there matches
    # nothing, exactly like the host path's dtype-promoting !=
    dtype = st.dev.dtype
    nd = st.nodata
    if np.isnan(nd):
        use_nd = np.dtype(dtype).kind == "f"
        nd_native = np.zeros((), dtype) if not use_nd \
            else np.asarray(np.nan, dtype)
        if use_nd:
            # NaN nodata: NaN != NaN, so the ~isnan term already covers
            # it — disable the equality term
            use_nd = False
    else:
        nd_native = np.asarray(nd).astype(dtype)
        use_nd = bool(np.asarray(float(nd_native) == float(nd)))
    dataf, validf = D.window_gather(
        st.dev, up.tsel, up.r0c, up.c0c, up.mask, nd_native,
        np.bool_(use_nd), up.hw)
    return _stats_enqueue(dataf, validf, req)


def _merge(acc, req: GeoDrillRequest) -> DrillResult:
    """Weighted means per (namespace, date), then band expressions."""
    dates = sorted({d for (_, d) in acc})
    raw_ns = sorted({n for (n, _) in acc})
    series: Dict[str, List[float]] = {}
    counts: Dict[str, List[int]] = {}
    for ns in raw_ns:
        vs, cs = [], []
        for d in dates:
            items = acc.get((ns, d), [])
            tot = sum(c for _, c in items)
            if tot > 0:
                vs.append(sum(v * c for v, c in items) / tot)
            else:
                vs.append(float("nan"))
            cs.append(tot)
        series[ns] = vs
        counts[ns] = cs

    exprs = req.band_exprs
    out_values: Dict[str, List[float]] = {}
    out_counts: Dict[str, List[int]] = {}
    for ce, name in zip(exprs.expressions, exprs.expr_names):
        if ce._ast[0] == "var" and ce.variables[0] in series:
            out_values[name] = series[ce.variables[0]]
            out_counts[name] = counts[ce.variables[0]]
            continue
        vs, cs = [], []
        for di, d in enumerate(dates):
            env = {}
            ok = True
            cnt = 0
            for var in ce.variables:
                if var not in series or math.isnan(series[var][di]):
                    ok = False
                    break
                env[var] = np.float64(series[var][di])
                cnt = max(cnt, counts[var][di])
            if ok:
                try:
                    vs.append(float(ce(env, xp=np)))
                except ZeroDivisionError:
                    vs.append(float("nan"))
            else:
                vs.append(float("nan"))
            cs.append(cnt if ok else 0)
        out_values[name] = vs
        out_counts[name] = cs
    # decile columns pass through
    for ns in raw_ns:
        if "_d" in ns and ns not in out_values:
            out_values[ns] = series[ns]
            out_counts[ns] = counts[ns]
    return DrillResult(dates, out_values, out_counts, raw_ns)


def drill_csv(res: DrillResult, namespaces: Optional[List[str]] = None) -> str:
    """CSV rows 'date,v1,v2,...' — the WPS template payload format
    (`processor/drill_merger.go:161-171`)."""
    import datetime as dt
    ns = namespaces or list(res.values)
    lines = []
    for i, d in enumerate(res.dates):
        stamp = dt.datetime.fromtimestamp(d, dt.timezone.utc) \
            .strftime("%Y-%m-%d")
        row = [stamp]
        for n in ns:
            v = res.values.get(n, [float("nan")] * len(res.dates))[i]
            row.append("" if math.isnan(v) else f"{v:.4f}")
        lines.append(",".join(row))
    return "\n".join(lines)
