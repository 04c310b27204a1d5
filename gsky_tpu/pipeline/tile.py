"""The tile pipeline: index -> decode -> batched TPU warp -> mosaic ->
band expressions.

The reference wires TileIndexer -> GeoRasterGRPC -> RasterMerger as
channel-connected goroutine stages (`processor/tile_pipeline.go:51-146`);
here the same dataflow is a function: the indexer is one MAS query +
granule expansion, the worker fan-out is one batched device dispatch, and
the merger is a vectorised mosaic + jit'd expressions.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..geo.crs import EPSG4326
from ..index.client import MASClient
from ..index.store import fmt_time
from ..obs import span as obs_span
from ..ops import mosaic as M
from ..ops.expr import BandExpressions
from ..resilience import check_partial
from .decode import decode_all
from .executor import WarpExecutor, _prefetch, default_executor
from .granule import expand_granules
from .types import GeoTileRequest, Granule, TileResult

log = logging.getLogger("gsky.tile")

_index_pool = None   # module-level fan-out pool (see _index_fanout)


def ns_prio(gs: Sequence[Granule]):
    """(ns_names, ns_ids, prio) for a granule set: namespace slots in
    first-seen order, mosaic priorities newest-first
    (`ops.mosaic.priority_order`).  Shared by the fused tile path and the
    export engine so both dispatch identically for the same granules."""
    ns_names: List[str] = []
    ns_index: Dict[str, int] = {}
    for g in gs:
        if g.namespace not in ns_index:
            ns_index[g.namespace] = len(ns_names)
            ns_names.append(g.namespace)
    ns_ids = [ns_index[g.namespace] for g in gs]
    order = M.priority_order([g.timestamp for g in gs])
    prio = [0.0] * len(gs)
    for rank, i in enumerate(order):
        prio[i] = float(len(gs) - rank)
    return ns_names, ns_ids, prio


class TilePipeline:
    def __init__(self, mas: MASClient, executor: Optional[WarpExecutor] = None,
                 decode_workers: int = 8, remote=None):
        """``remote``: an optional `worker.WorkerClient`; when set, the
        warp stage fans granules out to worker nodes over gRPC
        (`processor/tile_grpc.go`) instead of decoding+warping
        in-process."""
        self.mas = mas
        self.executor = executor or default_executor
        self.decode_workers = decode_workers
        self.remote = remote

    @staticmethod
    def _index_fanout():
        # one MODULE-level pool: the OWS server rebuilds pipelines on
        # config reload, and a per-pipeline pool would strand 8
        # non-daemon threads per discarded instance
        global _index_pool
        if _index_pool is None:
            import concurrent.futures as cf
            _index_pool = cf.ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="gsky-index")
        return _index_pool

    # -- indexing ------------------------------------------------------------

    def index(self, req: GeoTileRequest) -> List[Granule]:
        """MAS query + axis intersection (the TileIndexer stage)."""
        exprs = req.band_exprs
        namespaces = list(exprs.var_list)
        if req.mask is not None and req.mask.id \
                and not req.mask.data_source:
            if req.mask.id not in namespaces:
                namespaces.append(req.mask.id)
        kw = dict(srs=req.crs.name(), wkt=req.bbox.to_polygon_wkt(),
                  namespaces=",".join(namespaces),
                  nseg=req.polygon_segments, limit=req.query_limit)
        if req.start_time is not None:
            kw["time"] = fmt_time(req.start_time)
        if req.end_time is not None:
            kw["until"] = fmt_time(req.end_time)
        datasets = self._index_query(req, kw, req.collection)
        granules = expand_granules(datasets, req.start_time, req.end_time,
                                   req.axes)
        # separately indexed mask collection (`tile_indexer.go:265-284`),
        # subdivided under the same P2(b) policy as the data collection
        if req.mask is not None and req.mask.data_source:
            mkw = dict(kw)
            mkw["namespaces"] = req.mask.id
            mds = self._index_query(req, mkw, req.mask.data_source)
            granules += expand_granules(mds, req.start_time, req.end_time,
                                        req.axes)
        return granules

    def _index_query(self, req: GeoTileRequest, kw: Dict,
                     collection: str):
        """One MAS ?intersects, or — for coarse-resolution requests over
        a known layer extent — P2(b) spatial subdivision into concurrent
        index-tile queries (`tile_indexer.go:201-258`): the 256-px
        virtual grid over the clipped bbox splits into index tiles of
        256*index_tile_{x,y}_size pixels, each queried separately, so no
        single index query scans a continent at low zoom."""
        sub = self._index_subdivision(req)
        if sub is None:
            return self.mas.intersects(collection, **kw)
        if not sub:                # clipped bbox empty: nothing to ask
            return []

        def one(wkt4326):
            skw = dict(kw, srs="EPSG:4326", wkt=wkt4326)
            # failures propagate: a MAS outage must surface as an error
            # response, not render as an empty (or partially empty) tile
            return self.mas.intersects(collection, **skw)

        parts = list(self._index_fanout().map(one, sub))
        # a granule spanning several index tiles comes back once per
        # tile; identity-dedup keeps mosaic priorities unique
        seen = set()
        out = []
        for ds in (d for part in parts for d in part):
            k = (ds.file_path, ds.ds_name, ds.namespace)
            if k not in seen:
                seen.add(k)
                out.append(ds)
        return out

    def _index_subdivision(self, req: GeoTileRequest):
        """None = query as one; [] = empty; else sub-bbox WKTs (4326)."""
        if req.index_res_limit <= 0 or req.query_limit > 0 \
                or not req.spatial_extent:
            return None
        from ..geo.transform import BBox as _BBox
        from ..geo.transform import transform_bbox
        try:
            ll = transform_bbox(req.bbox, req.crs, EPSG4326)
        except ValueError:
            return None
        ext = req.spatial_extent
        xmin = max(ll.xmin, ext[0])
        ymin = max(ll.ymin, ext[1])
        xmax = min(ll.xmax, ext[2])
        ymax = min(ll.ymax, ext[3])
        if xmax < xmin or ymax < ymin:
            return []
        res_w = res_h = 256                  # virtual index raster
        xres = (xmax - xmin) / res_w
        yres = (ymax - ymin) / res_h
        if max(xres, yres) <= req.index_res_limit:
            return None
        mx = int(res_w * req.index_tile_x_size)
        my = int(res_h * req.index_tile_y_size)
        mx = mx if mx > 0 else res_w
        my = my if my > 0 else res_h
        if mx >= res_w and my >= res_h:
            return None
        subs = []
        for y in range(0, res_h, my):
            for x in range(0, res_w, mx):
                subs.append(_BBox(
                    xmin + x * xres, ymin + y * yres,
                    min(xmin + (x + mx) * xres, xmax),
                    min(ymin + (y + my) * yres, ymax)).to_polygon_wkt())
        return subs

    # -- full render ---------------------------------------------------------

    def _render_fused(self, req: GeoTileRequest,
                      granules: List[Granule]) -> TileResult:
        """Single-dispatch fast path (no mask band, local executor):
        decode -> fused warp+per-namespace mosaic
        (`ops.warp.warp_scenes_ctrl_scored` over padded windows) ->
        expressions.  Minimises device round trips: one upload set, one
        execution, results stay on device until encode."""
        exprs = req.band_exprs
        H, W = req.height, req.width

        # fastest path: scenes already resident in HBM — zero source upload
        ns_names, ns_ids, prio = ns_prio(granules)
        sc = self.executor.warp_mosaic_scenes(
            granules, ns_ids, prio, req.dst_gt(), req.crs, H, W,
            len(ns_names), req.resample)
        if sc is None:
            errs: List[Exception] = []
            ws = decode_all(granules, req.bbox, req.crs, req.resample,
                            self.decode_workers, dst_hw=(H, W), errors=errs)
            check_partial(len(errs), len(granules), "decode")
            live = [(g, w) for g, w in zip(granules, ws) if w is not None]
            if not live:
                return _empty_result(exprs, H, W)
            ns_names, ns_ids, prio = ns_prio([g for g, _ in live])
            sc = self.executor.warp_mosaic(
                [w for _, w in live], ns_ids, prio, req.dst_gt(), req.crs,
                H, W, len(ns_names), req.resample)
        canv, vals = sc
        data_env = {n: canv[i] for i, n in enumerate(ns_names)}
        valid_env = {n: vals[i] for i, n in enumerate(ns_names)}
        return evaluate_expressions(
            exprs, data_env, valid_env, H, W,
            granule_count=len(granules),
            file_count=len({g.path for g in granules}))

    def _timed_index(self, req: GeoTileRequest,
                     spans: Optional[Dict[str, float]] = None):
        """`index()` under a `tile.index` span, with the MAS-query
        seconds recorded into ``spans`` (the staged tile path's
        per-request "index" stage)."""
        t0 = time.perf_counter()
        try:
            with obs_span("tile.index"):
                return self.index(req)
        finally:
            if spans is not None:
                spans["index_s"] = spans.get("index_s", 0.0) \
                    + time.perf_counter() - t0

    def composite_prep(self, req: GeoTileRequest,
                       stats: Optional[Dict[str, int]] = None,
                       spans: Optional[Dict[str, float]] = None):
        """Qualification + ONE index pass for the fused composite path:
        (granules, ns_ids, prio, n_ns) or None.  Split from the dispatch
        half so the staged tile pipeline can run indexing, scene decode
        and device dispatch as separately bounded stages.

        Expression-bearing requests (non-trivial band algebra) return
        the 5-tuple `_expr_prep` form instead — granules stay at
        index 0, so stage consumers are agnostic."""
        if self.remote is not None or req.mask is not None:
            return None
        exprs = req.band_exprs
        if any(ce._ast[0] != "var" for ce in exprs.expressions):
            return self._expr_prep(req, exprs, stats, spans)
        granules = self._timed_index(req, spans)
        if not granules:
            return None
        if stats is not None:
            stats["granules"] = len(granules)
            stats["files"] = len({g.path for g in granules})
        ns_names, ns_ids, prio = ns_prio(granules)
        return granules, ns_ids, prio, len(ns_names)

    def _expr_prep(self, req: GeoTileRequest, exprs: BandExpressions,
                   stats: Optional[Dict[str, int]] = None,
                   spans: Optional[Dict[str, float]] = None):
        """Fused band-algebra qualification (GSKY_EXPR_FUSE): ONE index
        pass, variables resolved to namespaces with the same rules as
        `evaluate_expressions` (exact match, else unique `var#axis`
        candidate), granules mapped to fingerprint SLOT ids.  Returns
        (granules, ns_ids, prio, n_slots, fp) or None — the unfused
        post-warp leg then runs, byte-identically (the GSKY_EXPR_FUSE=0
        escape hatch is this None, unconditionally)."""
        from ..ops.expr import expr_fuse_enabled, fingerprint
        if len(exprs.expressions) != 1:
            return None
        ce = exprs.expressions[0]
        if ce._ast[0] == "var" or not ce.variables:
            return None
        if not expr_fuse_enabled():
            # a render that WOULD have fused rides the post-warp leg;
            # the counter keeps the escape hatch observable
            from ..ops.paged import note_expr_fused
            note_expr_fused("unfused")
            return None
        granules = self._timed_index(req, spans)
        if not granules:
            return None
        if stats is not None:
            stats["granules"] = len(granules)
            stats["files"] = len({g.path for g in granules})
        fp = fingerprint(ce)
        names = {g.namespace for g in granules}
        slot_of: Dict[str, int] = {}
        for i, var in enumerate(fp.slots):
            if var in names:
                slot_of[var] = i
                continue
            cands = [k for k in names if k.split("#")[0] == var]
            if len(cands) == 1:
                slot_of[cands[0]] = i
            # unresolved slot: no granules ever map to it, so it stays
            # all-invalid — exactly the unfused leg's missing-band
            # zeros/invalid output after scale-to-byte
        # granules of unreferenced namespaces are dropped: the output
        # is independent of them, and subset re-ranking preserves each
        # kept namespace's relative priority order (same mosaic winners)
        kept = [g for g in granules if g.namespace in slot_of]
        if not kept:
            return None
        ns_ids = [slot_of[g.namespace] for g in kept]
        order = M.priority_order([g.timestamp for g in kept])
        prio = [0.0] * len(kept)
        for rank, i in enumerate(order):
            prio[i] = float(len(kept) - rank)
        return kept, ns_ids, prio, len(fp.slots), fp

    def animation_prep(self, req: GeoTileRequest,
                       times: Sequence[float],
                       stats: Optional[Dict[str, int]] = None,
                       spans: Optional[Dict[str, float]] = None):
        """ONE index pass for a TIME-range animation: the whole
        sequence is resolved with a single MAS query over
        [min(times), max(times)] and partitioned per frame with the
        same point semantics as a single-timestep request
        (`granule._select_time_indices`: |timestamp - t| < 1s, untimed
        granules in every frame), so frame k's granule set — and hence
        its rendered bytes — matches what a lone GetMap at times[k]
        would have produced.  A frame with no exact match takes the
        nearest available timestep (WMS-T nearest-value semantics).

        Returns a list aligned with ``times`` of `composite_prep`-form
        tuples (granules, ns_ids, prio, n_ns), or None when the
        request doesn't qualify for the fused composite path (mask
        band, remote workers, non-trivial band algebra) — callers then
        render each frame independently."""
        if self.remote is not None or req.mask is not None:
            return None
        exprs = req.band_exprs
        if any(ce._ast[0] != "var" for ce in exprs.expressions):
            return None
        span_req = dataclasses.replace(
            req, start_time=min(times), end_time=max(times) + 1.0)
        granules = self._timed_index(span_req, spans)
        if not granules:
            return None
        if stats is not None:
            stats["granules"] = len(granules)
            stats["files"] = len({g.path for g in granules})
        untimed = [g for g in granules if g.timestamp == 0.0]
        timed = [g for g in granules if g.timestamp != 0.0]
        frames = []
        for t in times:
            fg = [g for g in timed if abs(g.timestamp - t) < 1.0]
            if not fg and timed:
                # nearest-available fallback: consecutive frames
                # between source timesteps resolve to the SAME granule
                # set, which is what lets the autoplanner merge their
                # superblocks and gather shared pages once per sequence
                best = min(abs(g.timestamp - t) for g in timed)
                fg = [g for g in timed if abs(g.timestamp - t) == best]
            fg = fg + untimed
            if not fg:
                frames.append(None)
                continue
            ns_names, ns_ids, prio = ns_prio(fg)
            frames.append((fg, ns_ids, prio, len(ns_names)))
        return frames

    def composite_dispatch(self, req: GeoTileRequest, made,
                           offset: float = 0.0, scale: float = 0.0,
                           clip: float = 0.0, colour_scale: int = 0,
                           auto: bool = True):
        if len(made) == 5:      # `_expr_prep` form: fused band algebra
            granules, ns_ids, prio, n_slots, fp = made
            out = self.executor.render_expr_byte(
                granules, ns_ids, prio, req.dst_gt(), req.crs,
                req.height, req.width, n_slots, fp, req.resample,
                offset, scale, clip, colour_scale, auto)
            if out is None:
                from ..ops.paged import note_expr_fused
                note_expr_fused("unfused")
            return out
        granules, ns_ids, prio, n_ns = made
        return self.executor.render_byte_scenes(
            granules, ns_ids, prio, req.dst_gt(), req.crs,
            req.height, req.width, n_ns, req.resample,
            offset, scale, clip, colour_scale, auto)

    def render_composite_byte(self, req: GeoTileRequest,
                              offset: float = 0.0, scale: float = 0.0,
                              clip: float = 0.0, colour_scale: int = 0,
                              auto: bool = True,
                              stats: Optional[Dict[str, int]] = None):
        """One-dispatch GetMap: index -> fused scene warp + mosaic +
        first-valid composite + byte scaling on device; returns the
        PNG-ready uint8 (H, W) jax array (255 = nodata), or None when
        the request doesn't qualify for the fused path (mask band,
        remote workers, non-trivial band expressions, uncacheable
        scenes) — callers then use `process()` + `ops.scale`.
        """
        made = self.composite_prep(req, stats)
        if made is None:
            return None
        return self.composite_dispatch(req, made, offset, scale, clip,
                                       colour_scale, auto)

    def _bands_prep(self, req: GeoTileRequest, n_bands: int = 0,
                    stats: Optional[Dict[str, int]] = None,
                    spans: Optional[Dict[str, float]] = None):
        """Shared index + namespace/selection resolution for the fused
        multi-band paths: (granules, ns_index, out_sel) or None.  ONE
        index pass feeds both rungs of the RGB ladder."""
        if self.remote is not None or req.mask is not None:
            return None
        exprs = req.band_exprs
        if not exprs.expressions or \
                (n_bands and len(exprs.expressions) != n_bands) or \
                any(ce._ast[0] != "var" for ce in exprs.expressions):
            return None
        granules = self._timed_index(req, spans)
        if not granules:
            return None
        if stats is not None:
            stats["granules"] = len(granules)
            stats["files"] = len({g.path for g in granules})
        ns_index: Dict[str, int] = {}
        for g in granules:
            if g.namespace not in ns_index:
                ns_index[g.namespace] = len(ns_index)
        out_sel = []
        for ce in exprs.expressions:
            var = ce.variables[0]
            if var in ns_index:
                out_sel.append(ns_index[var])
                continue
            cands = [k for k in ns_index if k.split("#")[0] == var]
            if len(cands) != 1:
                return None
            out_sel.append(ns_index[cands[0]])
        return granules, ns_index, out_sel

    @staticmethod
    def _ns_prios(granules, ns_index):
        """(namespace id, mosaic priority) of every granule: newest
        first, among equal timestamps the later arrival."""
        order = M.priority_order([g.timestamp for g in granules])
        prio = [0.0] * len(granules)
        for rank, i in enumerate(order):
            prio[i] = float(len(granules) - rank)
        return [ns_index[g.namespace] for g in granules], prio

    def _bands_dispatch(self, req: GeoTileRequest, granules, ns_index,
                        out_sel, offset, scale, clip, colour_scale,
                        auto):
        ns_ids, prio = self._ns_prios(granules, ns_index)
        return self.executor.render_bands_byte(
            granules, ns_ids, prio, req.dst_gt(), req.crs,
            req.height, req.width, len(ns_index), out_sel, req.resample,
            offset, scale, clip, colour_scale, auto)

    def render_bands_byte(self, req: GeoTileRequest,
                          offset: float = 0.0, scale: float = 0.0,
                          clip: float = 0.0, colour_scale: int = 0,
                          auto: bool = True,
                          stats: Optional[Dict[str, int]] = None):
        """One-dispatch multi-band GetMap (RGB styles): index -> fused
        scene warp + per-namespace mosaic + per-band byte scaling on
        device; returns uint8 (n_bands, H, W) in expression order, or
        None when the request doesn't qualify (mask band, remote
        workers, non-trivial expressions, unmatched namespaces,
        uncacheable scenes)."""
        made = self._bands_prep(req, stats=stats)
        if made is None:
            return None
        granules, ns_index, out_sel = made
        return self._bands_dispatch(req, granules, ns_index, out_sel,
                                    offset, scale, clip, colour_scale,
                                    auto)

    def _rgba_try(self, req: GeoTileRequest, granules, ns_index, out_sel,
                  offset, scale, clip, colour_scale, auto):
        """The channel-packed RGBA dispatch over an ALREADY-indexed
        granule set, or None when the set doesn't fit the true-colour
        shape (sets of three granules, one per band on one grid)."""
        if len(ns_index) != 3 or len(granules) % 3 \
                or sorted(out_sel) != [0, 1, 2]:
            return None
        ns_ids, prio = self._ns_prios(granules, ns_index)
        return self.executor.render_rgba_byte(
            granules, ns_ids, prio, out_sel, req.dst_gt(), req.crs,
            req.height, req.width, req.resample, offset, scale, clip,
            colour_scale, auto)

    def render_rgba_byte(self, req: GeoTileRequest,
                         offset: float = 0.0, scale: float = 0.0,
                         clip: float = 0.0, colour_scale: int = 0,
                         auto: bool = True,
                         stats: Optional[Dict[str, int]] = None):
        """One-dispatch RGB GetMap for the single-scene true-colour
        shape: index -> channel-packed warp + per-band scaling + alpha
        on device (`executor.render_rgba_byte`).  Returns the PNG-ready
        uint8 (H, W, 4) jax array, or None when the request doesn't
        qualify (callers then use `render_bands_byte` / `process`)."""
        made = self._bands_prep(req, n_bands=3, stats=stats)
        if made is None:
            return None
        granules, ns_index, out_sel = made
        return self._rgba_try(req, granules, ns_index, out_sel, offset,
                              scale, clip, colour_scale, auto)

    def render_rgb_auto(self, req: GeoTileRequest,
                        offset: float = 0.0, scale: float = 0.0,
                        clip: float = 0.0, colour_scale: int = 0,
                        auto: bool = True,
                        stats: Optional[Dict[str, int]] = None):
        """RGB fast-path ladder over ONE index pass: the channel-packed
        RGBA kernel when the granule set fits it, else the per-band
        planes kernel.  Returns ("rgba", dev (H,W,4)) /
        ("planes", dev (3,H,W)) / None."""
        made = self._bands_prep(req, n_bands=3, stats=stats)
        if made is None:
            return None
        granules, ns_index, out_sel = made
        out = self._rgba_try(req, granules, ns_index, out_sel, offset,
                             scale, clip, colour_scale, auto)
        if out is not None:
            return ("rgba", out)
        out = self._bands_dispatch(req, granules, ns_index, out_sel,
                                   offset, scale, clip, colour_scale,
                                   auto)
        return None if out is None else ("planes", out)

    def process(self, req: GeoTileRequest) -> TileResult:
        granules = self.index(req)
        return self.render(req, granules)

    def render(self, req: GeoTileRequest, granules: List[Granule]) -> TileResult:
        exprs = req.band_exprs
        H, W = req.height, req.width
        if not granules:
            return _empty_result(exprs, H, W)

        mask_id = req.mask.id if req.mask is not None else None
        if mask_id is None and self.remote is None:
            return self._render_fused(req, granules)
        # mask bands always resample nearest: interpolating bitfields is
        # meaningless (the reference's warp kernel is nearest-only anyway)
        is_mask = [mask_id is not None and g.base_namespace == mask_id
                   for g in granules]
        warped: List[Optional[Tuple[np.ndarray, np.ndarray]]] = \
            [None] * len(granules)
        for method, idxs in (
                (req.resample, [i for i, m in enumerate(is_mask) if not m]),
                ("near", [i for i, m in enumerate(is_mask) if m])):
            if not idxs:
                continue
            if self.remote is not None:
                wr = self.remote.warp_many([granules[i] for i in idxs],
                                           req, method)
                for k, i in enumerate(idxs):
                    warped[i] = wr[k]
                continue
            # curvilinear granules have no affine window; they warp
            # from the device scene cache via the geolocation ctrl
            # path even on this modular (mask-band) route
            reg = [i for i in idxs if not granules[i].geo_loc]
            gl = [i for i in idxs if granules[i].geo_loc]
            if reg:
                errs: List[Exception] = []
                ws = decode_all([granules[i] for i in reg], req.bbox,
                                req.crs, method, self.decode_workers,
                                dst_hw=(H, W), errors=errs)
                check_partial(len(errs), len(reg), "decode")
                wr = self.executor.warp_all(ws, req.dst_gt(), req.crs,
                                            H, W, method)
                for k, i in enumerate(reg):
                    warped[i] = wr[k]
            if gl:
                # one batched dispatch, each granule its own namespace
                # slot so per-granule rasters come back for the mask
                # machinery; on failure retry per granule so a single
                # uncacheable file degrades alone
                sc = self.executor.warp_mosaic_scenes(
                    [granules[i] for i in gl], list(range(len(gl))),
                    [1.0] * len(gl), req.dst_gt(), req.crs, H, W,
                    len(gl), method)
                if sc is not None:
                    canv, vals = sc
                    for k, i in enumerate(gl):
                        warped[i] = (canv[k], vals[k])
                else:
                    for i in gl:
                        one = self.executor.warp_mosaic_scenes(
                            [granules[i]], [0], [1.0], req.dst_gt(),
                            req.crs, H, W, 1, method)
                        if one is None:
                            log.warning(
                                "curvilinear granule %s uncacheable; "
                                "rendered empty", granules[i].path)
                            continue
                        warped[i] = (one[0][0], one[1][0])
        # group warped granules by base namespace
        by_ns: Dict[str, List[Tuple[Granule, np.ndarray, np.ndarray]]] = {}
        mask_by_stamp: Dict[float, np.ndarray] = {}
        for g, wr in zip(granules, warped):
            if wr is None:
                continue
            data, ok = wr
            if mask_id is not None and g.base_namespace == mask_id:
                import jax.numpy as jnp
                excl = M.compute_bit_mask(
                    _restore_int(data, g.array_type),
                    req.mask.value or None, req.mask.bit_tests)
                excl = jnp.where(jnp.asarray(ok), excl, False)
                if req.mask.inclusive:
                    excl = ~excl & ok
                prev = mask_by_stamp.get(g.timestamp)
                mask_by_stamp[g.timestamp] = \
                    excl if prev is None else (prev | excl)
                if mask_id not in [n for n in exprs.var_list]:
                    continue
            by_ns.setdefault(g.namespace, []).append((g, data, ok))

        # mosaic per namespace (newest wins, older fills holes)
        data_env: Dict[str, np.ndarray] = {}
        valid_env: Dict[str, np.ndarray] = {}
        for ns, items in by_ns.items():
            rasters = [d for _, d, _ in items]
            valids = []
            for g, _, ok in items:
                excl = mask_by_stamp.get(g.timestamp)
                valids.append(ok & ~excl if excl is not None else ok)
            stamps = [g.timestamp for g, _, _ in items]
            out, okm = M.mosaic_stack(rasters, valids, stamps)
            data_env[ns] = out
            valid_env[ns] = okm

        return evaluate_expressions(exprs, data_env, valid_env, H, W,
                                    granule_count=len(granules),
                                    file_count=len({g.path for g in granules}))


def evaluate_expressions(exprs: BandExpressions,
                         data_env: Dict[str, np.ndarray],
                         valid_env: Dict[str, np.ndarray],
                         H: int, W: int, granule_count: int = 0,
                         file_count: int = 0) -> TileResult:
    """Band-expression evaluation over mosaic canvases — the merger's
    final stage (`processor/tile_merger.go:523-731`).  Variables the index
    produced with axis suffixes (`var#axis=value`) are matched to the
    plain variable when unambiguous."""
    import jax.numpy as jnp

    out_data: Dict[str, np.ndarray] = {}
    out_valid: Dict[str, np.ndarray] = {}
    names: List[str] = []

    def lookup(var: str) -> Optional[str]:
        if var in data_env:
            return var
        cands = [k for k in data_env if k.split("#")[0] == var]
        return cands[0] if len(cands) == 1 else None

    for ce, name in zip(exprs.expressions, exprs.expr_names):
        env = {}
        venv = {}
        missing = False
        for var in ce.variables:
            k = lookup(var)
            if k is None:
                missing = True
                break
            env[var] = jnp.asarray(data_env[k])
            venv[var] = jnp.asarray(valid_env[k])
        if missing:
            out_data[name] = np.zeros((H, W), np.float32)
            out_valid[name] = np.zeros((H, W), bool)
        elif ce._ast[0] == "var":
            k = lookup(ce.variables[0])
            out_data[name] = data_env[k].astype(np.float32)
            out_valid[name] = valid_env[k]
        else:
            # stays on device: TileResult arrays are pulled to host only
            # at encode time (one sync per response).  Consumers
            # (encoders, WCS merge) pull next, so start the copies now —
            # transfers then overlap across concurrent requests
            o, ok = ce.eval_masked(env, venv)
            out_data[name] = _prefetch(o.astype(jnp.float32))
            out_valid[name] = _prefetch(ok)
        names.append(name)

    # axis-expanded outputs with no expression (`var#axis=value` pass
    # through as extra namespaces)
    for k in data_env:
        if "#" in k and k not in out_data:
            out_data[k] = data_env[k].astype(np.float32)
        if "#" in k and k not in out_valid:
            out_valid[k] = valid_env[k]
            names.append(k)

    return TileResult(out_data, out_valid, names, granule_count, file_count)


def _restore_int(data: np.ndarray, array_type: str) -> np.ndarray:
    """Warped mask bands come back float32; restore the integer type for
    bitwise tests."""
    from ..ops.raster import DTYPE_NP
    dt = DTYPE_NP.get(array_type, np.int32)
    if np.dtype(dt).kind not in "iu":
        dt = np.int32
    return data.astype(dt)


def _empty_result(exprs: BandExpressions, H: int, W: int) -> TileResult:
    data = {n: np.zeros((H, W), np.float32) for n in exprs.expr_names}
    valid = {n: np.zeros((H, W), bool) for n in exprs.expr_names}
    return TileResult(data, valid, list(exprs.expr_names), 0, 0)
