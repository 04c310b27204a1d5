"""Stage-pipelined WCS export engine: plan once, overlap everything.

Large GetCoverage exports used to fan out one `asyncio.to_thread` per
output tile, and each tile ran the whole chain serially — its own MAS
index query, its own granule decode, upload, warp and block encode.
Neighbouring tiles re-asked the index the same question and re-decoded
the granule windows they share, and nothing overlapped: while a tile's
block compressed on host, the device idled.

This engine restructures the export the way arXiv:2506.06235 structures
cloud->GPU EO ingestion (bounded staged pipeline, decode under compute)
and arXiv:1909.07190 structures overlapped tiling (plan footprints
jointly, fetch shared inputs once):

* **Planner** — ONE `TilePipeline.index` call over the full export bbox
  (instead of one per tile); granules are assigned to output tiles by
  footprint intersection, so the per-tile render sees exactly the
  granules the per-tile query would have returned (over-inclusion is
  harmless: a granule with no pixels in a tile contributes no valid
  taps).  Each distinct (path, band, var, time) source is decoded ONCE
  for the whole export — via the device scene cache when cacheable,
  via one memoised union window otherwise — no matter how many tiles
  it spans.

* **Three bounded stages** — a decode thread pool warms source scenes
  for tile i+1 while the warp stage (single thread: the device stream
  is one queue) renders tile i and the encode pool compresses/writes
  tile i-1.  Stages connect through bounded queues (depth
  ``GSKY_EXPORT_QUEUE_DEPTH``), so a slow writer backpressures decode
  instead of ballooning RAM.  Warp outputs are pushed device->host with
  `copy_to_host_async` (the `executor._prefetch` discipline) before
  they enter the encode queue, so the pull overlaps the next tile's
  warp.

* **Observability** — per-stage busy seconds (`plan_s`, `decode_s`,
  `warp_s`, `encode_s`), queue high-water marks, dedup counts, the
  tiles rendered from resident scenes against those that fell to the
  host-decoded window (`tiles_resident` / `tiles_fallback`, one
  `export.tile` span a tile carrying its route) and the bytes the
  encoders pulled off the device (`readback_bytes`) come back as a
  stats dict; the OWS server adds `write_s` (the assembly after the
  engine, span `export.write`), folds the dict once per answered export
  into `server.metrics.MetricsLogger`, and `/debug` serves the sums
  under ``export_pipeline``.

Only a coverage that `server/ows.py::_getcoverage` splits into more
than one tile reaches this engine (`len(local_tiles) > 1`): a
single-tile export renders on the per-tile path, so
``export_pipeline.exports`` counts engine exports only.  The benchmark
cell `landsat8-export.coverage-2k-cubic` (four 1024-px tiles an export)
runs the engine.  ``GSKY_EXPORT_PIPELINE=0`` restores the per-tile
serial path (read per request): an escape hatch, not measured on the
chip by any cell.

Knobs: ``GSKY_EXPORT_DECODE_WORKERS`` (default 4),
``GSKY_EXPORT_ENCODE_WORKERS`` (default 4),
``GSKY_EXPORT_QUEUE_DEPTH`` (default 4).
"""

from __future__ import annotations

import concurrent.futures as cf
import contextvars
import dataclasses
import logging
import os
import queue
import re
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..geo.crs import parse_crs
from ..geo.transform import BBox, transform_bbox
from ..obs import span as obs_span
from ..resilience import check_partial
from .decode import decode_window
from .executor import TAP_FORM, _prefetch
from .tile import _empty_result, evaluate_expressions, ns_prio
from .types import Granule

log = logging.getLogger("gsky.export")

_DONE = object()      # end-of-stream sentinel on the stage queues


def pipeline_enabled() -> bool:
    """GSKY_EXPORT_PIPELINE gate, read per request (default on) so a
    bench can A/B the overlap without restarting the server."""
    return os.environ.get("GSKY_EXPORT_PIPELINE", "1") != "0"


def _env_int(name: str, default: int, lo: int = 1, hi: int = 64) -> int:
    try:
        return max(lo, min(hi, int(os.environ.get(name, default))))
    except ValueError:
        return default


_NUM = re.compile(r"[-+]?[0-9]+(?:\.[0-9]*)?(?:[eE][-+]?[0-9]+)?")


def _wkt_bounds(wkt: str) -> Optional[BBox]:
    """Coordinate bounds of a WKT geometry — footprint enough for tile
    assignment without a geometry library.  None when unparseable."""
    if not wkt:
        return None
    nums = [float(m.group()) for m in _NUM.finditer(wkt)]
    if len(nums) < 4 or len(nums) % 2:
        return None
    xs, ys = nums[0::2], nums[1::2]
    return BBox(min(xs), min(ys), max(xs), max(ys))


def _scene_key(g: Granule) -> tuple:
    # the scene cache's identity (sans level): one decode per source
    return (g.path, g.band, g.var_name, g.time_index)


class ExportPipeline:
    """One WCS GetCoverage export: plan, then run the staged render.

    Output goes either to ``writer`` (a `GeoTIFFWriter`, streaming
    exports) or into the caller's ``out``/``valid`` whole-coverage
    arrays (in-RAM exports) — the same two sinks the serial per-tile
    path uses, block-for-block identical.
    """

    def __init__(self, pipe, base_req, tiles, ns_names: Sequence[str],
                 bbox: BBox, width: int, height: int,
                 nodata: float = -9999.0, writer=None,
                 out: Optional[Dict[str, np.ndarray]] = None,
                 valid: Optional[Dict[str, np.ndarray]] = None):
        self.pipe = pipe
        self.base_req = base_req
        self.tiles = list(tiles)      # [(bbox, ox, oy, tw, th), ...]
        self.ns_names = list(ns_names)
        self.bbox = bbox
        self.width = width
        self.height = height
        self.nodata = nodata
        self.writer = writer
        self.out = out
        self.valid = valid
        self.decode_workers = _env_int("GSKY_EXPORT_DECODE_WORKERS", 4)
        self.encode_workers = _env_int("GSKY_EXPORT_ENCODE_WORKERS", 4)
        self.queue_depth = _env_int("GSKY_EXPORT_QUEUE_DEPTH", 4)
        self._stop = threading.Event()
        self._errors: List[BaseException] = []
        self._err_lock = threading.Lock()
        # scene key -> DeviceScene | None, filled by the decode stage
        self._warm: Dict[tuple, object] = {}
        # scene key -> DecodedWindow | None: the ONE union-window decode
        # for sources the scene cache can't hold
        self._memo: Dict[tuple, object] = {}
        self._memo_lock = threading.Lock()
        # scene keys whose memo decode RAISED (vs. merely not
        # intersecting): feeds the partial-failure degradation policy
        self._memo_failed: set = set()
        # route -> tiles rendered that way, and tap form -> tiles whose
        # scored kernel fetched its taps that way; a batch renders
        # concurrently
        self._routes: Dict[str, int] = {}
        self._tap_forms: Dict[str, int] = {}
        self._routes_lock = threading.Lock()
        # tile index -> co-submission batch id (filled by _plan)
        self._batch_of: List[int] = list(range(len(self.tiles)))
        self.stats: Dict[str, object] = {}

    # -- control -------------------------------------------------------------

    def cancel(self) -> None:
        """Stop between tiles; in-flight stage work finishes, queued
        work is dropped.  The caller owns sink cleanup (the OWS handler
        closes + unlinks the partial stream file, as it did for the
        serial path)."""
        self._stop.set()

    def _fail(self, e: BaseException) -> None:
        with self._err_lock:
            self._errors.append(e)
        self._stop.set()

    # -- bounded-queue helpers (never deadlock a cancelled run) --------------

    def _put(self, q: queue.Queue, item) -> bool:
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _take(self, q: queue.Queue):
        while not self._stop.is_set():
            try:
                return q.get(timeout=0.05)
            except queue.Empty:
                continue
        return _DONE

    # -- planner -------------------------------------------------------------

    def _plan(self) -> List[List[Granule]]:
        """ONE index query over the full export bbox, then per-tile
        granule assignment by footprint intersection in the dst CRS."""
        full_req = dataclasses.replace(
            self.base_req, bbox=self.bbox, width=self.width,
            height=self.height)
        granules = self.pipe.index(full_req)
        dst_crs = self.base_req.crs
        bounds: List[Optional[BBox]] = []
        for g in granules:
            bb = _wkt_bounds(g.polygon)
            if bb is not None and g.srs:
                try:
                    src = parse_crs(g.srs)
                    bb = transform_bbox(bb, src, dst_crs)
                    # buffer against reprojection edge error: a granule
                    # the per-tile MAS query would return must never be
                    # dropped here (extra inclusions are free)
                    bb = bb.buffer(0.005 * max(bb.width, bb.height))
                except Exception:
                    bb = None
            else:
                bb = None      # no footprint: ride on every tile
            bounds.append(bb)
        plan = []
        for (tb, _, _, _, _) in self.tiles:
            plan.append([g for g, bb in zip(granules, bounds)
                         if bb is None or bb.intersects(tb)])
        self.stats["granules"] = len(granules)
        self.stats["granule_tile_refs"] = sum(len(gs) for gs in plan)
        self._batch_of = self._plan_batches(plan)
        return plan

    def _plan_batches(self, plan: List[List[Granule]]) -> List[int]:
        """Superblock planning over the tile assignment: consecutive
        tiles that share at least one source batch together (id per
        tile), so the warp stage can CO-SUBMIT them and the wave
        scheduler hands the dataflow autoplanner neighbouring windows
        to merge into shared-halo superblock gathers.  With the
        planner or waves off every tile is its own batch and the warp
        stage stays strictly serial — today's behaviour."""
        n = len(self.tiles)
        batch = [0] * n
        try:
            from . import autoplan
            from .waves import waves_enabled
            if not (autoplan.plan_enabled() and waves_enabled()):
                return list(range(n))
        except Exception:   # planner unavailable: serial warp
            return list(range(n))
        cap = _env_int("GSKY_EXPORT_COSUBMIT", 4, lo=1, hi=16)
        keys = [set(map(_scene_key, gs)) for gs in plan]
        bid, size = 0, 1
        for i in range(1, n):
            if size < cap and keys[i] & keys[i - 1]:
                batch[i] = bid
                size += 1
            else:
                bid += 1
                batch[i] = bid
                size = 1
        return batch

    # -- stage 1: decode / warm ----------------------------------------------

    def _warm_one(self, g: Granule) -> None:
        key = _scene_key(g)
        ex = self.pipe.executor
        s = ex.warm_scene(g, self._full_gt(), self.base_req.crs,
                          self.height, self.width)
        self._warm[key] = s
        if s is None and not g.geo_loc:
            # uncacheable: decode the ONE union window over the whole
            # export extent now, so no tile ever re-reads this source
            self._memo_window(g)

    def _full_gt(self):
        from ..geo.transform import GeoTransform
        return GeoTransform.from_bbox(self.bbox, self.width, self.height)

    def _memo_window(self, g: Granule):
        key = _scene_key(g)
        with self._memo_lock:
            if key in self._memo:
                return self._memo[key]
        failed = False
        try:
            w = decode_window(g, self.bbox, self.base_req.crs,
                              self.base_req.resample,
                              dst_hw=(self.height, self.width))
        except Exception:
            w = None
            failed = True
        with self._memo_lock:
            self._memo.setdefault(key, w)
            if failed:
                self._memo_failed.add(key)
            return self._memo[key]

    def _decode_stage(self, plan: List[List[Granule]],
                      q_warp: queue.Queue) -> None:
        """Walk tiles in output order, warming each tile's not-yet-seen
        sources through a small thread pool, and feed the warp queue.
        Runs ahead of the warp stage only as far as the bounded queue
        allows — that bound IS the pipeline's lookahead."""
        busy = 0.0
        seen: set = set()
        try:
            with cf.ThreadPoolExecutor(
                    self.decode_workers,
                    thread_name_prefix="gsky-export-decode") as pool:
                for tile, gs in zip(self.tiles, plan):
                    if self._stop.is_set():
                        return
                    t0 = time.monotonic()
                    fresh = []
                    for g in gs:
                        k = _scene_key(g)
                        if k not in seen:
                            seen.add(k)
                            fresh.append(g)
                    if fresh:
                        list(pool.map(self._warm_one, fresh))
                    # a tile with any uncacheable source falls back to
                    # the union-window path, which needs windows for ALL
                    # its granules — memoised, so shared windows still
                    # decode once across tiles
                    if any(self._warm.get(_scene_key(g)) is None
                           and not g.geo_loc for g in gs):
                        list(pool.map(self._memo_window,
                                      [g for g in gs if not g.geo_loc]))
                    busy += time.monotonic() - t0
                    self.stats["warp_queue_max"] = max(
                        self.stats.get("warp_queue_max", 0),
                        q_warp.qsize() + 1)
                    if not self._put(q_warp, (tile, gs)):
                        return
            self._put(q_warp, _DONE)
        except BaseException as e:     # noqa: BLE001 - must surface
            self._fail(e)
        finally:
            self.stats["decode_s"] = round(
                self.stats.get("decode_s", 0.0) + busy, 6)
            self.stats["scenes_warmed"] = len(seen)
            self.stats["scenes_uncacheable"] = sum(
                1 for v in self._warm.values() if v is None)
            self.stats["windows_decoded"] = len(self._memo)

    # -- stage 2: warp (runs on the caller's thread) -------------------------

    def _render_tile(self, req, gs: List[Granule]):
        """One tile under its `export.tile` span, which carries the
        route `_render_route` took: `resident` (the fused kernel over
        scenes the device holds), `fallback` (the export-wide
        host-decoded windows), `modular` or `empty`.  The first two
        reach the stats (`tiles_resident` / `tiles_fallback`).  Where
        the tile dispatched the XLA scored kernel, how that program
        fetches its taps as the executor recorded it (`executor.TAP_FORM`:
        `neighbourhood` or `per_tap`) reaches them too, and the span as
        `tap_form`."""
        with obs_span("export.tile") as sp:
            TAP_FORM.set(None)
            res, route = self._render_route(req, gs)
            form = TAP_FORM.get()
            sp.set(route=route)
            if form is not None:
                sp.set(tap_form=form)
        with self._routes_lock:
            self._routes[route] = self._routes.get(route, 0) + 1
            if form is not None:
                self._tap_forms[form] = self._tap_forms.get(form, 0) + 1
        return res

    def _render_route(self, req, gs: List[Granule]):
        """(result, route): render one tile from pre-warmed sources —
        the engine-side twin of `TilePipeline._render_fused`, with the
        decode fallback replaced by the export-wide memo windows."""
        exprs = req.band_exprs
        H, W = req.height, req.width
        if not gs:
            return _empty_result(exprs, H, W), "empty"
        if self.pipe.remote is not None or req.mask is not None:
            # modular path (mask bands / worker fan-out): the pipeline
            # still gets plan-once indexing and stage overlap; window
            # dedup is the scene cache's business on this route
            return self.pipe.render(req, gs), "modular"
        ex = self.pipe.executor
        names, ns_ids, prio = ns_prio(gs)
        route = "resident"
        sc = ex.warp_mosaic_scenes(gs, ns_ids, prio, req.dst_gt(),
                                   req.crs, H, W, len(names),
                                   req.resample)
        if sc is None:
            route = "fallback"
            ws = [self._memo_window(g) if not g.geo_loc else None
                  for g in gs]
            # this runs on the warp stage (the request's to_thread
            # context), so degradation marks reach the OWS handler
            with self._memo_lock:
                failed = sum(1 for g in gs
                             if _scene_key(g) in self._memo_failed)
            check_partial(failed, len(gs), "decode")
            live = [(g, w) for g, w in zip(gs, ws) if w is not None]
            if not live:
                return _empty_result(exprs, H, W), route
            names, ns_ids, prio = ns_prio([g for g, _ in live])
            sc = ex.warp_mosaic([w for _, w in live], ns_ids, prio,
                                req.dst_gt(), req.crs, H, W,
                                len(names), req.resample)
        canv, vals = sc
        data_env = {n: canv[i] for i, n in enumerate(names)}
        valid_env = {n: vals[i] for i, n in enumerate(names)}
        return evaluate_expressions(
            exprs, data_env, valid_env, H, W,
            granule_count=len(gs),
            file_count=len({g.path for g in gs})), route

    def _flush_batch(self, batch, q_encode, pool) -> bool:
        """Render one co-submission batch and hand the results to the
        encoders in output order.  A multi-tile batch renders its tiles
        CONCURRENTLY — each on its own context copy — so their wave
        entries land in the same scheduler tick and the autoplanner can
        superblock their shared gather windows; a single-tile batch is
        the serial path unchanged."""
        if not batch:
            return True
        reqs = [dataclasses.replace(self.base_req, bbox=tb, width=tw,
                                    height=th)
                for (tb, _ox, _oy, tw, th), _gs in batch]
        if pool is not None and len(batch) > 1:
            futs = [pool.submit(contextvars.copy_context().run,
                                self._render_tile, rq, gs)
                    for rq, (_t, gs) in zip(reqs, batch)]
            results = [f.result() for f in futs]
            self.stats["plan_batches"] = \
                self.stats.get("plan_batches", 0) + 1
            self.stats["plan_batched_tiles"] = \
                self.stats.get("plan_batched_tiles", 0) + len(batch)
        else:
            results = [self._render_tile(rq, gs)
                       for rq, (_t, gs) in zip(reqs, batch)]
        for ((_tb, ox, oy, tw, th), _gs), res in zip(batch, results):
            # start every device->host copy NOW: the encode stage's
            # np.asarray then completes an in-flight transfer while
            # this thread warps the next tile
            for n in res.namespaces:
                for env in (res.data, res.valid):
                    v = env.get(n)
                    if hasattr(v, "copy_to_host_async"):
                        _prefetch(v)
            self.stats["encode_queue_max"] = max(
                self.stats.get("encode_queue_max", 0),
                q_encode.qsize() + 1)
            if not self._put(q_encode, ((ox, oy, tw, th), res)):
                return False
        return True

    def _warp_stage(self, q_warp: queue.Queue,
                    q_encode: queue.Queue) -> None:
        busy = 0.0
        from collections import Counter
        co = max(Counter(self._batch_of).values(), default=1)
        pool = cf.ThreadPoolExecutor(
            co, thread_name_prefix="gsky-export-warp") if co > 1 \
            else None
        try:
            batch: List = []
            bid = None
            i = 0
            while True:
                item = self._take(q_warp)
                if item is _DONE:
                    break
                b = self._batch_of[i] if i < len(self._batch_of) else i
                i += 1
                t0 = time.monotonic()
                if bid is not None and b != bid:
                    ok = self._flush_batch(batch, q_encode, pool)
                    batch = []
                    if not ok:
                        return
                bid = b
                batch.append(item)
                busy += time.monotonic() - t0
            t0 = time.monotonic()
            self._flush_batch(batch, q_encode, pool)
            busy += time.monotonic() - t0
        except BaseException as e:     # noqa: BLE001
            self._fail(e)
        finally:
            if pool is not None:
                pool.shutdown(wait=True)
            self.stats["warp_s"] = round(busy, 6)

    # -- stage 3: encode / write ---------------------------------------------

    def _encode_one(self, ox: int, oy: int, tw: int, th: int, res) -> int:
        """Pull one tile's planes to the host and into the sink; returns
        the bytes pulled."""
        pulled = 0
        block = None
        if self.writer is not None:
            block = np.full((len(self.ns_names), th, tw), self.nodata,
                            np.float32)
        for i, n in enumerate(self.ns_names):
            if n not in res.data:
                continue
            d = np.asarray(res.data[n])
            v = np.asarray(res.valid[n])
            pulled += d.nbytes + v.nbytes
            if block is not None:
                block[i] = np.where(v, d, self.nodata)
            else:
                self.out[n][oy:oy + th, ox:ox + tw] = d
                self.valid[n][oy:oy + th, ox:ox + tw] = v
        if block is not None:
            self.writer.write_region(ox, oy, block)
        return pulled

    def _encode_stage(self, q_encode: queue.Queue, busy: List[float]
                      ) -> None:
        try:
            while True:
                item = self._take(q_encode)
                if item is _DONE:
                    return
                (ox, oy, tw, th), res = item
                t0 = time.monotonic()
                busy[1] += self._encode_one(ox, oy, tw, th, res)
                busy[0] += time.monotonic() - t0
        except BaseException as e:     # noqa: BLE001
            self._fail(e)

    # -- driver --------------------------------------------------------------

    def run(self) -> Dict:
        """Execute the export; returns the stats dict.  Raises the first
        stage error (the OWS handler's existing cleanup path then closes
        and unlinks any partial stream file)."""
        t0 = time.monotonic()
        self.stats = {"tiles": len(self.tiles), "index_queries": 1,
                      "decode_workers": self.decode_workers,
                      "encode_workers": self.encode_workers,
                      "queue_depth": self.queue_depth}
        # request-scoped cancellation: a client disconnect (or deadline
        # expiry) fires the token, which trips the engine's existing
        # stop flag — every stage loop already checks it, so decode /
        # warp / encode threads drain within one queue hop instead of
        # finishing an export nobody will download
        from ..resilience import current_token
        tok = current_token()
        unhook = tok.on_cancel(self.cancel) if tok else None
        t_plan = time.monotonic()
        with obs_span("export.plan") as psp:
            plan = self._plan()
            psp.set(tiles=len(self.tiles),
                    granules=self.stats.get("granules", 0))
        self.stats["plan_s"] = round(time.monotonic() - t_plan, 6)
        q_warp: queue.Queue = queue.Queue(self.queue_depth)
        q_encode: queue.Queue = queue.Queue(self.queue_depth)

        def _traced(span_name, fn, *args):
            # stage threads start from an empty contextvars.Context;
            # re-bind this request's context (trace included) and wrap
            # the stage's lifetime in one span.  One Context copy per
            # thread — a Context cannot be entered concurrently.
            ctx = contextvars.copy_context()

            def tgt():
                def body():
                    with obs_span(span_name):
                        fn(*args)
                ctx.run(body)
            return tgt

        decode_t = threading.Thread(
            target=_traced("export.decode_stage",
                           self._decode_stage, plan, q_warp),
            name="gsky-export-plan", daemon=True)
        # per encoder: [busy seconds, bytes pulled off the device]
        enc_busy = [[0.0, 0] for _ in range(self.encode_workers)]
        encoders = [threading.Thread(
            target=_traced("export.encode_stage",
                           self._encode_stage, q_encode, enc_busy[i]),
            name=f"gsky-export-encode-{i}", daemon=True)
            for i in range(self.encode_workers)]
        decode_t.start()
        for t in encoders:
            t.start()
        try:
            with obs_span("export.warp_stage"):
                self._warp_stage(q_warp, q_encode)
        finally:
            # wake every stage: workers blocked on a bounded queue must
            # observe either a sentinel or the stop flag
            for _ in encoders:
                self._put(q_encode, _DONE)
            decode_t.join()
            for t in encoders:
                t.join()
            if unhook is not None:
                unhook()
        with self._err_lock:
            if self._errors:
                raise self._errors[0]
        if tok is not None:
            tok.check("export")     # raises RequestCancelled when fired
        if self._stop.is_set():
            raise RuntimeError("export cancelled")
        self.stats["encode_s"] = round(sum(b[0] for b in enc_busy), 6)
        self.stats["readback_bytes"] = sum(b[1] for b in enc_busy)
        with self._routes_lock:
            routes = dict(self._routes)
            forms = dict(self._tap_forms)
        self.stats["tap_form"] = {f: forms.get(f, 0)
                                  for f in ("neighbourhood", "per_tap")}
        self.stats["tiles_resident"] = routes.get("resident", 0)
        self.stats["tiles_fallback"] = routes.get("fallback", 0)
        self.stats["wall_s"] = round(time.monotonic() - t0, 6)
        refs = self.stats.get("granule_tile_refs", 0)
        self.stats["dedup_saved"] = max(
            0, refs - int(self.stats.get("scenes_warmed", 0)))
        # wave engagement: export blocks render through the executor,
        # so under GSKY_WAVES the warp stage's tiles share wave
        # dispatches with concurrent WMS/drill traffic — surface the
        # scheduler's amortisation alongside the export's own numbers
        try:
            from .waves import wave_stats
            wst = wave_stats()
            if wst:
                self.stats["wave_dispatches"] = wst.get("dispatches", 0)
                self.stats["wave_requests"] = wst.get("requests", 0)
        except Exception:  # wave stats are advisory telemetry
            pass
        return self.stats
