"""gsky-ows: the OGC front-end server (WMS / WCS / WPS / DAP4).

Route and dispatch parity with `ows.go`: ``/`` serves the static demo
client, ``/ows`` and ``/ows/<namespace>`` take OGC KVP requests
dispatched on ``service=`` (or inferred from ``request=``,
`ows.go:1500-1524`), errors come back as OGC ServiceException XML, and
every request logs a metrics JSON record.

Compute runs in the tile/drill pipelines (TPU); handlers below do
request validation, config resolution, scaling/encoding and response
framing — the same division of labour as `ows.go`'s serveWMS/serveWCS/
serveWPS.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import datetime as dt
import functools
import io
import json
import logging
import math
import os
import tempfile
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np
from aiohttp import web

import jax.numpy as jnp

from ..geo.crs import EPSG3857, EPSG4326, parse_crs
from ..geo.transform import (BBox, GeoTransform, pixel_resolution, split_bbox,
                             transform_bbox)
from ..fleet import DrainController, Draining
from ..geo import geometry as geom
from ..index.client import MASClient
from ..index.store import fmt_time, parse_time
from ..io.geotiff import GeoTIFF, write_geotiff
from ..io.netcdf import write_netcdf3
from ..io.png import (ApngAssembler, empty_tile_png, encode_async,
                      encode_jpeg, encode_png, encode_rgba_png)
from ..ops.palette import gradient_palette, with_nodata_entry
from ..ops.raster import DTYPE_NP
from ..ops.scale import scale_params_auto, scale_to_byte
from ..pipeline import (DrillPipeline, GeoDrillRequest, GeoTileRequest,
                        TilePipeline)
from ..pipeline.export import ExportPipeline
from ..pipeline.export import pipeline_enabled as export_pipeline_enabled
from ..pipeline.extent import compute_reprojection_extent
from ..pipeline.feature_info import get_feature_info
from ..pipeline.tile_stages import render_staged
from ..pipeline.types import AxisSelector, MaskSpec
from .. import device_guard, obs
from ..resilience import (BackendUnavailable, Deadline, DeadlineExceeded,
                          TooManyFailures, brownout_level, cancel_scope,
                          cancel_stats, current_token, deadline_scope,
                          degraded_reasons, mark_degraded, request_scope)
from ..resilience import pressure as _pressure
from ..resilience import registry as resilience_registry
from ..serving import (AdmissionShed, ServingGateway, canonical_key,
                       default_gateway, layer_fingerprint, make_entry,
                       quantise_bbox)
from . import dap4
from . import templates as T

log = logging.getLogger("gsky.ows")

# GetCoverage outputs beyond this many pixels stream tiles to disk via
# GeoTIFFWriter instead of accumulating whole-coverage arrays in RAM
WCS_STREAM_PIXELS = 16 << 20

# output formats served by the temporal wave path (docs/PERF.md
# "Temporal waves"); video/mp4 is an APNG-container stub for now
_ANIM_FORMATS = ("image/apng", "video/mp4")


def anim_enabled() -> bool:
    """GSKY_ANIM=0 disables temporal wave serving: a TIME-range GetMap
    with an animation format falls through to the existing single-image
    ladder (temporal mosaic over the range), byte-identically."""
    return os.environ.get("GSKY_ANIM", "1") != "0"


def _anim_delay_ms() -> int:
    """Per-frame display delay in the APNG container
    (GSKY_ANIM_DELAY_MS, default 500)."""
    try:
        return max(1, int(os.environ.get("GSKY_ANIM_DELAY_MS", "500")))
    except ValueError:
        return 500


def _anim_max_frames() -> int:
    """Sequence-length cap (GSKY_ANIM_MAX_FRAMES, default 64; <= 0 =
    uncapped).  Over-long TIME lists are truncated — and labelled
    degraded — rather than rejected."""
    try:
        return int(os.environ.get("GSKY_ANIM_MAX_FRAMES", "64"))
    except ValueError:
        return 64


def _anim_workers() -> int:
    """Concurrent frame-submission threads (GSKY_ANIM_WORKERS,
    default 8): frames must be IN FLIGHT together for the wave
    scheduler to coalesce them into one device program."""
    try:
        return max(1, int(os.environ.get("GSKY_ANIM_WORKERS", "8")))
    except ValueError:
        return 8


@functools.lru_cache(maxsize=1)
def _jax_platform() -> str:
    import jax
    return jax.default_backend()
from .config import Config, ConfigWatcher, Layer
from .metrics import MetricsLogger
from .params import (OWSError, infer_service, normalise_query, parse_wcs,
                     parse_wms, parse_wps)


def export_temp_dir(path: str) -> str:
    """The directory GetCoverage assembles its files in: ``path``, made
    if it is not there yet (`-temp_dir` named a directory nobody had
    made, and every GeoTIFF and NetCDF export answered 500: the first
    thing the export cell's rehearsal found), or the system's."""
    if not path:
        return tempfile.gettempdir()
    os.makedirs(path, exist_ok=True)
    return path


_GATEWAY_DEFAULT = object()     # sentinel: None means "no gateway"
_FABRIC_DEFAULT = object()      # sentinel: None means "no fabric"


class OWSServer:
    def __init__(self, watcher: ConfigWatcher, mas_factory=None,
                 metrics: Optional[MetricsLogger] = None,
                 static_dir: str = "", temp_dir: str = "",
                 gateway=_GATEWAY_DEFAULT, fabric=_FABRIC_DEFAULT):
        self.watcher = watcher
        self.mas_factory = mas_factory
        self.metrics = metrics or MetricsLogger()
        self.static_dir = static_dir
        self.temp_dir = export_temp_dir(temp_dir)
        self._pipelines: Dict[str, Tuple[tuple, TilePipeline]] = {}
        # serving gateway: response cache + singleflight + admission in
        # front of the pipelines; pass gateway=None for the raw server
        self.gateway: Optional[ServingGateway] = \
            default_gateway if gateway is _GATEWAY_DEFAULT else gateway
        # serialize jax profiler captures: two concurrent start_trace
        # calls collide and wedge the profiler (threading.Lock, not
        # asyncio.Lock — handlers may run on different event loops)
        self._profile_mutex = threading.Lock()
        # graceful drain (SIGTERM): the accept gate for /ows requests —
        # /debug keeps answering so operators can watch the drain land
        self.drain = DrainController("ows")
        # start-up prewarm result (main.py sets it): /debug "prewarm"
        self.prewarm: Optional[Dict] = None
        # cache fabric (docs/FABRIC.md): peer replay of encoded
        # responses across gateways.  Default: built from env when the
        # master gate + peer list are set; explicit instances let the
        # soak run several in-process gateways with distinct rings.
        if fabric is _FABRIC_DEFAULT:
            from .. import fabric as _fabric_mod
            from ..fabric.replay import default_fabric
            self.fabric = default_fabric() \
                if _fabric_mod.fabric_enabled() else None
        else:
            self.fabric = fabric
        if self.gateway is not None:
            _register_gateway_invalidation(watcher, self.gateway)

    # -- plumbing -----------------------------------------------------------

    def _mas(self, cfg: Config) -> MASClient:
        sc = cfg.service_config
        if self.mas_factory is not None:
            return self.mas_factory(sc.mas_address)
        return MASClient(sc.mas_address, timeout=sc.mas_timeout)

    def _pipeline(self, cfg: Config) -> TilePipeline:
        # one pipeline per namespace, rebuilt (and the old WorkerClient
        # closed) when a SIGHUP reload changes mas_address/worker_nodes
        # (`WatchConfig`, `config.go:1373`)
        sc = cfg.service_config
        nskey = sc.namespace or sc.mas_address
        settings = (sc.mas_address, tuple(sc.worker_nodes))
        cur = self._pipelines.get(nskey)
        if cur is not None and cur[0] == settings:
            return cur[1]
        if cur is not None and cur[1].remote is not None:
            cur[1].remote.close()
        remote = None
        if sc.worker_nodes:
            from ..worker import WorkerClient
            remote = WorkerClient(sc.worker_nodes)
            # concurrency cap from the workers' real pool sizes
            # (`getGrpcPoolSize`, `utils/config.go:1124-1187`)
            remote.autosize()
        pipe = TilePipeline(self._mas(cfg), remote=remote)
        self._pipelines[nskey] = (settings, pipe)
        return pipe

    # -- serving gateway (cache / singleflight / admission) -----------------

    def _admit(self, service_class: str, tenant: str = ""):
        if self.gateway is None:
            return contextlib.nullcontext()
        return self.gateway.admission.admit(service_class, tenant)

    def _response_key(self, cfg: Config, op: str, lay: Layer,
                      style: Layer, p, q: Dict[str, str],
                      width: int, height: int) -> Tuple[str, str]:
        """Canonical cache/flight key for a render request: built from
        the PARSED request, so equivalent KVP spellings (axis order,
        case, float formatting, parameter order) collide."""
        fp = layer_fingerprint(lay)
        extras = tuple(sorted(
            (k, v) for k, v in q.items()
            if k not in _KEY_CONSUMED and not k.startswith("dim_")))
        key = canonical_key(
            ns=cfg.service_config.namespace, op=op, layer=lay.name,
            style=style.name, crs=repr(p.crs),
            bbox=quantise_bbox(p.bbox.xmin, p.bbox.ymin, p.bbox.xmax,
                               p.bbox.ymax, width, height),
            size=(width, height), fmt=p.format.lower(),
            times=tuple(p.times),
            axes=tuple(sorted(getattr(p, "axes", {}).items())),
            extras=extras, layer_fp=fp)
        return key, fp

    def _replay(self, request: web.Request, ent,
                cache_status: str) -> web.Response:
        """Build a per-request response from cached bytes with the HTTP
        cache contract: strong ETag, If-None-Match -> 304, per-layer
        Cache-Control."""
        headers = {"X-Gsky-Cache": cache_status}
        if cache_status == "stale":
            # stale-on-error replay: past its TTL, served only because
            # the backend is down — downstream caches must not keep it
            headers["Cache-Control"] = "no-store"
            for k, v in ent.headers:
                headers[k] = v
            return web.Response(body=ent.body, status=ent.status,
                                content_type=ent.content_type,
                                headers=headers)
        if ent.status == 200:
            # Age = time already spent in our cache, so downstream
            # caches don't stretch the layer TTL to ~2x (RFC 9111 §5.1)
            age = int(max(0.0, min(
                ent.max_age - (ent.expires - time.monotonic()),
                ent.max_age)))
            headers["ETag"] = ent.etag
            headers["Cache-Control"] = f"max-age={ent.max_age}"
            headers["Age"] = str(age)
            inm = request.headers.get("If-None-Match", "")
            if inm and _etag_match(inm, ent.etag):
                return web.Response(status=304, headers=headers)
        if cache_status == "peer" and brownout_level():
            # peer-replayed under local brownout: serve the bytes but
            # keep downstream caches from retaining a degraded-mode
            # response (docs/FABRIC.md failure semantics)
            headers["Cache-Control"] = "no-store"
        for k, v in ent.headers:
            headers[k] = v
        return web.Response(body=ent.body, status=ent.status,
                            content_type=ent.content_type,
                            headers=headers)

    async def _serve_gated(self, request: web.Request, svc: str,
                           key: Optional[str], meta, collector,
                           render_inner) -> web.Response:
        """Response cache -> singleflight -> admission -> render.

        ``render_inner()`` must return a fresh coroutine per call.  A
        cache hit costs no admission slot; on a miss exactly one caller
        per key renders (under the service class's admission semaphore)
        and everyone shares the bytes — or the error.  Unshareable
        results (streaming FileResponse) pass through for the leader;
        joiners fall back to their own render."""
        gw = self.gateway
        tenant = _tenant_of(request)
        if gw is None or key is None:
            async with self._admit(svc, tenant):
                return await render_inner()
        with obs.span("gateway.lookup") as lsp:
            ent = gw.cache.get(key)
            lsp.set(hit=ent is not None)
        if ent is not None:
            collector.info["response_cache"] = "hit"
            return self._replay(request, ent, "hit")
        if self.fabric is not None:
            # fabric peer replay (docs/FABRIC.md): a non-owner asks the
            # key's owner gateway for the encoded bytes before paying a
            # render.  fetch() never raises — any peer failure just
            # falls through to the local render below.
            with obs.span("gateway.fabric") as psp:
                pent = await self.fabric.fetch(key)
                psp.set(hit=pent is not None)
            if pent is not None:
                gw.cache.put(key, pent)
                collector.info["response_cache"] = "peer"
                return self._replay(request, pent, "peer")

        async def flight_fn():
            t0, pc0 = time.time(), time.perf_counter()
            async with gw.admission.admit(svc, tenant):
                obs.record_span("gateway.admission",
                                time.perf_counter() - pc0, t0=t0,
                                service=svc)
                with obs.span("render", service=svc):
                    return _freeze_response(await render_inner())

        try:
            with obs.span("gateway.singleflight") as fsp:
                frozen, joined = await gw.flight.do(key, flight_fn)
                fsp.set(joined=joined)
        except (BackendUnavailable, TooManyFailures):
            # backend-open breaker / dead dependency: a stale cached
            # tile beats an error page.  Served degraded + labelled.
            stale = gw.cache.get_stale(key)
            if stale is None:
                raise
            mark_degraded("stale-cache")
            collector.info["response_cache"] = "stale"
            return self._replay(request, stale, "stale")
        if not isinstance(frozen, tuple):     # passthrough response
            if joined:
                async with self._admit(svc, tenant):
                    return await render_inner()
            return frozen
        status, ctype, body, keep = frozen
        ns, layer_name, fp, max_age = meta
        ent = make_entry(body, ctype, status, ns, layer_name, fp,
                         max_age, keep)
        # degraded (partial) renders must not be cached: joiners would
        # replay the holes long after the fault cleared
        if status == 200 and not joined and not degraded_reasons():
            gw.cache.put(key, ent)
        tag = "join" if joined else "miss"
        collector.info["response_cache"] = tag
        return self._replay(request, ent, tag)

    def app(self) -> web.Application:
        app = web.Application(client_max_size=64 * 1024 * 1024)
        app.router.add_route("*", "/ows", self.handle)
        # profiling side-door (`net/http/pprof` on the reference's
        # servers, `ows.go:40`): rolling stage-timing summaries, cache
        # and executor state, optional jax-profiler trace capture
        app.router.add_get("/debug", self._debug)
        app.router.add_get("/debug/profile", self._debug_profile)
        # flight recorder: recent + slowest/degraded traces (JSON or
        # JSONL), one full span tree per id; Prometheus exposition
        app.router.add_get("/debug/trace", self._debug_trace)
        app.router.add_get("/debug/trace/{trace_id}",
                           self._debug_trace_one)
        app.router.add_get("/metrics", self._metrics)
        # cache-fabric peer endpoint: fully-encoded entry bytes for a
        # canonical key, served gateway-to-gateway (docs/FABRIC.md)
        app.router.add_get("/fabric/replay", self._fabric_replay)
        app.router.add_route("*", "/ows/{namespace:.*}", self.handle)
        if self.static_dir and os.path.isdir(self.static_dir):
            app.router.add_get("/", self._index)
            app.router.add_static("/", self.static_dir, show_index=False)
        return app

    async def _debug(self, request: web.Request) -> web.Response:
        doc = self.metrics.summary()
        import jax
        from .prewarm import compile_count
        doc["jax"] = {"backend": jax.default_backend(),
                      "device_kind": jax.devices()[0].device_kind,
                      "devices": len(jax.devices()),
                      "cache_dir": jax.config.jax_compilation_cache_dir,
                      # fresh backend compiles since the probe went in
                      # at prewarm (persistent-cache hits do not count)
                      "compiles": compile_count()}
        doc["prewarm"] = self.prewarm
        try:
            from ..parallel.spmd import spmd_enabled
            doc["spmd"] = spmd_enabled()
        except Exception:  # spmd module optional in this build
            pass
        try:
            from ..mesh.dispatch import mesh_stats
            from ..mesh.pools import active_mesh_pools
            doc["mesh"] = mesh_stats()
            mp = active_mesh_pools()
            if mp is not None:
                doc["mesh"]["pools"] = mp.stats()
        except Exception:  # mesh module optional in this build
            pass
        try:
            from ..pipeline.autoplan import plan_stats
            from ..ops.paged import gather_stats
            doc["plan"] = plan_stats()
            doc["plan"]["gather"] = gather_stats()
        except Exception:  # autoplanner optional in this build
            pass
        try:
            # fused band algebra (GSKY_EXPR_FUSE, docs/KERNELS.md):
            # compile-cache hit rate, distinct fused programs, and how
            # expression renders routed (percall/wave/mesh/unfused)
            from ..ops.expr import expr_cache_stats, expr_fuse_enabled
            from ..ops.paged import expr_fused_stats
            doc["expr"] = {"fuse": expr_fuse_enabled(),
                           "cache": expr_cache_stats(),
                           **expr_fused_stats()}
        except Exception:  # expr tier optional in this build
            pass
        try:
            from ..pipeline.drill_cache import default_drill_cache as dc
            from ..pipeline.executor import default_executor as ex
            from ..pipeline.scene_cache import default_scene_cache as sc
            doc["executor"] = {
                "geo_cache": len(ex._geo_cache),
                "stack_cache": len(sc._stacks),
                "stride_cache": len(ex._stride_cache),
                "dispatches": dict(ex.bucket_stats),
                # gather-window engagement (GSKY_WARP_WINDOW): groups
                # that got a footprint window vs declined
                "gather_window": {
                    "engaged": ex.win_engaged,
                    "declined": ex.win_declined},
                # ragged paged rendering (GSKY_PAGED, docs/KERNELS.md):
                # dispatches served from the page pool vs declined back
                # to buckets, and the pool's residency stats
                "paged": {
                    "engaged": ex.paged_engaged,
                    "declined": ex.paged_declined}}
            try:
                from ..pipeline import pages
                if pages._default is not None:
                    doc["executor"]["paged"]["pool"] = \
                        pages._default.stats()
            except Exception:  # no page pool allocated yet
                pass
            # band sets the channel-packed kernels formed, by the pixel
            # grids a set spans (docs/OBSERVABILITY.md)
            with ex._lock:
                doc["band_grids"] = dict(ex.band_grids)
                # ... and by the CRSs a tile's sets lie in
                doc["band_crs"] = dict(ex.band_crs)
            doc["scene_cache_bytes"] = sc._bytes
            doc["drill_cache_bytes"] = dc._bytes
        except Exception:  # executor tier unbooted - /debug still serves
            pass
        try:
            from ..ingest import stats as ingest_stats
            from ..ingest import ingest_enabled
            from ..ingest.prefetch import _default as _planner
            from ..ingest.staging import _default as _staging
            from ..pipeline.scene_cache import default_scene_cache as _sc
            doc["ingest"] = {
                "enabled": ingest_enabled(),
                **ingest_stats.snapshot(),
                "window_routed": _sc.window_routed,
                "staged_loads": _sc.staged_loads,
            }
            if _planner is not None:
                doc["ingest"]["prefetch_planner"] = _planner.stats()
            if _staging is not None:
                doc["ingest"]["staging"] = _staging.stats()
        except Exception:  # ingest disabled - skip its block
            pass
        if self.gateway is not None:
            doc["serving"] = self.gateway.stats()
        try:
            from .. import fabric as _fabric_mod
            if self.fabric is not None or _fabric_mod.fabric_enabled():
                doc["fabric"] = _fabric_mod.fabric_stats(self.fabric)
        except Exception:  # fabric optional in this build
            pass
        try:
            from ..fleet import elastic as _elastic
            if not _elastic.dormant():
                doc["elastic"] = _elastic.elastic_stats()
        except Exception:  # elastic optional in this build
            pass
        try:
            # temporal wave serving (docs/PERF.md "Temporal waves"):
            # animation sequences, frames-per-wave amortisation, and
            # streamed-DAP4 byte/peak-buffer counters
            from ..obs.metrics import temporal_stats
            doc["temporal"] = temporal_stats()
        except Exception:  # temporal tier optional in this build
            pass
        doc["drain"] = self.drain.stats()
        doc["cancel"] = cancel_stats()
        doc["pressure"] = _pressure.default_monitor().stats()
        from ..obs.tsan import tsan_stats
        doc["tsan"] = tsan_stats()
        return web.json_response(doc)

    async def _fabric_replay(self, request: web.Request) -> web.Response:
        """Peer endpoint of the gateway replay tier (docs/FABRIC.md):
        the fully-encoded cache entry for a canonical key, or 404.
        Serves only FRESH 200 entries — stale and degraded bytes never
        cross the fabric; under brownout it sheds (peers render
        locally, this node keeps its cycles for its own clients)."""
        from .. import fabric as _fabric_mod
        from ..fabric import replay as _freplay
        key = request.query.get("key", "")
        gw = self.gateway
        if gw is None or not key or not _fabric_mod.replay_enabled():
            raise web.HTTPNotFound(text="fabric replay unavailable")
        if brownout_level():
            raise web.HTTPNotFound(
                text="brownout", headers={"X-Gsky-Fabric-NoStore": "1"})
        ent = gw.cache.peek(key)
        if ent is None or ent.status != 200:
            raise web.HTTPNotFound(text="miss")
        headers, body = _freplay.encode_entry(ent)
        return web.Response(body=body, content_type=ent.content_type,
                            headers=headers)

    async def _metrics(self, request: web.Request) -> web.Response:
        text = await asyncio.to_thread(obs.render_metrics)
        return web.Response(
            text=text,
            content_type="text/plain",
            charset="utf-8",
            headers={"X-Prometheus-Exposition": "0.0.4"})

    async def _debug_trace(self, request: web.Request) -> web.Response:
        rec = obs.default_recorder()
        if request.query.get("format") == "jsonl":
            return web.Response(text=rec.dump_jsonl(),
                                content_type="application/x-ndjson")
        if request.query.get("slowest"):
            slow = rec.slowest()
            if slow is None:
                raise web.HTTPNotFound(text="no traces recorded")
            return web.json_response(slow)
        return web.json_response({"stats": rec.stats(),
                                  "traces": rec.summary()})

    async def _debug_trace_one(self, request: web.Request) -> web.Response:
        tid = request.match_info["trace_id"]
        trace = obs.default_recorder().lookup(tid)
        if trace is None:
            raise web.HTTPNotFound(text=f"trace {tid!r} not retained")
        return web.json_response(trace)

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """Capture a jax profiler trace for ?seconds=N (default 3, max
        30) into the temp dir and report the path — ad-hoc device-time
        attribution on a LIVE server, the role of pprof's CPU profile
        endpoint."""
        try:
            seconds = min(max(float(
                request.query.get("seconds", "3")), 0.1), 30.0)
        except ValueError:
            seconds = 3.0
        # one capture at a time: overlapping start_trace calls collide
        # and wedge the profiler for the life of the process
        if not self._profile_mutex.acquire(blocking=False):
            return web.json_response(
                {"error": "a profile capture is already in progress"},
                status=409)
        try:
            out_dir = os.path.join(
                self.temp_dir,
                f"gsky_jax_trace_{int(time.time())}")
            try:
                import jax
                jax.profiler.start_trace(out_dir)
                try:
                    await asyncio.sleep(seconds)
                finally:
                    # client disconnect cancels the handler with a
                    # BaseException; an un-stopped trace would wedge
                    # the profiler for the life of the process
                    jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 - report, don't 500
                return web.json_response(
                    {"error": f"trace failed: {e}"}, status=503)
        finally:
            self._profile_mutex.release()
        return web.json_response({"trace_dir": out_dir,
                                  "seconds": seconds})

    async def _index(self, request):
        index = os.path.join(self.static_dir, "index.html")
        if os.path.exists(index):
            return web.FileResponse(index)
        raise web.HTTPNotFound()

    # -- graceful drain (SIGTERM) -------------------------------------------

    async def shutdown(self, timeout_s: Optional[float] = None) -> bool:
        """Drain protocol: stop accepting /ows requests (new ones get a
        fast 503 + Retry-After), let every in-flight request run to
        completion, flush the metrics sink (kernel-ledger verdicts are
        already per-record durable), then release the worker clients —
        whose own close() broadcasts nothing new will be dispatched.
        Returns False when in-flight work outlived the timeout."""
        if timeout_s is None:
            try:
                timeout_s = float(
                    os.environ.get("GSKY_DRAIN_TIMEOUT_S", "30") or 30)
            except ValueError:
                timeout_s = 30.0
        self.drain.start_drain()
        ok = await asyncio.to_thread(self.drain.wait_drained, timeout_s)
        st = self.drain.stats()
        log.info("ows drain %s: completed=%d refused=%d inflight=%d",
                 "complete" if ok else "TIMED OUT",
                 st["completed"], st["refused"], st["inflight"])
        self.metrics.flush()
        self.close()
        return ok

    def close(self) -> None:
        """Release per-namespace pipelines and their worker clients
        (idempotent — WorkerClient.close() tolerates repeats)."""
        for _, pipe in self._pipelines.values():
            if pipe.remote is not None:
                try:
                    pipe.remote.close()
                except Exception:  # client already closed during an earlier drain
                    pass

    # -- dispatch (generalHandler, `ows.go:1444-1530`) ----------------------

    async def handle(self, request: web.Request) -> web.Response:
        try:
            with self.drain.track():
                # the trace context is born here, travels the whole
                # request (ContextVar), crosses the worker RPC hop via
                # gRPC metadata, and lands in the flight recorder on
                # exit (GSKY_TRACE=0 short-circuits all of it).  The
                # cancel token is born alongside it: a client
                # disconnect cancels this task, but the render runs in
                # worker threads that cancellation cannot interrupt —
                # firing the token lets every downstream stage bail out
                # and hand back its permits, gate slots, pins and
                # encode workers instead of finishing a render nobody
                # will read.
                with obs.start_trace(
                        "ows.request",
                        path=getattr(request, "path", "")) as otrace, \
                        cancel_scope() as ctok:
                    try:
                        resp = await self._handle(request)
                    except asyncio.CancelledError:
                        ctok.cancel("client-disconnect")
                        raise
                    if otrace is not None:
                        otrace.status = resp.status
                        deg = resp.headers.get("X-GSKY-Degraded")
                        if deg:
                            otrace.degraded = deg.split(",")
                    return resp
        except Draining:
            # refused at the gate: the balancer should close this
            # connection and retry against a peer gateway
            resp = _exception_response(
                OWSError("server is draining", "ServerBusy", status=503),
                headers={"Retry-After": "5"})
            resp.headers["Connection"] = "close"
            return resp

    async def _handle(self, request: web.Request) -> web.Response:
        collector = self.metrics.collector()
        q = normalise_query(request.query)
        ns = request.match_info.get("namespace", "")
        collector.set_url(str(request.rel_url), request.path, q)
        peer = request.remote or ""
        collector.set_remote(request.headers.get(
            "X-Forwarded-For", peer).split(",")[0].strip())
        try:
            with request_scope() as rstate:
                obs.set_attr(
                    verb="DAP4.ce" if "dap4.ce" in q else
                    f"{q.get('service', '?')}.{q.get('request', '?')}",
                    ns=ns)
                cfg = self.watcher.get(ns)
                if cfg is None:
                    raise OWSError(
                        f"no configuration for namespace {ns!r}",
                        status=404)
                if "dap4.ce" in q:
                    async with self._admit("DAP4", _tenant_of(request)):
                        resp = await self.serve_dap(request, cfg, q,
                                                    collector)
                else:
                    svc = infer_service(q)
                    if svc == "WMS":
                        resp = await self.serve_wms(request, cfg, q,
                                                    collector)
                    elif svc == "WCS":
                        resp = await self.serve_wcs(request, cfg, q,
                                                    collector)
                    else:
                        resp = await self.serve_wps(request, cfg, q,
                                                    collector)
                reasons = sorted(set(rstate.reasons))
            if reasons and resp.status == 200:
                # partial result: still a 2xx, but honestly labelled so
                # clients (and the chaos soak) can tell it from a clean
                # render
                resp.headers["X-GSKY-Degraded"] = ",".join(reasons)
                resilience_registry.count_degraded()
                collector.info["degraded"] = reasons
            collector.log(resp.status)
            return resp
        except AdmissionShed as e:
            # shed, don't queue into latency collapse: fast OGC 503 +
            # Retry-After so well-behaved clients back off.  When the
            # fleet knows a shard with spare capacity, name it so a
            # multi-gateway balancer can steer the retry instead of
            # re-queueing blind.
            collector.log(503)
            headers = {"Retry-After": str(e.retry_after)}
            if getattr(e, "alt_node", None):
                headers["X-GSKY-Alt-Node"] = e.alt_node
            return _exception_response(
                OWSError(str(e), "ServerBusy", status=503),
                headers=headers)
        except OWSError as e:
            collector.log(e.status)
            return _exception_response(e)
        except BackendUnavailable as e:
            # a dependency (MAS / worker fleet / shard peer) stayed down
            # through retries and failover: clean 503 + Retry-After, not
            # a bare 500
            collector.log(503)
            return _exception_response(
                OWSError(f"backend unavailable: {e}", "ServerBusy",
                         status=503),
                headers={"Retry-After":
                         str(max(1, int(getattr(e, "retry_after", 5))))})
        except TooManyFailures as e:
            # more granules lost than the degradation budget allows: an
            # honest error beats a mostly-empty mosaic
            collector.log(503)
            return _exception_response(
                OWSError(str(e), "ServerBusy", status=503))
        except (asyncio.TimeoutError, DeadlineExceeded):
            # the stage timed out at the await, but its worker thread
            # is still rendering: fire the token so it unwinds at the
            # next stage check instead of holding gates to completion
            tok = current_token()
            if tok is not None:
                tok.cancel("deadline")
            collector.log(504)
            return _exception_response(OWSError("request timed out",
                                                status=504))
        except Exception as e:  # pragma: no cover - last resort
            collector.log(500)
            return _exception_response(OWSError(f"internal error: {e}",
                                                status=500))

    # -- WMS (`ows.go:160-566`) ---------------------------------------------

    async def serve_wms(self, request, cfg: Config, q, collector):
        p = parse_wms(q)
        req_name = p.request.lower()
        host = _host_of(request, cfg)
        ns_path = request.path
        if req_name == "getcapabilities" or not req_name:
            await self._ensure_layer_dates(cfg)
            return _xml(T.wms_capabilities(cfg, ns_path, host))
        if req_name == "describelayer":
            layers = [cfg.layer(n) for n in p.layers]
            if any(l is None for l in layers):
                raise OWSError("layer not found", "LayerNotDefined")
            return _xml(T.wms_describe_layer(layers, ns_path, host))
        if req_name == "getlegendgraphic":
            return self._legend(cfg, q)
        if req_name == "getmap":
            return await self._getmap_gated(request, cfg, p, q, collector)
        if req_name == "getfeatureinfo":
            async with self._admit("WMS", _tenant_of(request)):
                return await self._feature_info(cfg, p)
        raise OWSError(f"WMS request {p.request!r} not supported",
                       "OperationNotSupported")

    async def _ensure_layer_dates(self, cfg: Config) -> None:
        """Populate empty per-layer date lists from the live index so
        GetCapabilities advertises `<Dimension name="time">` extents
        for on-demand layers too (the eager strategies resolved at
        config load).  Advisory: a MAS outage leaves the dimension out
        rather than failing the capabilities document; resolved lists
        cache on the layer until the next config reload."""
        lays = [l for l in cfg.layers
                if not l.dates and l.data_source
                and not l.service_disabled("wms")]
        if not lays:
            return
        try:
            mas = self._mas(cfg)
        except Exception:  # no MAS configured: nothing to resolve from
            return
        from .config import get_layer_dates
        for lay in lays:
            try:
                await asyncio.to_thread(get_layer_dates, lay, mas)
                for s in lay.styles:
                    s.dates = lay.dates
                    s.effective_start_date = lay.effective_start_date
                    s.effective_end_date = lay.effective_end_date
            except Exception:  # per-layer resolution is advisory
                pass

    def _resolve_layer(self, cfg: Config, name: str, styles: List[str],
                       service: str) -> Tuple[Layer, Layer]:
        lay = cfg.layer(name)
        if lay is None:
            raise OWSError(f"layer {name!r} not found", "LayerNotDefined")
        if lay.service_disabled(service):
            raise OWSError(f"{service} disabled for layer {name!r}",
                           "OperationNotSupported")
        style = lay
        for sname in styles:
            if sname:
                s = lay.style(sname)
                if s is None:
                    raise OWSError(f"style {sname!r} not defined",
                                   "StyleNotDefined")
                style = s
                break
        if not style.rgb_products and lay.styles:
            style = lay.styles[0]
        return lay, style

    def _tile_request(self, cfg: Config, lay: Layer, style: Layer,
                      p, width: int, height: int,
                      segments: int) -> GeoTileRequest:
        times = p.times
        start = end = None
        if times:
            start = times[0]
            end = times[-1] if len(times) > 1 else None
        elif lay.effective_end_date:
            start = parse_time(lay.effective_end_date)
        if lay.accum and lay.effective_start_date and start is not None:
            end = end or start
            start = parse_time(lay.effective_start_date)
        axes = []
        for ax in lay.axes_info:
            idx_sels = getattr(p, "axis_idx", {}).get(ax.name)
            if idx_sels:
                # DAP4 index selection `[start:step:end]` (`dap.go:123-131`)
                for (s, e, st, is_range, is_all) in idx_sels:
                    if is_all:
                        axes.append(AxisSelector(name=ax.name, idx_start=0,
                                                 aggregate=0))
                    elif not is_range:
                        axes.append(AxisSelector(name=ax.name, idx_start=s,
                                                 idx_end=s, aggregate=0))
                    else:
                        axes.append(AxisSelector(
                            name=ax.name, idx_start=s or 0, idx_end=e,
                            idx_step=st or 1, aggregate=0))
                continue
            val = getattr(p, "axes", {}).get(ax.name, ax.default)
            if isinstance(val, tuple):  # WCS subset=(lo, hi)
                lo, hi = val
                axes.append(AxisSelector(name=ax.name, start=lo,
                                         end=hi if hi is not None else lo))
            elif val:
                try:
                    v = float(val)
                    axes.append(AxisSelector(name=ax.name, start=v, end=v))
                except (TypeError, ValueError):
                    pass
        mask = None
        if style.mask or lay.mask:
            m = style.mask or lay.mask
            mask = MaskSpec(id=m.id, value=m.value, bit_tests=m.bit_tests,
                            data_source=m.data_source, inclusive=m.inclusive)
        # the layer's own collection wins: styles inherit their parent's
        # data_source at load time, and overview layers carry their own
        return GeoTileRequest(
            collection=lay.data_source or style.data_source,
            bands=style.rgb_products or lay.rgb_products,
            bbox=p.bbox, crs=p.crs, width=width, height=height,
            start_time=start, end_time=end, axes=axes, mask=mask,
            resample=style.resample or lay.resample,
            polygon_segments=segments,
            spatial_extent=tuple(lay.default_geo_bbox)
            if len(lay.default_geo_bbox) >= 4 else None,
            index_tile_x_size=lay.index_tile_x_size,
            index_tile_y_size=lay.index_tile_y_size,
            index_res_limit=lay.index_res_limit,
            grpc_tile_x_size=lay.grpc_tile_x_size,
            grpc_tile_y_size=lay.grpc_tile_y_size)

    async def _getmap_gated(self, request, cfg: Config, p, q, collector):
        """GetMap through the serving gateway.  The cache key is only
        built once the request is complete enough to resolve (layer,
        bbox, crs, size); incomplete requests fall through to _getmap
        for its usual validation errors."""
        key = meta = None
        if p.layers and p.bbox is not None and p.crs is not None \
                and p.width > 0 and p.height > 0:
            # feed the admitted key to the prefetch planner: pan/zoom
            # continuations predicted from this stream warm the scene
            # cache ahead of the client's next tile (docs/INGEST.md)
            self._note_prefetch(cfg, p)
        # animation sequences are streamed and never cached: the frames
        # are large, degraded variants (brownout halving) must not be
        # replayed, and the StreamResponse can't be frozen anyway
        is_anim = anim_enabled() and len(p.times) > 1 \
            and p.format.lower() in _ANIM_FORMATS
        if self.gateway is not None and p.layers and p.bbox is not None \
                and p.crs is not None and p.width > 0 and p.height > 0 \
                and not is_anim:
            lay, style = self._resolve_layer(cfg, p.layers[0], p.styles,
                                             "wms")
            if lay.cache_max_age > 0:
                key, fp = self._response_key(cfg, "map", lay, style, p,
                                             q, p.width, p.height)
                meta = (cfg.service_config.namespace, lay.name, fp,
                        lay.cache_max_age)
        return await self._serve_gated(
            request, "WMS", key, meta, collector,
            lambda: self._getmap(cfg, p, collector, request=request))

    def _note_prefetch(self, cfg: Config, p) -> None:
        """Feed one resolvable GetMap key to the prefetch planner,
        registering the warm callback on first use.  Never raises and
        never blocks: observation is bookkeeping, warming runs on the
        planner's own worker thread."""
        try:
            from ..ingest import ingest_enabled
            if not ingest_enabled():
                return
            from ..ingest.prefetch import default_planner
            planner = default_planner()
            if planner.warm_fn is None:
                planner.warm_fn = self._prefetch_warm
            b = p.bbox
            # the whole times selection rides in the key (hashable
            # tuple): a temporal-range GetMap must warm the same
            # granule set the real request will mosaic
            t = tuple(p.times) if getattr(p, "times", None) else None
            planner.observe(
                f"{cfg.service_config.namespace}\x1f{p.layers[0]}",
                (b.xmin, b.ymin, b.xmax, b.ymax),
                p.width, p.height, p.crs.name(), t)
        except Exception:  # prefetch observation is advisory
            pass

    def _prefetch_warm(self, layer_key: str, qb, width: int, height: int,
                       crs_s: str, time_s):
        """Planner warm callback: resolve the predicted key exactly like
        a real GetMap (same layer resolution, same tile request, same
        index query), then warm the distinct scenes into the device
        cache and their touched pages into the page pool.  Returns
        approximate bytes warmed (the planner's budget currency)."""
        import numpy as np
        from ..geo.crs import parse_crs
        from ..geo.transform import BBox
        from ..pipeline.export import _scene_key
        from ..resilience import check_cancel
        ns, _, lname = layer_key.partition("\x1f")
        cfg = self.watcher.get(ns)
        if cfg is None:
            return 0
        lay, style = self._resolve_layer(cfg, lname, [], "wms")

        class _P:
            pass

        p = _P()
        p.bbox = BBox(*qb)
        p.crs = parse_crs(crs_s)
        if time_s is None:
            p.times = []
        elif isinstance(time_s, tuple):
            p.times = list(time_s)
        else:
            p.times = [time_s]
        p.axes = {}
        p.axis_idx = {}
        req = self._tile_request(cfg, lay, style, p, int(width),
                                 int(height), lay.wms_polygon_segments)
        pipe = self._pipeline(cfg)
        granules = pipe.index(req)
        dst_gt = req.dst_gt()
        warmed = 0
        seen = set()
        for g in granules:
            check_cancel("prefetch")
            k = _scene_key(g)
            if k in seen:
                continue
            seen.add(k)
            s = pipe.executor.warm_scene(g, dst_gt, req.crs,
                                         req.height, req.width)
            if s is not None:
                warmed += int(np.prod(s.bucket)) * 4
                self._prewarm_pages(s, req)
        return warmed

    @staticmethod
    def _prewarm_pages(s, req) -> None:
        """Stage the pages this request footprint will gather through
        (best-effort: pool declines are fine, the real request stages
        as usual)."""
        try:
            from ..geo.transform import transform_bbox
            from ..ops.paged import page_shape
            from ..pipeline.decode import _pixel_window
            from ..pipeline.pages import default_page_pool
            src_bbox = transform_bbox(req.bbox, req.crs, s.crs)
            win = _pixel_window(s.gt, src_bbox, s.width, s.height, 3)
            if win is None:
                return
            c0, r0, w, h = win
            pr, pc = page_shape()
            i0, i1 = r0 // pr, (r0 + h - 1) // pr
            j0, j1 = c0 // pc, (c0 + w - 1) // pc
            if (i1 - i0 + 1) * (j1 - j0 + 1) > 64:
                return      # a footprint that large isn't a tile pan
            default_page_pool().prewarm(s.dev, s.serial, i0, i1, j0, j1)
        except Exception:  # pool prewarm is advisory - a miss stages on demand
            pass

    async def _getmap(self, cfg: Config, p, collector, request=None):
        if not p.layers:
            raise OWSError("no layers requested", "LayerNotDefined")
        if p.bbox is None or p.crs is None:
            raise OWSError("bbox/crs required", "MissingParameterValue")
        lay, style = self._resolve_layer(cfg, p.layers[0], p.styles, "wms")
        if p.width <= 0 or p.height <= 0:
            raise OWSError("width/height required", "MissingParameterValue")
        if p.width > lay.wms_max_width or p.height > lay.wms_max_height:
            raise OWSError(
                f"requested size exceeds {lay.wms_max_width}x"
                f"{lay.wms_max_height}", "InvalidParameterValue")

        # zoom limit -> overview substitution or "zoom in" tile
        # (`ows.go:437-473`, `utils/wms.go:534-553`)
        source = lay
        if lay.zoom_limit > 0:
            res = pixel_resolution(p.bbox, p.crs, p.width, p.height)
            if res > lay.zoom_limit:
                use = _best_overview(lay, res)
                if use is None:
                    png = self._placeholder_tile(
                        lay.nodata_legend_path, p.width, p.height,
                        compress_level=_png_level(lay, style))
                    return _png(png)
                source = use  # render the overview collection; the style
                # keeps supplying scaling/palette below

        # brownout: under memory pressure degrade QUALITY before
        # availability — substitute a coarser overview (fewer granules
        # decoded, fewer pages staged) and let _png_level drop the
        # compression effort.  Honestly labelled via X-GSKY-Degraded so
        # clients and the overload soak can tell; degraded responses
        # are never cached, so recovery is immediate when pressure
        # clears.
        bl = brownout_level()
        if bl:
            mark_degraded("brownout")
            if source is lay and lay.overviews:
                res = pixel_resolution(p.bbox, p.crs, p.width, p.height)
                use = _best_overview(lay, res * (2.0 ** bl))
                if use is not None:
                    source = use

        # temporal wave serving (docs/PERF.md "Temporal waves"): a TIME
        # range/list with an animation output format resolves all
        # frames in ONE index pass and renders the sequence as lanes of
        # one wave — the autoplanner merges consecutive frames'
        # near-identical windows into shared superblocks, so shared
        # granule pages are gathered once per sequence, not per frame
        if len(p.times) > 1 and p.format.lower() in _ANIM_FORMATS \
                and anim_enabled() and not lay.input_layers:
            return await self._getmap_animation(request, cfg, p, lay,
                                                source, style, collector)

        req = self._tile_request(cfg, source, style, p, p.width, p.height,
                                 lay.wms_polygon_segments)
        pipe = self._pipeline(cfg)
        t0 = time.time()
        auto = scale_params_auto(style.offset_value, style.scale_value,
                                 style.clip_value)
        scaled = None
        n_exprs = len(req.band_exprs.expr_names)
        # per-request span record of the staged tile path; stays None
        # on renders that fell back to the modular pipeline
        spans = None
        # one deadline budget for the whole render: every stage's
        # wait_for AND every downstream timeout (MAS HTTP, worker gRPC)
        # draws from what is LEFT of wms_timeout, not a fresh allowance
        with deadline_scope(Deadline(lay.wms_timeout)) as dl:
            if not lay.input_layers and 1 <= n_exprs <= 4:
                # staged fast path: one fused dispatch per tile (the
                # modular path below costs several device round trips
                # per request), decomposed into bounded plan/index/
                # decode/dispatch/readback stages so concurrent
                # requests overlap (tile N+1's output is in flight
                # while tile N encodes)
                stats: Dict[str, int] = {}
                made_spans: Dict = {}
                made = await asyncio.wait_for(
                    asyncio.to_thread(render_staged, pipe, req, n_exprs,
                                      style.offset_value,
                                      style.scale_value,
                                      style.clip_value,
                                      style.colour_scale, auto, stats,
                                      made_spans),
                    timeout=dl.remaining())
                if made is not None:
                    spans = made_spans
                    kind, arr = made
                    if n_exprs == 3:
                        self.metrics.record_rgb_route(kind)
                    rgba = None
                    if kind == "rgba":
                        rgba = arr              # (H, W, 4)
                        scaled = [arr[..., 0], arr[..., 1], arr[..., 2]]
                    elif kind == "planes":      # (n, H, W)
                        scaled = list(arr)
                    else:                       # "composite": (H, W)
                        scaled = [arr] if arr.ndim == 2 else list(arr)
                    collector.info["device"]["duration"] = int(
                        (spans.get("dispatch_s", 0.0)
                         + spans.get("readback_s", 0.0)) * 1e9)
                    collector.info["device"]["platform"] = _jax_platform()
                    collector.info["indexer"]["num_granules"] = \
                        stats.get("granules", 0)
                    collector.info["indexer"]["num_files"] = \
                        stats.get("files", 0)
                    spans["granules"] = stats.get("granules", 0)
                    if rgba is not None and \
                            p.format.lower() not in ("image/jpeg",
                                                     "image/jpg"):
                        collector.info["rpc"]["duration"] = \
                            int((time.time() - t0) * 1e9)
                        return _png(await self._encode_tile(
                            encode_rgba_png, rgba,
                            compress_level=_png_level(lay, style),
                            spans=spans))
            if scaled is None:
                res = await asyncio.wait_for(
                    asyncio.to_thread(_render_with_fusion, pipe, req, lay,
                                      cfg, self),
                    timeout=dl.remaining())
                collector.info["indexer"]["num_granules"] = \
                    res.granule_count
                collector.info["indexer"]["num_files"] = res.file_count
                if n_exprs == 3:
                    self.metrics.record_rgb_route(
                        "fallback" if res.granule_count else "empty")

                bands = [res.data[n] for n in res.namespaces
                         if n in res.data]
                valids = [res.valid[n] for n in res.namespaces
                          if n in res.valid]
                if not bands:
                    return _png(empty_tile_png(
                        p.width, p.height,
                        compress_level=_png_level(lay, style)))
                scaled = []
                for b, v in zip(bands[:4], valids[:4]):
                    sb = scale_to_byte(jnp.asarray(b), jnp.asarray(v),
                                       offset=style.offset_value,
                                       scale=style.scale_value,
                                       clip=style.clip_value,
                                       colour_scale=style.colour_scale,
                                       auto=auto)
                    scaled.append(device_guard.guarded_readback(
                        "tile.readback", lambda sb=sb: np.asarray(sb)))
        collector.info["rpc"]["duration"] = int((time.time() - t0) * 1e9)
        if p.format.lower() in ("image/jpeg", "image/jpg"):
            return web.Response(
                body=await self._encode_tile(encode_jpeg, scaled[:3],
                                             spans=spans),
                content_type="image/jpeg")
        palette = None
        if len(scaled) == 1 and (style.palette or lay.palette):
            spec = style.palette or lay.palette
            palette = with_nodata_entry(
                gradient_palette(spec.colours, spec.interpolate))
        return _png(await self._encode_tile(
            encode_png, scaled, palette,
            compress_level=_png_level(lay, style), spans=spans))

    async def _getmap_animation(self, request, cfg: Config, p, lay,
                                source, style, collector):
        """GetMap TIME-range animation: ONE index pass
        (`TilePipeline.animation_prep`), every frame a lane of the
        same wave group, APNG container assembled on the encode pool
        and streamed.  Degrade = frame-count halving under brownout;
        the response is never cached (see `_getmap_gated`)."""
        from ..obs import metrics as _om
        from ..pipeline import waves as _waves
        times = list(p.times)
        maxf = _anim_max_frames()
        if maxf > 0 and len(times) > maxf:
            times = times[:maxf]
            mark_degraded("anim-cap")
        bl = brownout_level()
        if bl:
            # quality before availability: halve the frame count per
            # brownout level (frame 0 always survives); the degraded
            # label was already set by _getmap's brownout block
            times = times[::2] if bl == 1 else times[::4]
        req = self._tile_request(cfg, source, style, p, p.width,
                                 p.height, lay.wms_polygon_segments)
        pipe = self._pipeline(cfg)
        auto = scale_params_auto(style.offset_value, style.scale_value,
                                 style.clip_value)
        t0 = time.time()
        w0 = _waves.wave_stats().get("dispatches", 0)
        # one budget for the whole sequence, scaled by frame count:
        # every stage and every frame lane draws from what is left
        with deadline_scope(Deadline(lay.wms_timeout
                                     * max(1, len(times)))) as dl:
            stats: Dict[str, int] = {}
            made = await asyncio.wait_for(
                asyncio.to_thread(pipe.animation_prep, req, times,
                                  stats),
                timeout=dl.remaining())
            if made is not None:
                planes = await asyncio.wait_for(
                    asyncio.to_thread(self._anim_frames_wave, pipe,
                                      req, times, made, style, auto),
                    timeout=dl.remaining())
            else:
                planes = await asyncio.wait_for(
                    asyncio.to_thread(self._anim_frames_serial, pipe,
                                      req, times, lay, cfg, style,
                                      auto),
                    timeout=dl.remaining())
            collector.info["indexer"]["num_granules"] = \
                stats.get("granules", 0)
            collector.info["indexer"]["num_files"] = \
                stats.get("files", 0)
            collector.info["device"]["platform"] = _jax_platform()
            palette = None
            if all(len(pl) == 1 for pl in planes) \
                    and (style.palette or lay.palette):
                spec = style.palette or lay.palette
                palette = with_nodata_entry(
                    gradient_palette(spec.colours, spec.interpolate))
            level = _png_level(lay, style)
            pngs = await asyncio.wait_for(
                asyncio.gather(*(self._encode_tile(
                    encode_png, pl, palette, compress_level=level)
                    for pl in planes)),
                timeout=dl.remaining())
        # dispatch amortisation, telemetry only (concurrent requests
        # can inflate the delta; the bench isolates the true count)
        wave_n = max(1, _waves.wave_stats().get("dispatches", 0) - w0)
        collector.info["rpc"]["duration"] = int((time.time() - t0) * 1e9)
        headers = {"X-Gsky-Anim-Frames": str(len(pngs))}
        if p.format.lower() == "video/mp4":
            # mp4 muxing is out of scope: the stub ships the same APNG
            # bytes, honestly labelled, so clients can fall back
            headers["X-Gsky-Anim-Container"] = "apng-stub"
        asm = ApngAssembler(len(pngs), delay_ms=_anim_delay_ms())

        def _record(cancelled=False):
            try:
                _om.record_anim_sequence(
                    len(pngs), wave_n,
                    degraded=bool(degraded_reasons()),
                    cancelled=cancelled)
            except Exception:  # animation metrics are telemetry only
                pass

        if request is None:
            body = b"".join(asm.frame(b_) for b_ in pngs) \
                + asm.trailer()
            _record()
            return web.Response(body=body, content_type="image/apng",
                                headers=headers)
        resp = web.StreamResponse(status=200, headers=headers)
        resp.content_type = "image/apng"
        await resp.prepare(request)
        try:
            for b_ in pngs:
                await resp.write(asm.frame(b_))
            await resp.write(asm.trailer())
        except BaseException:
            # client gone / teardown mid-container: count the sequence
            # cancelled and unwind normally (the request scope cancels
            # the token, releasing scene pins and staging slots)
            _record(cancelled=True)
            raise
        await resp.write_eof()
        _record()
        return resp

    def _anim_frames_wave(self, pipe, req, times, made, style, auto):
        """Render the sequence's frames as concurrent lanes of one
        wave group: each frame submits `composite_dispatch` on its
        pre-resolved granule set from a small pool — inside the
        caller's cancellation/deadline context via `copy_context` — so
        the wave scheduler sees all lanes together and the autoplanner
        merges same-serial frames into shared-halo superblocks.
        Returns one [byte-plane] list per frame."""
        import concurrent.futures as cf
        import contextvars
        n = len(times)
        outs: List = [None] * n

        def one(i):
            fr = dataclasses.replace(req, start_time=times[i],
                                     end_time=None)
            dev = None
            if made[i] is not None:
                dev = pipe.composite_dispatch(
                    fr, made[i], style.offset_value, style.scale_value,
                    style.clip_value, style.colour_scale, auto)
                if dev is None:
                    # scenes not device-cacheable: this frame renders
                    # on its own serial pass (correctness over
                    # amortisation; the rest of the wave still merges)
                    dev = pipe.render_composite_byte(
                        fr, style.offset_value, style.scale_value,
                        style.clip_value, style.colour_scale, auto)
            if dev is None:
                return np.full((req.height, req.width), 255, np.uint8)
            return device_guard.guarded_readback(
                "anim.readback", lambda dev=dev: np.asarray(dev))

        with cf.ThreadPoolExecutor(
                max_workers=min(n, _anim_workers()),
                thread_name_prefix="gsky-anim") as ex:
            futs = {}
            for i in range(n):
                ctx = contextvars.copy_context()
                futs[ex.submit(ctx.run, one, i)] = i
            for f in cf.as_completed(futs):
                outs[futs[f]] = f.result()
        return [[a] for a in outs]

    def _anim_frames_serial(self, pipe, req, times, lay, cfg, style,
                            auto):
        """Per-frame fallback (mask band, fused band algebra, remote
        workers): each frame renders through the modular pipeline on
        its own index pass; the output container is still one APNG."""
        frames = []
        for t in times:
            fr = dataclasses.replace(req, start_time=t, end_time=None)
            res = _render_with_fusion(pipe, fr, lay, cfg, self)
            bands = [res.data[n] for n in res.namespaces
                     if n in res.data]
            valids = [res.valid[n] for n in res.namespaces
                      if n in res.valid]
            if not bands:
                frames.append([np.full((fr.height, fr.width), 255,
                                       np.uint8)])
                continue
            scaled = []
            for b, v in zip(bands[:4], valids[:4]):
                sb = scale_to_byte(jnp.asarray(b), jnp.asarray(v),
                                   offset=style.offset_value,
                                   scale=style.scale_value,
                                   clip=style.clip_value,
                                   colour_scale=style.colour_scale,
                                   auto=auto)
                scaled.append(device_guard.guarded_readback(
                    "anim.readback", lambda sb=sb: np.asarray(sb)))
            frames.append(scaled)
        return frames

    async def _encode_tile(self, fn, *args, spans=None, **kw):
        """PNG/JPEG encode off the event loop on io/png's sized pool.
        A staged render's completed span record rides along and is
        folded into the /debug `tile_stages` aggregates once the
        encode lands."""
        try:
            return await encode_async(fn, *args, spans=spans, **kw)
        finally:
            if spans is not None:
                # a traced request adds its stages' thread CPU and the
                # root's age: wall_s less the stages is what none covers
                trace = obs.current_trace()
                if trace is None:
                    self.metrics.record_tile(spans)
                else:
                    self.metrics.record_tile(spans, trace.cpu_by_name(),
                                             trace.age_s())

    async def _feature_info(self, cfg: Config, p):
        if not p.layers:
            raise OWSError("no layers requested", "LayerNotDefined")
        lay, style = self._resolve_layer(cfg, p.layers[0], p.styles, "wms")
        if p.bbox is None or p.x is None or p.y is None:
            raise OWSError("bbox/i/j required", "MissingParameterValue")
        req = self._tile_request(cfg, lay, style, p, p.width or 256,
                                 p.height or 256, lay.wms_polygon_segments)
        req = _with_bands(req, lay.feature_info_bands or req.bands)
        if not (0 <= p.x < req.width and 0 <= p.y < req.height):
            raise OWSError(f"i/j ({p.x},{p.y}) outside "
                           f"{req.width}x{req.height}", "InvalidPoint")
        pipe = self._pipeline(cfg)
        with deadline_scope(Deadline(lay.wms_timeout)) as dl:
            fi = await asyncio.wait_for(
                asyncio.to_thread(get_feature_info, pipe, req, p.x, p.y),
                timeout=dl.remaining())
        props = {k: (v if v is not None else "n/a")
                 for k, v in fi.values.items()}
        if lay.feature_info_max_dates != 0:
            props["available_dates"] = fi.dates[-abs(
                lay.feature_info_max_dates):]
        doc = {"type": "FeatureCollection", "features": [{
            "type": "Feature", "properties": props,
            "geometry": None}]}
        return web.json_response(doc)

    def _legend(self, cfg: Config, q):
        name = q.get("layer") or q.get("layers", "")
        lay = cfg.layer(name)
        if lay is None:
            raise OWSError(f"layer {name!r} not found", "LayerNotDefined")
        style = lay.style(q.get("style", "") or q.get("styles", "")) or lay
        path = style.legend_path or lay.legend_path
        if path and os.path.exists(path):
            with open(path, "rb") as fp:
                return _png(fp.read())
        spec = style.palette or lay.palette
        if spec is None:
            raise OWSError("no legend available", status=404)
        lut = gradient_palette(spec.colours, spec.interpolate)
        h, w = style.legend_height, style.legend_width
        img = np.zeros((h, w, 4), np.uint8)
        ramp = np.linspace(254, 0, h).astype(np.uint8)
        img[:] = lut[ramp][:, None, :]
        from ..io.png import encode_rgba_png
        return _png(encode_rgba_png(
            img, compress_level=_png_level(lay, style)))

    def _placeholder_tile(self, image_path: str, width: int,
                          height: int, compress_level=None) -> bytes:
        img_bytes = None
        if image_path and os.path.exists(image_path):
            with open(image_path, "rb") as fp:
                img_bytes = fp.read()
        return empty_tile_png(width, height, img_bytes,
                              compress_level=compress_level)

    # -- DAP4 (`dap.go:13-36`) ----------------------------------------------

    async def serve_dap(self, request, cfg: Config, q, collector):
        """``dap4.ce`` constraint expression -> WCS GetCoverage with
        dap4 output."""
        try:
            ce = dap4.parse_constraint_expr(q["dap4.ce"])
        except ValueError as e:
            raise OWSError(f"Failed to parse dap4.ce: {e}",
                           "InvalidParameterValue")
        p = dap4.dap_to_wcs(ce, cfg)
        # the request rides along so multi-tile coverages can stream
        # chunk-by-chunk off the export spool (GSKY_DAP_STREAM)
        return await self._getcoverage(cfg, p, collector,
                                       request=request)

    # -- WCS (`ows.go:568-1221`) --------------------------------------------

    async def serve_wcs(self, request, cfg: Config, q, collector):
        p = parse_wcs(q)
        req_name = p.request.lower()
        host = _host_of(request, cfg)
        if req_name == "getcapabilities" or not req_name:
            return _xml(T.wcs_capabilities(cfg, request.path, host))
        if req_name == "describecoverage":
            layers = [cfg.layer(n) for n in p.coverages] if p.coverages \
                else [l for l in cfg.layers if not l.service_disabled("wcs")]
            if any(l is None for l in layers):
                raise OWSError("coverage not found", "CoverageNotDefined")
            return _xml(T.wcs_describe_coverage(layers, host))
        if req_name == "getcoverage":
            return await self._getcoverage_gated(
                request, cfg, p, q, collector,
                is_shard=bool(q.get("wshard")))
        raise OWSError(f"WCS request {p.request!r} not supported",
                       "OperationNotSupported")

    async def _getcoverage_gated(self, request, cfg: Config, p, q,
                                 collector, is_shard: bool):
        """GetCoverage through the serving gateway.  Shard re-entries
        (wshard=1 from a peer OWS) and auto-sized requests (width or
        height 0, resolved against the live index) bypass the cache;
        huge exports exceed the per-entry byte cap at put() and simply
        aren't retained."""
        key = meta = None
        if self.gateway is not None and not is_shard and p.coverages \
                and p.bbox is not None and p.crs is not None \
                and p.width > 0 and p.height > 0:
            lay, style = self._resolve_layer(cfg, p.coverages[0],
                                             p.styles, "wcs")
            if lay.cache_max_age > 0:
                key, fp = self._response_key(cfg, "cov", lay, style, p,
                                             q, p.width, p.height)
                meta = (cfg.service_config.namespace, lay.name, fp,
                        lay.cache_max_age)
        return await self._serve_gated(
            request, "WCS", key, meta, collector,
            lambda: self._getcoverage(cfg, p, collector, q=q,
                                      path=request.path,
                                      is_shard=is_shard))

    async def _getcoverage(self, cfg: Config, p, collector, q=None,
                           path: str = "/ows", is_shard: bool = False,
                           request=None):
        if not p.coverages:
            raise OWSError("no coverage requested", "CoverageNotDefined")
        lay, style = self._resolve_layer(cfg, p.coverages[0], p.styles,
                                         "wcs")
        if p.bbox is None or p.crs is None:
            raise OWSError("bbox/crs required", "MissingParameterValue")
        width, height = p.width, p.height
        pipe = self._pipeline(cfg)
        base_req = self._tile_request(cfg, lay, style, p, 256, 256,
                                      lay.wcs_polygon_segments)
        if getattr(p, "bands_override", None):
            # DAP4 CEs name the variables to fetch (`dap.go:137-143`)
            base_req = _with_bands(base_req, p.bands_override)
        if width <= 0 or height <= 0:
            # auto size from source resolution (`ows.go:773-806`)
            width, height = await asyncio.to_thread(
                compute_reprojection_extent, pipe.mas, base_req)
            if width <= 0 or height <= 0:
                raise OWSError("no data for requested extent",
                               "CoverageNotDefined")
        if width > lay.wcs_max_width or height > lay.wcs_max_height:
            raise OWSError(
                f"requested size {width}x{height} exceeds "
                f"{lay.wcs_max_width}x{lay.wcs_max_height}",
                "InvalidParameterValue")

        fmt = p.format.lower()
        if fmt not in ("geotiff", "gtiff", "tiff", "netcdf", "nc",
                       "application/x-netcdf", "image/tiff", "dap4"):
            raise OWSError(f"format {p.format!r} not supported",
                           "InvalidFormat")

        # tiled render (`ows.go:815-833,1010-1092`)
        tiles = split_bbox(p.bbox, width, height, lay.wcs_max_tile_width,
                           lay.wcs_max_tile_height)
        # one budget for the whole export; shard fetches, their local
        # fallbacks and every downstream timeout draw from what remains
        dl = Deadline(lay.wcs_timeout * max(1, len(tiles)))
        exprs = base_req.band_exprs
        ns_names = list(exprs.expr_names)
        # very large GeoTIFF exports stream tiles straight to disk
        # (GeoTIFFWriter) instead of accumulating whole-coverage arrays
        # — the reference's incremental flush (`ows.go:695,1088-1091`)
        stream_tif = (
            fmt in ("geotiff", "gtiff", "tiff", "image/tiff")
            and width * height > WCS_STREAM_PIXELS
            and lay.wcs_max_tile_width % 256 == 0
            and lay.wcs_max_tile_height % 256 == 0)
        # streamed DAP4 (docs/PERF.md): multi-tile coverages route
        # through the staged export engine into a disk spool instead of
        # whole-coverage RAM canvases, then the response body streams
        # chunk-by-chunk with bounded peak RSS.  serve_dap only (q is
        # None: no shard re-entry, no gateway freeze of the stream);
        # GSKY_DAP_STREAM=0 keeps the in-RAM leg, byte-identically.
        stream_dap = (
            fmt == "dap4" and request is not None and q is None
            and dap4.dap_stream_enabled() and len(tiles) > 1
            and not lay.input_layers and export_pipeline_enabled())
        out = {} if stream_tif or stream_dap else \
            {n: np.zeros((height, width), np.float32) for n in ns_names}
        valid = {} if stream_tif or stream_dap else \
            {n: np.zeros((height, width), bool) for n in ns_names}

        nodata = -9999.0
        gt = GeoTransform.from_bbox(p.bbox, width, height)
        stamp = dt.datetime.now(dt.timezone.utc).strftime("%Y%m%d%H%M%S")
        writer = None
        if stream_tif:
            from ..io.geotiff import GeoTIFFWriter
            # distinct name: `path` is the request path, needed for peer
            # shard URL construction in fetch_shard
            stream_path = os.path.join(self.temp_dir,
                                       f"wcs_{stamp}_{id(p)}.tif")
            writer = GeoTIFFWriter(stream_path, len(ns_names), height,
                                   width, np.float32, gt, p.crs,
                                   nodata=nodata)
        elif stream_dap:
            # band-major float32 spool in temp_dir: tiles land via the
            # same write_region interface the GeoTIFF stream uses, and
            # the response later reads it back row-batch by row-batch
            stream_path = os.path.join(self.temp_dir,
                                       f"dap_{stamp}_{id(p)}.raw")
            writer = dap4.CoverageSpool(stream_path, len(ns_names),
                                        height, width)

        async def render_tile(tb, ox, oy, tw, th):
            req = dataclasses.replace(
                base_req, bbox=tb, width=tw, height=th,
                polygon_segments=lay.wcs_polygon_segments)
            res = await asyncio.to_thread(_render_with_fusion, pipe, req,
                                          lay, cfg, self)
            if writer is not None:
                block = np.full((len(ns_names), th, tw), nodata,
                                np.float32)
                for i, n in enumerate(ns_names):
                    if n in res.data:
                        # float export pull, under the device guard
                        # (hang watchdog + output-integrity probe)
                        d = device_guard.guarded_readback(
                            "export.readback", lambda n=n:
                            np.asarray(res.data[n]))
                        v = np.asarray(res.valid[n])
                        block[i] = np.where(v, d, nodata)
                await asyncio.to_thread(writer.write_region, ox, oy,
                                        block)
                return
            for n in ns_names:
                if n in res.data:
                    out[n][oy:oy + th, ox:ox + tw] = \
                        device_guard.guarded_readback(
                            "export.readback", lambda n=n:
                            np.asarray(res.data[n]))
                    valid[n][oy:oy + th, ox:ox + tw] = \
                        np.asarray(res.valid[n])
        # OWS-cluster scale-out (`ows.go:835-872,930-995,1094-1150`):
        # partition the output into contiguous tile-row bands, render
        # band 0 locally and re-enter GetCoverage on peer nodes for the
        # rest (wshard=1 guards recursion); peer GeoTIFFs merge into the
        # master canvas, and a failed peer's band falls back to local
        # rendering.
        nodes = cfg.service_config.ows_cluster_nodes
        local_tiles = list(tiles)
        remote_jobs = []
        if q is not None and not is_shard and not stream_tif \
                and len(nodes) > 1 and len(tiles) >= 2 * len(nodes):
            row_starts = sorted({t[2] for t in tiles})
            per = max(1, -(-len(row_starts) // len(nodes)))
            groups = [row_starts[i * per:(i + 1) * per]
                      for i in range(len(nodes))]
            local_rows = set(groups[0])
            local_tiles = [t for t in tiles if t[2] in local_rows]
            resy = (p.bbox.ymax - p.bbox.ymin) / height
            for node, grp in zip(nodes[1:], groups[1:]):
                if not grp:
                    continue
                tiles_in = [t for t in tiles if t[2] in set(grp)]
                y0px = grp[0]
                y1px = max(t[2] + t[4] for t in tiles_in)
                bb = BBox(p.bbox.xmin, p.bbox.ymax - y1px * resy,
                          p.bbox.xmax, p.bbox.ymax - y0px * resy)
                remote_jobs.append((node, tiles_in, bb, y0px, y1px))

        async def fetch_shard(node, tiles_in, bb, y0px, y1px):
            try:
                import aiohttp
                params = {k: str(v) for k, v in q.items()}
                params.update({
                    "service": "WCS", "request": "GetCoverage",
                    "bbox": f"{bb.xmin},{bb.ymin},{bb.xmax},{bb.ymax}",
                    "width": str(width), "height": str(y1px - y0px),
                    "format": "geotiff", "wshard": "1"})
                url = node if "://" in node else f"http://{node}"
                url = url.rstrip("/") + path
                # peer fetch charged against the request budget: a slow
                # peer can't eat more than what's left, and the local
                # fallback below runs on the remainder
                tmo = aiohttp.ClientTimeout(total=dl.clamp(
                    lay.wcs_timeout * max(1, len(tiles_in))))
                async with aiohttp.ClientSession(timeout=tmo) as s:
                    async with s.get(url, params=params) as resp:
                        if resp.status != 200:
                            raise RuntimeError(
                                f"shard node {node}: HTTP {resp.status}")
                        body = await resp.read()
                spath = os.path.join(
                    self.temp_dir, f"shard_{y0px}_{id(bb)}.tif")
                with open(spath, "wb") as fp:
                    fp.write(body)
                try:
                    tif = GeoTIFF(spath)
                    for bi, n in enumerate(ns_names):
                        a = np.asarray(tif.read(bi + 1), np.float32)
                        v = a != nodata
                        out[n][y0px:y1px, :] = a
                        valid[n][y0px:y1px, :] = v
                    tif.close()
                finally:
                    os.remove(spath)
            except Exception:
                log.exception("WCS shard via %s failed; rendering locally",
                              node)
                results = await asyncio.gather(
                    *(render_tile(*t) for t in tiles_in),
                    return_exceptions=True)
                errs = [r for r in results if isinstance(r, BaseException)]
                for r in errs:
                    # cancellation (request teardown) must still unwind
                    if isinstance(r, asyncio.CancelledError):
                        raise r
                if errs:
                    # a failed fallback tile degrades its band instead of
                    # 500ing the whole export — the rest keeps merging
                    log.warning(
                        "%d/%d local-fallback tiles failed after shard "
                        "%s failure (first: %s)", len(errs),
                        len(tiles_in), node, errs[0])
                    mark_degraded("shard-fallback")

        # multi-tile exports go through the staged export engine: ONE
        # index query over the full bbox, cross-tile decode dedup, and
        # decode/warp/encode overlap (docs/EXPORT.md).  Fusion layers
        # keep the per-tile path (each tile composes its input layers);
        # GSKY_EXPORT_PIPELINE=0 is the serial escape hatch.
        engine = None
        if (len(local_tiles) > 1 and not lay.input_layers
                and export_pipeline_enabled()):
            engine = ExportPipeline(
                pipe,
                dataclasses.replace(
                    base_req, polygon_segments=lay.wcs_polygon_segments),
                local_tiles, ns_names, p.bbox, width, height,
                nodata=nodata, writer=writer, out=out, valid=valid)

        export_stats: Dict = {}

        async def render_local():
            if engine is None:
                await asyncio.gather(*(render_tile(*t)
                                       for t in local_tiles))
                return
            export_stats.update(await asyncio.to_thread(engine.run))

        def fold_export(write_s=None):
            """Once per answered engine export, when its last timed part
            is over: the stats into /debug `export_pipeline`."""
            if not export_stats:
                return
            if write_s is not None:
                export_stats["write_s"] = round(write_s, 6)
            try:
                self.metrics.record_export(export_stats)
            except Exception:  # export metrics are telemetry only
                pass

        try:
            with deadline_scope(dl):
                await asyncio.wait_for(
                    asyncio.gather(render_local(),
                                   *(fetch_shard(*j) for j in remote_jobs)),
                    timeout=dl.remaining())
        except BaseException:
            # close + unlink the partial stream file on timeout/failure
            # (ADVICE r1: fd and temp-file leak)
            if engine is not None:
                engine.cancel()
            if writer is not None:
                try:
                    await asyncio.to_thread(writer.close)
                except Exception:  # writer already closed by a completed engine
                    pass
                try:
                    os.remove(stream_path)
                except OSError:
                    pass
            raise
        if writer is not None:
            fold_export()       # a streamed export has no assembly
        if stream_dap:
            # the coverage is complete on disk; the DAP4 body now
            # streams spool row-batches through the chunk framer, so
            # peak RSS is one row batch + one chunk, not the canvases
            stats_d: Dict[str, int] = {}
            gen = dap4.stream_dap4(ns_names, writer, stats=stats_d)
            resp = web.StreamResponse(status=200)
            resp.content_type = dap4.CONTENT_TYPE
            await resp.prepare(request)
            try:
                while True:
                    chunk = await asyncio.to_thread(next, gen, None)
                    if chunk is None:
                        break
                    await resp.write(chunk)
            finally:
                await asyncio.to_thread(writer.close)
            try:
                from ..obs import metrics as _om
                _om.record_dap_stream(stats_d.get("bytes", 0),
                                      stats_d.get("peak_buffer", 0))
            except Exception:  # stream metrics are telemetry only
                pass
            await resp.write_eof()
            return resp
        if writer is not None:
            await asyncio.to_thread(writer.close)
            fname = f"{lay.name}_{stamp}.tif"
            asyncio.get_event_loop().call_later(
                600, lambda: os.path.exists(stream_path)
                and os.remove(stream_path))
            return web.FileResponse(writer.path, headers={
                "Content-Disposition": f'attachment; filename="{fname}"',
                "Content-Type": "image/geotiff"})
        # the assembly after the engine, for the in-RAM legs: mask to
        # nodata, encode, write, read the file back into the body
        t_write = time.perf_counter()
        with obs.span("export.write", format=fmt, width=width,
                      height=height):
            resp = await self._assemble_coverage(
                lay, fmt, ns_names, out, valid, nodata, gt, p, stamp,
                width, height)
        fold_export(time.perf_counter() - t_write)
        return resp

    async def _assemble_coverage(self, lay, fmt, ns_names, out, valid,
                                 nodata, gt, p, stamp, width, height):
        """The response of an in-RAM GetCoverage from its whole-coverage
        canvases."""
        # finalise in place: the render is done with out[n], so masking
        # nodata needs no second full-coverage copy (a 4-band 4K export
        # peaked at 2x the float32 canvases)
        arrays = {}
        for n in ns_names:
            a = out[n]
            a[~valid[n]] = nodata
            arrays[n] = a
        if fmt == "dap4":
            body = await asyncio.to_thread(dap4.encode_dap4, ns_names,
                                           arrays)
            return web.Response(body=body, content_type=dap4.CONTENT_TYPE)
        if fmt in ("netcdf", "nc", "application/x-netcdf"):
            path = os.path.join(self.temp_dir, f"wcs_{stamp}_{id(p)}.nc")
            xs = gt.x0 + (np.arange(width) + 0.5) * gt.dx
            ys = gt.y0 + (np.arange(height) + 0.5) * gt.dy
            await asyncio.to_thread(write_netcdf3, path, arrays, xs, ys,
                                    p.crs, None, nodata)
            fname = f"{lay.name}_{stamp}.nc"
            ctype = "application/x-netcdf"
        else:
            path = os.path.join(self.temp_dir, f"wcs_{stamp}_{id(p)}.tif")
            stack = np.stack([arrays[n] for n in ns_names])
            await asyncio.to_thread(write_geotiff, path, stack, gt, p.crs,
                                    nodata)
            fname = f"{lay.name}_{stamp}.tif"
            ctype = "image/geotiff"
        size = os.path.getsize(path)
        headers = {"Content-Disposition": f'attachment; filename="{fname}"'}
        if size <= 256 * 1024 * 1024:
            with open(path, "rb") as fp:
                body = fp.read()
            os.remove(path)
            return web.Response(body=body, content_type=ctype,
                                headers=headers)
        # very large outputs stream from disk; reap the temp file later
        asyncio.get_event_loop().call_later(
            600, lambda: os.path.exists(path) and os.remove(path))
        headers["Content-Type"] = ctype
        return web.FileResponse(path, headers=headers)

    # -- WPS (`ows.go:1223-1441`) -------------------------------------------

    async def serve_wps(self, request, cfg: Config, q, collector):
        body = await request.read() if request.method == "POST" else None
        with obs.span("wps.parse", body_bytes=len(body or b"")):
            p = parse_wps(q, body if body else None)
        req_name = (p.request or "").lower()
        host = _host_of(request, cfg)
        if req_name == "getcapabilities" or not req_name:
            return _xml(T.wps_capabilities(cfg, request.path, host))
        if req_name == "describeprocess":
            proc = cfg.process(p.identifier)
            if proc is None:
                raise OWSError(f"process {p.identifier!r} not found",
                               "InvalidParameterValue")
            return _xml(T.wps_describe_process(proc))
        if req_name != "execute":
            raise OWSError(f"WPS request {p.request!r} not supported",
                           "OperationNotSupported")
        t0, pc0 = time.time(), time.perf_counter()
        async with self._admit("WPS", _tenant_of(request)):
            obs.record_span("gateway.admission",
                            time.perf_counter() - pc0, t0=t0,
                            service="WPS")
            return await self._wps_execute(cfg, p)

    async def _wps_execute(self, cfg: Config, p) -> web.Response:
        proc = cfg.process(p.identifier)
        if proc is None:
            raise OWSError(f"process {p.identifier!r} not found",
                           "InvalidParameterValue")
        if not p.geometry_json:
            raise OWSError("geometry input required",
                           "MissingParameterValue")
        with obs.span("wps.parse") as psp:
            try:
                g = geom.from_geojson(p.geometry_json)
            except (ValueError, KeyError) as e:
                raise OWSError(f"invalid GeoJSON geometry: {e}")
            if g.kind not in ("Point", "Polygon", "MultiPolygon"):
                raise OWSError(
                    f"geometry type {g.kind} not supported; use Point/"
                    f"Polygon/MultiPolygon")
            if proc.max_area > 0 and g.area() > proc.max_area:
                raise OWSError(
                    f"geometry area exceeds process limit {proc.max_area}")
            wkt = g.to_wkt()
            psp.set(vertices=sum(len(r) for poly in g.polys for r in poly)
                    + (len(g.points) if g.points is not None else 0))

        results = []
        for src in proc.data_sources:
            vrt_xml = ""
            if src.vrt_url:
                # drill-through-VRT: load the registered template
                # (`ows.go:1389-1406` VRTURL -> view.GetTemplate)
                vp = src.vrt_url if os.path.isabs(src.vrt_url) \
                    else os.path.join(cfg.base_dir, src.vrt_url)
                try:
                    with open(vp) as fp:
                        vrt_xml = fp.read()
                except OSError as e:
                    raise OWSError(f"VRT template {src.vrt_url!r} "
                                   f"unreadable: {e}")
            dreq = GeoDrillRequest(
                collection=src.data_source, bands=src.rgb_products,
                geometry_wkt=wkt,
                start_time=p.start_time, end_time=p.end_time,
                deciles=proc.deciles, approx=proc.approx,
                band_strides=src.band_strides,
                pixel_count="pixel_count" in proc.drill_algorithm,
                vrt_url=src.vrt_url, vrt_xml=vrt_xml,
                mask_namespaces=[src.mask.id] if src.mask else (),
                index_tile_x_size=src.index_tile_x_size,
                index_tile_y_size=src.index_tile_y_size)
            dp = DrillPipeline(self._mas(cfg))
            # year-stepped splitting (TimeSplitter parity) bounds the
            # per-window working set for multi-decade drills
            with deadline_scope(Deadline(src.wcs_timeout or 30)) as ddl:
                res = await asyncio.wait_for(
                    asyncio.to_thread(dp.process_split, dreq,
                                      proc.year_step),
                    timeout=ddl.remaining())
            results.append(res)
        from ..pipeline.drill import drill_csv
        with obs.span("wps.format") as fsp:
            csv_blocks = [drill_csv(res, list(res.values))
                          for res in results]
            resp = _xml(T.wps_execute_response(p.identifier, csv_blocks))
            fsp.set(rows=sum(len(res.dates) for res in results),
                    bytes=len(resp.body))
        trace = obs.current_trace()
        if trace is not None:
            # the drill's counters are a fold of its spans: one
            # measurement, read by /debug, /metrics and the trace alike
            self.metrics.record_drill(trace.seconds_by_name(),
                                      trace.age_s(),
                                      files=trace.total("files"),
                                      windows=trace.count("drill.prepare"),
                                      cpu=trace.cpu_by_name())
        return resp


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

# query params represented canonically (parsed/normalised) inside the
# cache key; everything else is folded in verbatim as `extras`
_KEY_CONSUMED = frozenset({
    "service", "request", "version", "layers", "layer", "styles",
    "style", "crs", "srs", "bbox", "width", "height", "format", "time",
    "coverage", "coverageid", "identifier", "subset", "exceptions",
})


def _register_gateway_invalidation(watcher, gateway) -> None:
    """Subscribe ``gateway``'s reload invalidation to ``watcher`` once
    per (watcher, gateway) pair — constructing many servers against one
    shared watcher/gateway (tests, embedding) must not accumulate
    listeners or sweep the cache N times per reload.  The listener
    holds the gateway weakly and unregisters itself when it dies."""
    if not hasattr(watcher, "add_listener"):
        return
    registered = getattr(watcher, "_serving_gateways", None)
    if registered is None:
        registered = weakref.WeakSet()
        try:
            watcher._serving_gateways = registered
        except AttributeError:
            return
    if gateway in registered:
        return
    registered.add(gateway)
    gw_ref = weakref.ref(gateway)

    def _listener(configs):
        gw = gw_ref()
        if gw is None:
            remove = getattr(watcher, "remove_listener", None)
            if remove is not None:
                remove(_listener)
            return
        gw.invalidate_for_configs(configs)

    watcher.add_listener(_listener)


def _freeze_response(resp: web.StreamResponse):
    """(status, content_type, body, kept_headers) for responses whose
    body is in RAM; streaming responses (FileResponse) pass through
    unfrozen — they can be returned once, by the flight leader."""
    body = getattr(resp, "body", None)
    if not isinstance(body, (bytes, bytearray)):
        return resp
    keep = tuple((k, resp.headers[k]) for k in ("Content-Disposition",)
                 if k in resp.headers)
    return (resp.status, resp.content_type, bytes(body), keep)


def _etag_match(header: str, etag: str) -> bool:
    if header.strip() == "*":
        return True
    for tok in header.split(","):
        tok = tok.strip()
        if tok.startswith("W/"):
            tok = tok[2:]
        if tok == etag:
            return True
    return False


def _render_with_fusion(pipe: TilePipeline, req: GeoTileRequest, lay: Layer,
                        cfg: Config, server: OWSServer):
    """Plain layers render directly; fusion layers (`input_layers`,
    `processor/tile_pipeline.go:196-324`) render each input layer and
    compose first-valid in order (earlier inputs win, later fill holes)."""
    if not lay.input_layers:
        return pipe.process(req)
    from ..pipeline.tile import evaluate_expressions
    data_env: Dict[str, np.ndarray] = {}
    valid_env: Dict[str, np.ndarray] = {}
    total_granules = total_files = 0
    import dataclasses
    for dep in lay.input_layers:
        dep_mask = None
        if dep.mask is not None:
            dep_mask = MaskSpec(id=dep.mask.id, value=dep.mask.value,
                                bit_tests=dep.mask.bit_tests,
                                data_source=dep.mask.data_source,
                                inclusive=dep.mask.inclusive)
        dreq = dataclasses.replace(
            req, collection=dep.data_source, bands=list(dep.rgb_products),
            mask=dep_mask or req.mask,
            resample=dep.resample or req.resample, _exprs=None)
        res = pipe.process(dreq)
        total_granules += res.granule_count
        total_files += res.file_count
        for n in res.namespaces:
            if n not in data_env:
                data_env[n] = res.data[n]
                valid_env[n] = res.valid[n]
            else:  # later inputs fill holes (device-resident)
                fill = ~jnp.asarray(valid_env[n]) & jnp.asarray(res.valid[n])
                data_env[n] = jnp.where(fill, jnp.asarray(res.data[n]),
                                        jnp.asarray(data_env[n]))
                valid_env[n] = jnp.asarray(valid_env[n]) \
                    | jnp.asarray(res.valid[n])
    return evaluate_expressions(req.band_exprs, data_env, valid_env,
                                req.height, req.width, total_granules,
                                total_files)


def _best_overview(lay: Layer, res: float) -> Optional[Layer]:
    """`FindLayerBestOverview` (`utils/wms.go:534-553`): coarsest overview
    whose zoom_limit still admits the request resolution."""
    best = None
    for ov in lay.overviews:
        if ov.zoom_limit <= 0 or res <= ov.zoom_limit:
            if best is None or ov.zoom_limit > best.zoom_limit:
                best = ov
    return best


def _with_bands(req: GeoTileRequest, bands) -> GeoTileRequest:
    import dataclasses
    return dataclasses.replace(req, bands=list(bands), _exprs=None)


def _host_of(request, cfg: Config) -> str:
    if cfg.service_config.ows_hostname:
        host = cfg.service_config.ows_hostname
        if not host.startswith("http"):
            host = f"http://{host}"
        return host
    return f"{request.scheme}://{request.host}"


def _xml(doc: str) -> web.Response:
    return web.Response(text=doc, content_type="text/xml")


def _png(data: bytes) -> web.Response:
    return web.Response(body=data, content_type="image/png")


def _png_level(lay, style=None):
    """Effective per-layer PNG zlib level: style (when it sets one)
    beats layer beats None (= GSKY_PNG_LEVEL / the io.png default).
    Under brownout every PNG drops to the cheapest effort — larger
    bytes on the wire beat CPU spent compressing while the host is
    short on memory (this is the single chokepoint for all encode
    call sites, so the lever covers GetMap, legends and placeholders
    alike)."""
    if brownout_level():
        return 0
    for src in (style, lay):
        if src is not None and src.png_compress_level >= 0:
            return src.png_compress_level
    return None


def _tenant_of(request) -> str:
    """Tenant identity for weighted-fair admission queues: explicit API
    key when presented, else the first X-Forwarded-For hop (the real
    client behind a proxy), else the socket peer.  Coarse by design —
    the queues only need enough identity to stop one bulk client from
    starving everyone else."""
    key = request.headers.get("X-API-Key") or request.query.get("key")
    if key:
        return f"key:{key[:32]}"
    fwd = request.headers.get("X-Forwarded-For")
    if fwd:
        return fwd.split(",")[0].strip() or "anon"
    return request.remote or "anon"


def _exception_response(e: OWSError,
                        headers: Optional[Dict[str, str]] = None
                        ) -> web.Response:
    return web.Response(text=T.service_exception(str(e), e.code),
                        content_type="application/vnd.ogc.se_xml",
                        status=e.status, headers=headers)
