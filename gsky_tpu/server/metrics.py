"""Structured per-request metrics logging.

JSON schema parity with `metrics/metrics.go:22-57` + `metrics/log_format.md`:
``{req_time, req_duration, url{raw_url,host,path,query}, remote_addr,
remote_host, remote_port, http_status, indexer{duration,url,geometry,
geometry_area,num_files,num_granules}, rpc{duration,num_tiled_granules,
bytes_read,user_time,sys_time}}``.  Durations are nanoseconds.  Query
params outside the reference's allowlist are dropped
(`metrics/metrics.go:64`).  Sink: stdout or size-rotated gzip files
(`metrics/logger.go`).
"""

from __future__ import annotations

import datetime as dt
import gzip
import json
import os
import sys
import threading
import time
from typing import Dict, Optional

RESERVED_QUERY_PARAMS = {
    "bbox", "coverage", "crs", "dptol", "height", "identifier",
    "identitytol", "layer", "layers", "limit", "namespace", "nseg",
    "request", "service", "srs", "styles", "time", "until", "version",
    "width", "wkt",
    # DAP4 constraint marker: without it every DAP request aggregates
    # under "?.?" in the /debug summary
    "dap4.ce",
}


# Cache counter sources, resolved once per process.  Every /debug
# scrape and every request record folds these in; re-running the import
# machinery four times per scrape was pure overhead.  The getters read
# through the owning module so tests that swap a singleton still see
# the live object.
_CACHE_HANDLES = None
_CACHE_HANDLES_LOCK = threading.Lock()


def _resolve_cache_handles():
    handles = []
    try:
        from ..pipeline import scene_cache as m
        handles.append(("scene", lambda m=m: m.default_scene_cache.stats()))
    except Exception:  # tier absent in this build - skip its counters
        pass
    try:
        from ..pipeline import drill_cache as m
        handles.append(("drill_stack", lambda m=m: {
            "hits": m.default_drill_cache.hits,
            "misses": m.default_drill_cache.misses}))
    except Exception:  # tier absent in this build - skip its counters
        pass
    try:
        from ..index.store import MASStore as cls
        handles.append(("mas_query", lambda cls=cls: {
            "hits": cls.total_query_hits,
            "misses": cls.total_query_misses}))
        handles.append(("mas_rows", lambda cls=cls: {
            "hits": cls.total_row_hits,
            "misses": cls.total_row_misses}))
        handles.append(("mas_footprints", lambda cls=cls: {
            "hits": cls.total_footprint_hits,
            "misses": cls.total_footprint_misses}))
        handles.append(("mas_sql", lambda cls=cls: {
            "queries": cls.total_query_misses,
            "statements": cls.total_sql_statements}))
    except Exception:  # tier absent in this build - skip its counters
        pass
    try:
        # the serving gateway in front of the pipelines: rendered-
        # response LRU hits, singleflight joins, admission sheds
        from .. import serving as m
        handles.append(("response",
                        lambda m=m: m.default_gateway.cache_counters()))
    except Exception:  # tier absent in this build - skip its counters
        pass
    return tuple(handles)


def cache_stats() -> Dict:
    """Cumulative hit/miss counters of the process-wide caches — the
    observability the reference gets from memcached stats in front of
    MAS (`mas/api/api.go:43-52`), extended to the device-resident
    tiers.  Guarded: metrics must never fail a request.  Also the
    source for the `/metrics` cache families (obs/metrics.py) so the
    two endpoints cannot drift."""
    global _CACHE_HANDLES
    handles = _CACHE_HANDLES
    if handles is None:
        with _CACHE_HANDLES_LOCK:
            if _CACHE_HANDLES is None:
                _CACHE_HANDLES = _resolve_cache_handles()
            handles = _CACHE_HANDLES
    out: Dict = {}
    for key, fn in handles:
        try:
            out[key] = fn()
        except Exception:  # a failing handle yields no row, not a failed scrape
            pass
    return out


_cache_stats = cache_stats          # historical internal name


class MetricsCollector:
    def __init__(self, logger: "MetricsLogger"):
        self._logger = logger
        self._t0 = time.time()
        self.info: Dict = {
            "req_time": dt.datetime.now(dt.timezone.utc)
            .strftime("%Y-%m-%dT%H:%M:%S.%f")[:-3] + "Z",
            "req_duration": 0,
            "url": {"raw_url": "", "host": "", "path": "", "query": {}},
            "remote_addr": "",
            "remote_host": "",
            "remote_port": "",
            "http_status": 200,
            "indexer": {"duration": 0,
                        "url": {"raw_url": "", "host": "", "path": "",
                                "query": {}},
                        "geometry": "", "geometry_area": 0.0,
                        "num_files": 0, "num_granules": 0},
            "rpc": {"duration": 0, "num_tiled_granules": 0,
                    "bytes_read": 0, "user_time": 0, "sys_time": 0},
            # beyond the reference schema (SURVEY §5.1): time spent
            # blocked on the accelerator result, and the jax platform
            "device": {"duration": 0, "platform": ""},
            # correlation id: joins this record to the flight-recorder
            # trace and to worker-side log lines
            "trace_id": "",
        }

    def set_url(self, raw_url: str, path: str, query: Dict[str, str]):
        self.info["url"] = {
            "raw_url": raw_url, "host": "", "path": path,
            "query": {k: v for k, v in query.items()
                      if k in RESERVED_QUERY_PARAMS},
        }

    def set_remote(self, addr: str):
        self.info["remote_addr"] = addr
        host, port = addr, ""
        if addr.startswith("["):          # [v6]:port
            host, _, rest = addr.partition("]")
            host = host[1:]
            port = rest.lstrip(":")
        elif addr.count(":") == 1:        # v4:port
            host, _, port = addr.partition(":")
        # bare v4 / bare v6: no port
        self.info["remote_host"] = host
        self.info["remote_port"] = port

    def log(self, status: int = 200):
        self.info["http_status"] = status
        self.info["req_duration"] = int((time.time() - self._t0) * 1e9)
        self.info["cache"] = cache_stats()
        if not self.info.get("trace_id"):
            try:
                from ..obs import current_trace_id
                self.info["trace_id"] = current_trace_id() or ""
            except Exception:  # trace id is optional decoration on the summary
                pass
        self._logger.record_summary(self.info)
        self._logger.write(self.info)


class MetricsLogger:
    """stdout or rotated gzip file sink (`metrics/logger.go:35-223`),
    tunables via env GSKY_MAX_LOG_FILE_SIZE / GSKY_MAX_LOG_FILES."""

    # per-verb rolling latency reservoir size (the /debug side-door's
    # percentile window)
    _RESERVOIR = 512

    def __init__(self, log_dir: str = "", verbose: bool = False):
        self.log_dir = log_dir
        self.verbose = verbose
        self._lock = threading.Lock()
        self._fp = None
        self._size = 0
        self.max_size = int(os.environ.get("GSKY_MAX_LOG_FILE_SIZE",
                                           50 * 1024 * 1024))
        self.max_files = int(os.environ.get("GSKY_MAX_LOG_FILES", 10))
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
        self.started = time.time()
        # verb -> {count, errors, lat (deque of recent seconds),
        #          device_ms_sum, rpc_ms_sum}
        self._summary: Dict[str, Dict] = {}
        self._summary_lock = threading.Lock()
        # cumulative staged-export (pipeline/export.py) aggregates
        self._export: Dict = {}
        # cumulative staged-tile (pipeline/tile_stages.py) aggregates
        self._tiles: Dict = {}
        # which program served each three-band GetMap (record_rgb_route)
        self._rgb_routes: Dict[str, int] = {"rgba": 0, "planes": 0,
                                            "fallback": 0, "empty": 0}
        # cumulative WPS Execute stage aggregates, folded from spans
        self._drills: Dict = {}

    def collector(self) -> MetricsCollector:
        return MetricsCollector(self)

    def record_summary(self, info: Dict) -> None:
        """Fold one request into the rolling per-verb aggregates the
        /debug route serves (`net/http/pprof` role, `ows.go:40`)."""
        from collections import deque
        try:
            q = info.get("url", {}).get("query", {})
            if "dap4.ce" in q:
                verb = "DAP4.ce"
            else:
                verb = (str(q.get("service", "?")) + "."
                        + str(q.get("request", "?")))[:48]
            dur_s = info.get("req_duration", 0) / 1e9
            status = info.get("http_status", 200)
            with self._summary_lock:
                s = self._summary.get(verb)
                if s is None:
                    s = self._summary[verb] = {
                        "count": 0, "errors": 0,
                        "lat": deque(maxlen=self._RESERVOIR),
                        "device_ms": 0.0, "rpc_ms": 0.0}
                s["count"] += 1
                if status >= 400:
                    s["errors"] += 1
                s["lat"].append(dur_s)
                s["device_ms"] += info.get("device", {}).get(
                    "duration", 0) / 1e6
                s["rpc_ms"] += info.get("rpc", {}).get(
                    "duration", 0) / 1e6
            # same fold point feeds /metrics: one clock, no drift
            from ..obs.metrics import REQUESTS, REQUEST_SECONDS
            svc = "DAP4" if "dap4.ce" in q else \
                str(q.get("service", "?")).upper()
            REQUESTS.labels(service=svc, status=str(status)).inc()
            REQUEST_SECONDS.labels(service=svc).observe(dur_s)
        except Exception:   # observability must never fail a request
            pass

    # sum / max folding for export-stats keys; everything else keeps
    # the latest value via the "last" snapshot
    _EXPORT_SUMS = ("tiles", "granules", "index_queries", "scenes_warmed",
                    "scenes_uncacheable", "windows_decoded",
                    "granule_tile_refs", "dedup_saved", "plan_s",
                    "decode_s", "warp_s", "encode_s", "write_s", "wall_s",
                    "tiles_resident", "tiles_fallback", "readback_bytes")
    _EXPORT_MAXES = ("warp_queue_max", "encode_queue_max")

    def record_export(self, stats: Dict) -> None:
        """Fold one staged export's stats dict (`ExportPipeline.run`)
        into the /debug aggregates."""
        try:
            with self._summary_lock:
                e = self._export
                e["exports"] = e.get("exports", 0) + 1
                for k in self._EXPORT_SUMS:
                    if k in stats:
                        e[k] = round(e.get(k, 0) + stats[k], 6)
                for k in self._EXPORT_MAXES:
                    if k in stats:
                        e[k] = max(e.get(k, 0), stats[k])
                # tiles by how their kernel fetched its taps
                forms = e.setdefault("tap_form", {})
                for k, n in stats.get("tap_form", {}).items():
                    forms[k] = forms.get(k, 0) + n
                e["last"] = dict(stats)
            from ..obs.metrics import STAGE_SECONDS
            for k in ("plan_s", "decode_s", "warp_s", "encode_s",
                      "write_s", "wall_s"):
                if k in stats:
                    STAGE_SECONDS.labels(
                        stage="export_" + k[:-2]).observe(stats[k])
        except Exception:   # observability must never fail a request
            pass

    # staged-tile span folding (pipeline/tile_stages.py), mirroring the
    # export aggregates above: per-stage seconds sum, queue high-water
    # marks max, the raw per-request record kept as "last"
    _TILE_STAGES = ("plan_s", "index_s", "decode_s", "dispatch_s",
                    "readback_s", "encode_s")
    _TILE_SUMS = _TILE_STAGES + (
        "granules", "plan_cpu_s", "index_cpu_s", "decode_cpu_s",
        "dispatch_cpu_s", "readback_cpu_s", "encode_cpu_s", "wall_s")
    _TILE_MAXES = ("decode_queue_max", "dispatch_queue_max",
                   "encode_queue_max")
    # tile_stages key <- the span whose thread CPU it sums; `tile.plan`
    # holds `tile.index`, and plan_cpu_s is the rest, as plan_s is
    _TILE_CPU = (("plan_cpu_s", "tile.plan"), ("index_cpu_s", "tile.index"),
                 ("decode_cpu_s", "tile.decode"),
                 ("dispatch_cpu_s", "tile.dispatch"),
                 ("readback_cpu_s", "tile.readback"))

    def record_tile(self, spans: Dict, cpu: Optional[Dict[str, float]] = None,
                    wall_s: Optional[float] = None) -> None:
        """Fold one staged GetMap render's stage spans into the /debug
        `tile_stages` aggregates.  ``cpu`` is the request's trace folded
        by span name (`Trace.cpu_by_name`), ``wall_s`` its root span's
        age when the encode landed; both None untraced, and then the
        keys made of them are absent."""
        try:
            if cpu is not None:
                spans.update({k: cpu[name] for k, name in self._TILE_CPU
                              if name in cpu})
                if "plan_cpu_s" in spans:
                    spans["plan_cpu_s"] = max(0.0, spans["plan_cpu_s"]
                                              - spans.get("index_cpu_s", 0.0))
            if wall_s is not None:
                spans["wall_s"] = wall_s
            with self._summary_lock:
                e = self._tiles
                e["tiles"] = e.get("tiles", 0) + 1
                for k in self._TILE_SUMS:
                    if k in spans:
                        e[k] = round(e.get(k, 0) + spans[k], 6)
                for k in self._TILE_MAXES:
                    if k in spans:
                        e[k] = max(e.get(k, 0), spans[k])
                e["last"] = dict(spans)
            from ..obs.metrics import STAGE_SECONDS
            for k in self._TILE_STAGES:
                if k in spans:
                    STAGE_SECONDS.labels(stage=k[:-2]).observe(spans[k])
        except Exception:   # observability must never fail a request
            pass

    def record_rgb_route(self, route: str) -> None:
        """Count one three-band GetMap by the program that served it:
        `rgba` (one granule, `render_rgba_ctrl`), `planes` (several,
        `render_scenes_bands_ctrl`), `fallback` (neither qualified: the
        modular window-decode render) or `empty` (the index found no
        granule under the tile: no program ran).  /debug `rgb_routes`."""
        with self._summary_lock:
            self._rgb_routes[route] += 1

    # /debug drill_stages key <- the span it sums (docs/OBSERVABILITY.md);
    # the stages of one Execute run one after another, so wall_s minus
    # their sum is what no span covers yet
    _DRILL_STAGES = (("parse_s", "wps.parse"),
                     ("admission_s", "gateway.admission"),
                     ("index_s", "drill.index"),
                     ("prepare_s", "drill.prepare"),
                     ("device_s", "drill.device"),
                     ("host_read_s", "drill.host_read"),
                     ("merge_s", "drill.merge"),
                     ("format_s", "wps.format"))

    def record_drill(self, stages: Dict[str, float], wall_s: float,
                     files: int, windows: int,
                     cpu: Optional[Dict[str, float]] = None) -> None:
        """Fold one answered WPS Execute into the /debug `drill_stages`
        aggregates.  ``stages`` is the request's trace folded by span
        name (`Trace.seconds_by_name`), ``wall_s`` the root span's age,
        ``files`` the files drilled (answered by the device or by host
        reads), ``windows`` the windows and masks made for them (the
        `drill.prepare` spans: files on one grid share one).  ``cpu``
        (`Trace.cpu_by_name`) adds `<stage>_cpu_s` for each stage whose
        spans carry thread CPU (those run off the event loop)."""
        try:
            last = {k: round(stages.get(name, 0.0), 6)
                    for k, name in self._DRILL_STAGES}
            last["wall_s"] = round(wall_s, 6)
            last["files"] = files
            last["windows"] = windows
            for k, name in self._DRILL_STAGES:
                if cpu and name in cpu:
                    last[k[:-2] + "_cpu_s"] = round(cpu[name], 6)
            with self._summary_lock:
                e = self._drills
                e["requests"] = e.get("requests", 0) + 1
                for k, v in last.items():
                    e[k] = round(e.get(k, 0) + v, 6)
                e["last"] = last
            from ..obs.metrics import STAGE_SECONDS
            for k, v in last.items():
                if k.endswith("_s") and not k.endswith("_cpu_s"):
                    STAGE_SECONDS.labels(stage="drill_" + k[:-2]).observe(v)
        except Exception:   # observability must never fail a request
            pass

    def summary(self) -> Dict:
        """The /debug document body: uptime, per-verb counts + latency
        percentiles over the rolling window, cumulative device/pipeline
        time, cache hit/miss counters."""
        out: Dict = {"uptime_s": round(time.time() - self.started, 1),
                     "requests": {}}
        with self._summary_lock:
            for verb, s in self._summary.items():
                lat = sorted(s["lat"])

                def pct(p, lat=lat):
                    return round(
                        lat[min(int(len(lat) * p), len(lat) - 1)] * 1e3,
                        1) if lat else None
                out["requests"][verb] = {
                    "count": s["count"], "errors": s["errors"],
                    "p50_ms": pct(0.5), "p99_ms": pct(0.99),
                    "window": len(lat),
                    "device_ms_total": round(s["device_ms"], 1),
                    "pipeline_ms_total": round(s["rpc_ms"], 1)}
            if self._export.get("exports"):
                from ..io.geotiff import deflate_pool_stats
                out["export_pipeline"] = dict(
                    self._export,
                    tap_form=dict(self._export.get("tap_form", {})),
                    deflate=deflate_pool_stats())
            if self._drills.get("requests"):
                out["drill_stages"] = dict(self._drills)
            if self._tiles.get("tiles"):
                out["tile_stages"] = dict(self._tiles)
                try:
                    from ..io.png import encode_pool_stats
                    from ..pipeline.tile_stages import gate_stats
                    out["tile_stages"]["gates"] = gate_stats()
                    out["tile_stages"]["encode_pool"] = encode_pool_stats()
                except Exception:  # stage gates absent when the tile pipeline is off
                    pass
            out["rgb_routes"] = dict(self._rgb_routes)
        out["cache"] = _cache_stats()
        try:
            # the process's CPU clock and the cyclic collector's pauses
            from ..obs.process import process_stats
            out["process"] = process_stats()
        except Exception:   # observability must never fail a request
            pass
        try:
            from ..resilience import registry as _resilience
            out["resilience"] = _resilience.stats()
        except Exception:   # observability must never fail a request
            pass
        try:
            from ..ops import kernel_ledger
            out["kernels"] = kernel_ledger.stats()
        except Exception:   # observability must never fail a request
            pass
        try:
            # device supervisor state machine + page-residency journal:
            # the "is the accelerator healthy, and how warm would a
            # rebuilt pool come back" block (docs/RESILIENCE.md)
            from .. import device_guard
            dev = device_guard.default_supervisor().stats()
            dev["journal"] = device_guard.journal.stats()
            # the byte budget of scenes + stacks and what it was derived
            # from (cache.scene holds what is charged against it)
            from ..device import residency_budget
            dev["residency"] = residency_budget()
            out["device"] = dev
        except Exception:   # observability must never fail a request
            pass
        try:
            # wave-dispatch coalescing: requests vs device programs,
            # occupancy histogram, readback queue depth — the "is the
            # ~75 ms dispatch tax actually being amortised" block
            # (docs/PERF.md); {} until the first wave request
            from ..pipeline.waves import wave_stats
            ws = wave_stats()
            if ws:
                out["waves"] = ws
        except Exception:   # observability must never fail a request
            pass
        try:
            # per-node health states, routed/hedged/re-routed counts,
            # ring generation — one entry per live fleet router
            from ..fleet import fleet_stats
            fs = fleet_stats()
            if fs:
                out["fleet"] = fs
        except Exception:   # observability must never fail a request
            pass
        try:
            # flight-recorder occupancy (full traces via /debug/trace)
            from ..obs import default_recorder
            out["trace"] = default_recorder().stats()
        except Exception:   # observability must never fail a request
            pass
        return out

    def flush(self) -> None:
        """Drain-time flush: push buffered metrics records to durable
        storage before the process exits (the kernel ledger needs no
        flush — each verdict is an O_APPEND write of its own)."""
        with self._lock:
            if self._fp is not None:
                try:
                    self._fp.flush()
                    os.fsync(self._fp.fileno())
                except OSError:
                    pass
            else:
                try:
                    sys.stdout.flush()
                except Exception:  # stdout may be closed during interpreter shutdown
                    pass

    def write(self, info: Dict):
        if not self.log_dir and not self.verbose:
            return  # no sink — skip serialization entirely
        line = json.dumps(info, separators=(",", ":"))
        with self._lock:
            if not self.log_dir:
                sys.stdout.write(line + "\n")
                # stdout is block-buffered when piped (containers,
                # collectors): without a flush records sit in the
                # buffer indefinitely on an idle server
                sys.stdout.flush()
                return
            if self._fp is None or self._size > self.max_size:
                self._rotate()
            self._fp.write((line + "\n").encode())
            self._size += len(line) + 1

    def _rotate(self):  # gskylint: holds-lock
        if self._fp is not None:
            self._fp.close()
            self._gzip_old()
        stamp = dt.datetime.now(dt.timezone.utc).strftime("%Y%m%dT%H%M%S")
        self._path = os.path.join(self.log_dir, f"gsky_metrics_{stamp}.log")
        self._fp = open(self._path, "ab")
        self._size = 0

    def _gzip_old(self):
        try:
            with open(self._path, "rb") as src, \
                    gzip.open(self._path + ".gz", "wb") as dst:
                dst.write(src.read())
            os.remove(self._path)
        except OSError:
            pass
        logs = sorted(f for f in os.listdir(self.log_dir)
                      if f.endswith(".log.gz"))
        while len(logs) > self.max_files:
            try:
                os.remove(os.path.join(self.log_dir, logs.pop(0)))
            except OSError:
                break
