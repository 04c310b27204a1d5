"""The worker node: gRPC front door + device executor + decode pool.

Role of the reference's `grpc-server/main.go` (binary ``gsky-rpc``): a
gRPC service exposing ``rpc Process(Task) returns (Result)`` with
operations

- ``worker_info`` — answered inline (`grpc-server/main.go:31-33`),
- ``warp``       — decode in the subprocess pool, then warp on the TPU
                   executor owned by this process (the reference does the
                   whole thing in a GDAL subprocess, `warp.go:82-410`),
- ``drill``      — decode + rasterized-mask reductions on device
                   (`worker/gdalprocess/drill.go`),
- ``extent`` / ``info`` — pure IO, delegated to the pool.

The pool gives crash isolation for codec IO; the OOM monitor SIGKILLs the
fattest child under memory pressure (§5.3 semantics).
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import logging
import math
import os
import signal
import threading
import time
import weakref
from typing import Optional

import numpy as np

from ..device_guard import DeviceGuardError
from ..fleet import DrainController, Draining
from ..obs import current_trace_id, remote_trace, span as obs_span
from ..resilience import faults
from . import gskyrpc_pb2 as pb
from .oom import OOMMonitor
from .pool import PoolFullError, ProcessPool
from .serialize import granule_from_pb, pack_raster, unpack_raster

log = logging.getLogger("gsky.worker.server")

SERVICE = "gskyrpc.GDAL"
METHOD = f"/{SERVICE}/Process"


def _compile_probe():
    """Pre-dispatch compile-counter sample (None when the probe is
    unavailable); paired with :func:`_device_attrs`."""
    try:
        from ..server.prewarm import compile_count
        return compile_count()
    except Exception:
        return None


def _device_attrs(sp, c0) -> None:
    """Device-side dispatch-span attributes: did THIS dispatch trigger a
    fresh XLA compile, and is the fused pallas kernel in play (the race
    verdict ledger's gate) — both cheap probes, both best-effort."""
    if c0 is not None:
        try:
            from ..server.prewarm import compile_count
            sp.set(fresh_compile=compile_count() > c0)
        except Exception:  # compile probe is best-effort telemetry
            pass
    try:
        from ..ops.pallas_tpu import use_pallas
        sp.set(pallas=bool(use_pallas()))
    except Exception:  # pallas gate probe is best-effort telemetry
        pass


class WorkerService:
    """Op dispatch shared by the gRPC wrapper and in-process tests."""

    def __init__(self, pool: Optional[ProcessPool] = None,
                 pool_size: Optional[int] = None,
                 task_timeout: float = 120.0):
        self.pool = pool or ProcessPool(size=pool_size,
                                        task_timeout=task_timeout)
        self.drain = DrainController("worker-node")
        from ..pipeline.executor import WarpExecutor
        self.executor = WarpExecutor()
        # elastic-fleet lifecycle (fleet/elastic.py): preemption state
        # + warm-handoff bookkeeping.  advertise_addr is how THIS node
        # names itself to peers (set by main() / GSKY_ELASTIC_SELF).
        self.advertise_addr: Optional[str] = \
            os.environ.get("GSKY_ELASTIC_SELF") or None
        self._preempt_lock = threading.Lock()
        self.preempted = False
        self.preempt_exit = None        # graceful: unpark main()
        self.preempt_exit_hard = None   # nograce: take the process
        self._handoff = {"entries": 0, "filled": 0, "cold": 0,
                         "active": 0}
        self._warm_cache = (0.0, None)  # (monotonic ts, journal want)
        # a node:preempt fault is delivered through the real protocol,
        # not a bespoke test path; weakref so a dropped in-process
        # service doesn't live on inside the faults module
        ref = weakref.ref(self)

        def _on_preempt(grace_s: float, graceful: bool) -> None:
            svc = ref()
            if svc is not None:
                svc.begin_preemption(grace_s, graceful=graceful)

        faults.set_preempt_handler(_on_preempt)

    # -- ops -----------------------------------------------------------------

    def process(self, task: pb.Task, ctx=None) -> pb.Result:
        """``ctx`` is the gRPC ServicerContext (None from in-process
        callers): its ``x-gsky-trace`` metadata continues the gateway's
        trace here, and the child spans ride back on ``info_json`` for
        ops that leave that channel free."""
        op = task.operation
        header = None
        if ctx is not None:
            try:
                for k, v in ctx.invocation_metadata():
                    if k == "x-gsky-trace":
                        header = v
                        break
            except Exception:
                header = None
        with remote_trace(header, f"worker.{op}") as wtrace:
            res = self._process(task, op, ctx)
            if wtrace is not None and not res.info_json \
                    and op in ("warp", "drill", "extent"):
                try:
                    res.info_json = json.dumps(
                        {"spans": wtrace.span_dicts()})
                except Exception:  # span attachment is advisory telemetry
                    pass
            return res

    def _process(self, task: pb.Task, op: str, ctx=None) -> pb.Result:
        try:
            # node-level chaos (GSKY_FAULTS="node:kill:..." etc.) hits
            # every RPC including health probes — a killed node just dies
            faults.inject("node")
            if op == "worker_info":
                # answered even while draining: this IS the drain
                # handshake the fleet health monitor reads
                return self._worker_info()
            if op == "preempt":
                # control plane, answered inline: the notice must land
                # on a node that is busy (that's the point)
                return self._preempt(task)
            if op == "journal_handoff":
                # likewise: a successor may be receiving while its own
                # admission picture is grim — inheritance is not work
                return self._journal_handoff(task)
            if op == "page_fetch":
                # outside the drain gate deliberately: a draining
                # (preempted) node serving its resident pages to the
                # successor during the grace window IS the warm
                # handoff — refusing it would force a cold restage
                return self._page_fetch(task)
            with self.drain.track():
                if op == "warp":
                    return self._warp(task, ctx)
                if op == "drill":
                    return self._drill(task)
                if op in ("extent", "info", "decode"):
                    return self.pool.submit(task)
                return pb.Result(error=f"unknown operation {op!r}")
        except Draining as e:
            return pb.Result(error=f"draining: {e}")
        except PoolFullError as e:
            return pb.Result(error=f"backpressure: {e}")
        except DeviceGuardError as e:
            # retryable device incident (hang/crash/OOM/corruption or
            # mid-reinit): the "device:" prefix tells the client to fail
            # over to another node without charging this one a breaker
            # penalty — the supervisor is already rebuilding it
            return pb.Result(error=f"device: {e}")
        except Exception as e:
            log.exception("op %s failed trace=%s", op,
                          current_trace_id() or "-")
            return pb.Result(error=f"{type(e).__name__}: {e}")

    def _worker_info(self) -> pb.Result:
        import jax
        r = pb.Result()
        r.worker.pool_size = self.pool.size
        r.worker.queue_cap = self.pool.queue.maxsize
        r.worker.platform = jax.default_backend()
        # WorkerInfo has no spare proto field; the drain handshake rides
        # the free-form info_json channel instead.  The device
        # supervisor's state and the decode pool's crash-loop breaker
        # ride along so the fleet health monitor can mark a node
        # degraded (suspect/reinitializing) or fatal (dead/crash-loop)
        # from the same probe.
        info = dict(self.drain.stats())
        try:
            from .. import device_guard
            info["device"] = device_guard.default_supervisor().stats()
        except Exception:  # device guard absent - health still reports drain stats
            pass
        try:
            info["pool"] = self.pool.stats()
        except Exception:  # pool stats optional in the health probe
            pass
        try:
            from ..pipeline import pages
            if pages._default is not None:
                # page-pool residency rides the same probe so the soak
                # (and operators) can see peer fills vs cold stages
                info["pages"] = pages._default.stats()
        except Exception:  # no page pool in this build
            pass
        try:
            info["elastic"] = self._elastic_info()
        except Exception:  # readiness is advisory; the probe still answers
            pass
        r.info_json = json.dumps(info)
        return r

    # -- elastic lifecycle (fleet/elastic.py; docs/FLEET.md) -----------------

    def _elastic_info(self) -> dict:
        """Readiness + handoff block of the ``worker_info`` probe: the
        autoscaler's join gate reads ``ready``; ``warm_fraction`` is
        the share of the journal's hot set already resident in this
        node's page pool (1.0 when there is nothing to warm)."""
        from ..fleet import elastic
        from ..pipeline import pages
        pool = pages._default
        want = self._journal_want()
        resident = 0
        capacity = 0
        if pool is not None:
            try:
                st = pool.stats()
                resident = int(st.get("resident", 0))
                capacity = int(st.get("capacity", 0))
            except Exception:  # pool mid-teardown: report cold
                pass
        if want <= 0:
            warm = 1.0
        else:
            goal = min(want, capacity) if capacity else want
            warm = min(1.0, resident / max(goal, 1))
        from .. import fabric
        can_warm = fabric.pages_enabled()
        ready = (not can_warm) or warm >= elastic.warm_fraction_target()
        with self._preempt_lock:
            handoff = dict(self._handoff)
            preempted = self.preempted
        return {"ready": bool(ready),
                "warm_fraction": round(warm, 4),
                "prewarm_done": True,
                "preempted": preempted,
                "handoff": handoff}

    def _journal_want(self) -> int:
        """Journal hot-set size, cached a few seconds — the probe fires
        every heartbeat and replay() re-reads the whole file."""
        now = time.monotonic()
        ts, cached = self._warm_cache
        if cached is not None and now - ts < 5.0:
            return cached
        want = 0
        try:
            from ..device_guard import journal
            if journal.journal_enabled():
                want = len(journal.replay())
        except Exception:
            want = 0
        self._warm_cache = (now, want)
        return want

    def _preempt(self, task: pb.Task) -> pb.Result:
        """The preemption notice (autoscaler scale-down, or the soak
        playing the cloud's spot reclaim): start the drain + warm
        journal handoff under the grace deadline.  Idempotent."""
        try:
            doc = json.loads(task.path or "{}")
        except ValueError:
            doc = {}
        grace = doc.get("grace_s")
        from ..fleet import elastic
        grace_s = float(grace) if grace is not None \
            else elastic.preempt_grace_s()
        self.begin_preemption(
            grace_s, graceful=bool(doc.get("graceful", True)),
            successor=doc.get("successor") or None,
            peers=[p for p in (doc.get("peers") or [])
                   if isinstance(p, str)])
        r = pb.Result()
        r.info_json = json.dumps({"ok": True, "grace_s": grace_s})
        return r

    def begin_preemption(self, grace_s: float, graceful: bool = True,
                         successor: Optional[str] = None,
                         peers=()) -> bool:
        """First notice wins; later notices (a retried RPC, a second
        fault roll) are no-ops.  Returns True when this call started
        the preemption."""
        with self._preempt_lock:
            if self.preempted:
                return False
            self.preempted = True
        threading.Thread(
            target=self._run_preemption,
            args=(max(float(grace_s), 0.0), graceful, successor,
                  list(peers)),
            daemon=True, name="gsky-preempt").start()
        return True

    def _run_preemption(self, grace_s, graceful, successor, peers):
        from ..fleet import elastic
        deadline = time.monotonic() + grace_s
        elastic.note_preemption(graceful and grace_s > 0)
        if not graceful or grace_s <= 0:
            # zero grace: flush what a local restart can use, then go
            log.warning("preemption (no grace): flushing journal")
            self._flush_pool_journal()
            hard = self.preempt_exit_hard or self.preempt_exit
            if hard is not None:
                hard()
            return
        log.info("preemption notice: grace=%.1fs successor=%s",
                 grace_s, successor or "-")
        self.drain.start_drain()
        self._ship_journal(successor, peers,
                           timeout=max(min(grace_s * 0.5, 5.0), 0.5))
        left = deadline - time.monotonic() - 0.25
        ok = self.drain.wait_drained(max(left, 0.0))
        if not ok:
            # hard grace deadline: fail over the stragglers explicitly
            # (counted; their callers see a transport failure, which
            # the fleet router retries on another node)
            n = self.drain.abandon_inflight()
            log.warning("preemption grace expired with %d in flight; "
                        "failing them over", n)
        self._flush_pool_journal()
        st = self.drain.stats()
        log.info("preemption drain done: completed=%d refused=%d "
                 "abandoned=%d", st["completed"], st["refused"],
                 st["abandoned"])
        # hold until the grace deadline even when the drain finished
        # early: the successor is still pulling our pages over
        # page_fetch, and the fleet's health probes need at least one
        # beat of the draining state to classify this departure as a
        # preemption rather than a crash
        left = deadline - time.monotonic() - 0.1
        if left > 0:
            time.sleep(left)
        if self.preempt_exit is not None:
            self.preempt_exit()

    def _ship_journal(self, successor, peers, timeout: float) -> None:
        """Ship this node's hot-set journal (heat scores included) to
        its ring successor so the pages can be pulled from our HBM
        while the grace window keeps us alive."""
        from ..fleet import elastic
        try:
            from ..device_guard import journal
            entries = journal.export_hot(elastic.handoff_max())
        except Exception:
            entries = []
        if successor is None and self.advertise_addr:
            successor = elastic.successor_for(self.advertise_addr, peers)
        if not entries or not successor:
            return
        doc = {"v": 1, "source": self.advertise_addr,
               "peers": [p for p in peers if p != successor],
               "entries": [[s, pi, pj, round(score, 3)]
                           for s, pi, pj, score in entries]}
        try:
            elastic.control_rpc(successor, "journal_handoff", doc,
                                timeout=timeout)
            elastic.note_handoff_shipped(len(entries), True)
            log.info("journal handoff: %d entries -> %s",
                     len(entries), successor)
        except Exception:
            elastic.note_handoff_shipped(len(entries), False)
            log.warning("journal handoff to %s failed", successor)

    def _flush_pool_journal(self) -> None:
        """Dump the pool's in-memory heat to the journal (the teardown
        path already writes heat lines) so even an abandoned exit
        leaves a replayable hot set behind."""
        try:
            from ..pipeline import pages
            if pages._default is not None:
                pages._default.teardown()
        except Exception:
            log.exception("journal flush on preemption failed")

    def _journal_handoff(self, task: pb.Task) -> pb.Result:
        """Successor half of the warm handoff: merge the preempted
        node's scored hot set into our journal, then pull the pages
        hottest-first from its still-alive HBM (and the other peers)
        over the page RPC — in the background; the notice must return
        within the sender's grace window."""
        from ..device_guard import journal
        from ..fleet import elastic
        try:
            doc = json.loads(task.path or "{}")
        except ValueError:
            return pb.Result(error="elastic: malformed handoff")
        entries = []
        for e in doc.get("entries") or []:
            try:
                s, pi, pj = int(e[0]), int(e[1]), int(e[2])
                score = float(e[3]) if len(e) > 3 else 1.0
            except (TypeError, ValueError, IndexError):
                continue
            if pi < 0 or pj < 0:      # same guard as merge_scored
                continue
            entries.append((s, pi, pj, score))
        entries = entries[:elastic.handoff_max()]
        journal.merge_scored(entries)
        self._warm_cache = (0.0, None)   # hot set just grew
        source = doc.get("source") or None
        peers = [p for p in (doc.get("peers") or [])
                 if isinstance(p, str) and p != self.advertise_addr]
        with self._preempt_lock:
            self._handoff["entries"] += len(entries)
            self._handoff["active"] += 1
        threading.Thread(
            target=self._handoff_fill, args=(entries, source, peers),
            daemon=True, name="gsky-handoff-fill").start()
        r = pb.Result()
        r.info_json = json.dumps({"accepted": len(entries)})
        return r

    def _handoff_fill(self, entries, source, peers):
        from .. import fabric
        from ..fleet import elastic
        filled = 0
        keys = [(s, pi, pj) for s, pi, pj, _ in entries]
        try:
            if fabric.pages_enabled() and keys:
                from ..fabric import pagerpc
                from ..pipeline.pages import default_page_pool
                pool = default_page_pool()
                missing = [k for k in keys if not pool.has_page(*k)]
                already = len(keys) - len(missing)
                fill_peers = [p for p in ([source] + peers) if p]
                filled = already + pagerpc.fill_from_peers(
                    pool, missing, peers=fill_peers, prefer=source)
        except Exception:
            log.exception("handoff fill failed")
        cold = len(keys) - filled
        elastic.note_handoff_pages("peer", filled)
        elastic.note_handoff_pages("cold", cold)
        with self._preempt_lock:
            self._handoff["filled"] += filled
            self._handoff["cold"] += cold
            self._handoff["active"] -= 1
        log.info("handoff fill: %d/%d pages from peers", filled,
                 len(keys))

    def _page_fetch(self, task: pb.Task) -> pb.Result:
        """Cache-fabric page RPC (docs/FABRIC.md): read requested
        resident pages back to host and ship them content-keyed with
        per-page CRCs.  Refused when the worker page tier is off."""
        from .. import fabric
        if not fabric.pages_enabled():
            return pb.Result(error="fabric: page peering disabled")
        from ..fabric import pagerpc
        from ..pipeline import pages
        res = pb.Result()
        pool = pages._default
        try:
            doc = json.loads(task.path or "{}")
        except ValueError:
            return pb.Result(error="fabric: malformed page_fetch request")
        if pool is None:
            res.info_json = json.dumps(
                {"v": 1, "page_shape": [0, 0], "pages": []})
            return res
        manifest, blob = pagerpc.serve_page_fetch(pool, doc)
        res.raster = blob
        res.info_json = json.dumps(manifest)
        return res

    def _warp(self, task: pb.Task, ctx=None) -> pb.Result:
        from ..geo.crs import parse_crs
        from ..geo.transform import GeoTransform
        from ..pipeline.decode import DecodedWindow

        # the gateway's cancel token propagates here as a gRPC
        # cancellation; ctx.is_active() goes False the moment the
        # client aborts, so poll it at the expensive boundaries and
        # stop decoding/warping for a response nobody will receive
        def _gone() -> bool:
            try:
                return ctx is not None and not ctx.is_active()
            except Exception:
                return False

        d = task.dst
        res = pb.Result()
        if _gone():
            return pb.Result(error="cancelled: client departed")
        g = granule_from_pb(task.granule)
        if g.geo_loc:
            # curvilinear granules have no affine window to decode; warp
            # straight from the device scene cache through the
            # geolocation ctrl-grid path (executor._geoloc_ctrl).  This
            # read happens in-process rather than through the decode
            # pool: the scene must land in THIS process's HBM cache
            # anyway, and the NetCDF read path here is Python/h5py (the
            # crash-prone native codec is the TIFF path) — the pool's
            # isolation buys little for the cost of a second full-scene
            # copy over IPC.
            dst_gt = GeoTransform.from_gdal(list(d.geo_transform))
            c0 = _compile_probe()
            with obs_span("worker.dispatch", curvilinear=True,
                          shape=[d.height, d.width]) as wsp:
                sc = self.executor.warp_mosaic_scenes(
                    [g], [0], [1.0], dst_gt, parse_crs(d.srs), d.height,
                    d.width, 1, d.resample or "near")
            _device_attrs(wsp, c0)
            if sc is None:
                # parity with the local path's loud degradation: a
                # blank remote tile must not look like absent data
                log.warning("curvilinear granule %s uncacheable; "
                            "warp RPC returns empty trace=%s", g.path,
                            current_trace_id() or "-")
                return res
            canv, vals = sc
            with obs_span("worker.readback") as rb:
                from .. import device_guard
                a = device_guard.guarded_readback(
                    "worker.readback", lambda: np.asarray(canv[0]))
                v = np.asarray(vals[0])
                rb.set(bytes=int(a.nbytes + v.nbytes))
            pack_raster(res, a, v)
            b = dst_gt.bbox(d.width, d.height)
            res.bbox.extend([b.xmin, b.ymin, b.xmax, b.ymax])
            res.dtype = "Float32"
            res.metrics.bytes_read = int(
                np.asarray(canv[0]).nbytes)
            return res
        decode = pb.Task()
        decode.CopyFrom(task)
        decode.operation = "decode"
        with obs_span("worker.decode") as dsp:
            dres = self.pool.submit(decode)
            dsp.set(bytes_read=int(dres.metrics.bytes_read))
        if dres.error:
            return dres
        if _gone():
            # decoded bytes for a departed client: stop before the
            # device dispatch, the costliest remaining step
            return pb.Result(error="cancelled: client departed")
        win = unpack_raster(dres)
        if win is None:  # granule doesn't touch the tile -> empty result
            return res
        data, valid = win
        wdw = DecodedWindow(
            granule=g, data=data, valid=valid,
            window_gt=GeoTransform.from_gdal(list(dres.window_gt)),
            src_crs=parse_crs(dres.src_srs))
        dst_gt = GeoTransform.from_gdal(list(d.geo_transform))
        c0 = _compile_probe()
        with obs_span("worker.dispatch",
                      shape=[d.height, d.width]) as wsp:
            out = self.executor.warp_all([wdw], dst_gt, parse_crs(d.srs),
                                         d.height, d.width,
                                         d.resample or "near")[0]
        _device_attrs(wsp, c0)
        if out is None:
            return res
        with obs_span("worker.readback") as rb:
            from .. import device_guard
            a = device_guard.guarded_readback(
                "worker.readback", lambda: np.asarray(out[0]))
            v = np.asarray(out[1])
            rb.set(bytes=int(a.nbytes + v.nbytes))
        pack_raster(res, a, v)
        b = dst_gt.bbox(d.width, d.height)
        res.bbox.extend([b.xmin, b.ymin, b.xmax, b.ymax])
        res.dtype = "Float32"
        res.metrics.CopyFrom(dres.metrics)
        return res

    def _drill(self, task: pb.Task) -> pb.Result:
        from ..geo import geometry as geom
        from ..index.client import Dataset
        from ..pipeline.drill import _drill_file
        from ..pipeline.types import GeoDrillRequest

        g = task.granule
        sp = task.drill
        ds = Dataset(
            file_path=g.path, ds_name=g.ds_name, namespace=g.namespace,
            array_type=g.array_type or "Float32", srs=g.srs,
            geo_transform=list(g.geo_transform),
            timestamps=[], timestamps_iso=[], polygon="",
            nodata=g.nodata if g.has_nodata else 0.0)
        req = GeoDrillRequest(
            collection="", bands=[g.namespace or "b1"],
            geometry_wkt=sp.geometry_wkt,
            band_strides=max(int(sp.stride), 1),
            deciles=9 if sp.deciles else 0,
            pixel_count=sp.pixel_count,
            clip_lower=sp.clip_lower if sp.has_clip else -3.0e38,
            clip_upper=sp.clip_upper if sp.has_clip else 3.0e38)
        sel = list(sp.time_indices) or [0]
        # sp.vrt_xml arrives RENDERED (the client renders per granule,
        # `drill_indexer.go:340`); drill through the VRT when present
        out = _drill_file(ds, sel, geom.from_wkt(sp.geometry_wkt), req,
                          vrt_xml=sp.vrt_xml or None)
        res = pb.Result()
        if out is None:
            return res
        vals, counts, dec = out
        res.series.means.extend(float(v) if math.isfinite(v) else 0.0
                                for v in np.asarray(vals).ravel())
        res.series.counts.extend(int(c) for c in np.asarray(counts).ravel())
        res.series.deciles.extend(float(v) for v in np.asarray(dec).ravel())
        return res

    def close(self):
        self.pool.close()


# ---------------------------------------------------------------------------
# gRPC wiring (generic handler; stubs aren't generated without grpcio-tools)
# ---------------------------------------------------------------------------


def make_grpc_server(service: WorkerService, address: str = "[::]:11429",
                     max_workers: int = 32, max_msg: int = 64 << 20):
    import grpc

    handler = grpc.method_handlers_generic_handler(SERVICE, {
        "Process": grpc.unary_unary_rpc_method_handler(
            lambda req, ctx: service.process(req, ctx),
            request_deserializer=pb.Task.FromString,
            response_serializer=pb.Result.SerializeToString),
    })
    server = grpc.server(
        cf.ThreadPoolExecutor(max_workers=max_workers),
        options=[("grpc.max_receive_message_length", max_msg),
                 ("grpc.max_send_message_length", max_msg),
                 ("grpc.so_reuseport", 1)])
    server.add_generic_rpc_handlers((handler,))
    server.add_insecure_port(address)
    return server


def main(argv=None):
    ap = argparse.ArgumentParser(prog="gsky-rpc")
    ap.add_argument("-p", "--port", type=int, default=11429)
    ap.add_argument("-host", default="[::]",
                    help="listen address ([::] needs a dual-stack host; "
                         "use 127.0.0.1 on IPv4-only ones)")
    ap.add_argument("-n", "--pool", type=int, default=0,
                    help="decode pool size (default: cpu count)")
    ap.add_argument("-max_tasks", type=int, default=20000)
    ap.add_argument("-timeout", type=float, default=120.0)
    ap.add_argument("-oom_threshold", type=int, default=1536,
                    help="MemAvailable floor in MiB (0 disables)")
    a = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    # in the split topology this process holds the chip (the gateway
    # is started with JAX_PLATFORMS=cpu); no TPU raises PlatformError
    from ..device import ensure_platform
    plat = ensure_platform()
    log.info("gsky-rpc: platform %s (%s x%d), compile cache %s",
             plat["platform"], plat["device_kind"], plat["device_count"],
             plat["cache_dir"])

    svc = WorkerService(pool_size=a.pool or None, task_timeout=a.timeout)
    if not svc.advertise_addr:
        # how peers reach us for the page RPC / journal handoff; wildcard
        # listen addresses advertise loopback (single-host fleets)
        host = "127.0.0.1" if a.host in ("[::]", "0.0.0.0") else a.host
        svc.advertise_addr = f"{host}:{a.port}"
    monitor = None
    if a.oom_threshold:
        def _oom_killed(pid: int) -> None:
            # a defensive kill IS a host-memory OOM incident: count it
            # on the supervisor and shed node-wide pressure so the next
            # victim isn't immediately re-grown
            from .. import device_guard
            from ..resilience.pressure import default_monitor
            device_guard.default_supervisor().record_oom(
                "worker.oom", RuntimeError(f"killed decode pid {pid}"))
            default_monitor().escalate()

        monitor = OOMMonitor(svc.pool.child_pids,
                             threshold_bytes=a.oom_threshold << 20,
                             on_kill=_oom_killed)
        monitor.start()
    server = make_grpc_server(svc, f"{a.host}:{a.port}")
    server.start()
    log.info("gsky-rpc listening on %s:%d (pool=%d)",
             a.host, a.port, svc.pool.size)

    try:
        from .. import fabric
        if fabric.pages_enabled() and fabric.page_peer_addrs():
            # cache-fabric warm boot (docs/FABRIC.md): pull the
            # journal's hot set from ring-adjacent peers instead of
            # cold-staging it request by request.  Backgrounded: the
            # node serves (and cold-stages) normally while it warms.
            from ..pipeline.pages import default_page_pool

            def _warm_boot():
                try:
                    n = default_page_pool().rehydrate()
                    log.info("fabric: warm boot restored %d pages", n)
                except Exception:
                    log.exception("fabric: warm boot failed")

            threading.Thread(target=_warm_boot, daemon=True,
                             name="gsky-fabric-warm").start()
    except Exception:  # fabric optional; a worker must boot without it
        log.exception("fabric: warm boot setup failed")

    # graceful drain: SIGTERM/SIGINT closes the accept gate (new ops
    # answer "draining:", worker_info keeps answering with the draining
    # flag so the fleet deregisters us), in-flight ops run to completion,
    # then the server exits.  A supervisor that can't wait will SIGKILL
    # after its own grace period; GSKY_DRAIN_TIMEOUT_S bounds ours.
    stop = threading.Event()
    # preemption notices (the `preempt` RPC or a node:preempt fault)
    # exit through the same park-loop as a signal drain; a no-grace
    # preemption takes the process the way the reclaim would
    svc.preempt_exit = stop.set
    svc.preempt_exit_hard = lambda: os._exit(1)

    def _drain():
        svc.drain.start_drain()
        timeout = float(os.environ.get("GSKY_DRAIN_TIMEOUT_S", "30") or 30)
        ok = svc.drain.wait_drained(timeout)
        if not ok:
            # grace deadline: fail over the stragglers explicitly
            # (counted) instead of silent in-flight loss, and flush
            # the page journal so the restart replays warm
            n = svc.drain.abandon_inflight()
            log.warning("drain timed out with %d in flight; "
                        "failing them over", n)
            svc._flush_pool_journal()
        st = svc.drain.stats()
        log.info("drain %s: completed=%d refused=%d inflight=%d "
                 "abandoned=%d",
                 "complete" if ok else "TIMED OUT",
                 st["completed"], st["refused"], st["inflight"],
                 st["abandoned"])
        stop.set()

    def _on_term(signum, frame):
        log.info("signal %d: draining worker node", signum)
        threading.Thread(target=_drain, daemon=True,
                         name="gsky-drain").start()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    try:
        # park until a signal-triggered drain completes; the gRPC
        # server keeps serving from its own threads meanwhile
        while not stop.wait(0.5):
            pass
    finally:
        server.stop(grace=5).wait()
        if monitor:
            monitor.stop()
        svc.close()


if __name__ == "__main__":
    main()
