#!/usr/bin/env python
"""Soak the in-process OWS server.

Two scenarios:

``--scenario churn`` (default): sustained GetMap load across a
DISTINCT-tile sweep (cache churn, not cache hits) while sampling the
process RSS and the /debug cache sizes — the leak/bounds check a
long-lived tile server needs and the acceptance suite's fixed grid
can't give.  Runs with the serving gateway disabled so the RSS bound
measures the pipeline tiers, not the response cache filling.

    JAX_PLATFORMS=cpu python tools/soak.py [--seconds 120] [--conc 8]

Exit 0 when (a) every request succeeded, (b) RSS growth over the
steady-state phase (after the first quarter, which pays compiles +
cache fills) is under --max-rss-growth-mb, and (c) the /debug cache
sizes stay at or below their configured LRU bounds.

``--scenario hot``: the public-tile-server access pattern — a FIXED
tile grid with Zipf-distributed popularity — driven against a baseline
server (gateway=None) and then a gateway-fronted one, reporting
client-side p50/p99 per phase plus the gateway's response-cache hit
rate, singleflight joins and admission sheds from /debug.  Also runs
the tracing overhead guard — hot-cache p50 with tracing on (default
sampling) must stay within --max-trace-overhead percent of a
GSKY_TRACE=0 phase — asserts /metrics passes the strict exposition
parser, and prints the slowest request's critical path.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario hot --seconds 60

``--scenario wcs``: repeated large GetCoverage exports against a
running server — the staged export engine (pipeline/export.py) under
sustained load.  Asserts every export succeeds, RSS stays bounded, and
/debug's ``export_pipeline`` block reports the expected export count
with non-zero per-stage timings.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario wcs --seconds 60

``--scenario chaos``: mixed GetMap/GetCoverage load with deterministic
injected faults (default 20% MAS + worker + decode errors, see
``--faults``) against a gateway-fronted server.  Every response must be
a clean 2xx, a degraded-but-labelled 2xx (``X-GSKY-Degraded``), or a
well-formed OGC ServiceException (503/504 + ``se_xml`` body + honest
``Retry-After``); a bare HTTP 500 — an unhandled internal error — or a
dropped connection fails the soak.  Also requires /debug's
``resilience`` block to show the machinery actually firing: non-zero
retry, injected-fault, breaker-failure and degraded-response counters.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario chaos --seconds 30

``--scenario burst``: the deploy-then-traffic-spike pattern the staged
GetMap path (pipeline/tile_stages.py) and the shape-bucket prewarm
(server/prewarm.py) exist for.  Prewarms the layer programs, takes one
warm lap, then storms the server with concurrent distinct-tile GetMaps
and requires (a) every response is a clean 200 PNG, (b) ZERO fresh XLA
compiles during the burst (the `install_compile_probe` counter), and
(c) /debug's ``tile_stages`` block shows the stage overlap actually
engaged: gate entries, encode-pool throughput, and a >1 queue
high-water on at least one stage.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario burst --seconds 30

``--scenario fleet``: multi-process fleet fault tolerance (the
gsky_tpu/fleet subsystem, see docs/FLEET.md).  Boots three REAL
``gsky_tpu.worker.server`` subprocesses, points a layer's
``worker_nodes`` at them, and drives a fixed tile grid through the
consistent-hash router in three phases: baseline (per-tile-key
locality under a healthy fleet), kill (SIGKILL one node mid-load —
every response must stay a clean 2xx / labelled-degraded / OGC error,
never a bare 5xx or dropped connection), and revive (restart the node,
wait for the phi-accrual detector to re-admit it, and require the
locality rate to recover to >= 90% of the pre-kill baseline).  A coda
spawns one deliberately slow node (``GSKY_FAULTS=node:slow``) and
shows hedged keyed dispatch beating unhedged p99 within the hedge
budget.  Also requires at least one recorded trace STITCHED across the
process boundary (worker-process spans under the gateway's trace id),
a strict /metrics parse including the worker-RPC histogram, and prints
the slowest request's critical-path waterfall (tools/trace_view.py).

    JAX_PLATFORMS=cpu python tools/soak.py --scenario fleet --seconds 25

``--scenario overload``: overload survival (docs/RESILIENCE.md
"Overload & brownout").  Drives the adaptive-admission gateway through
five phases: a serial warm lap that sets the AIMD latency baseline, a
two-tenant storm (premium + bulk ``X-API-Key``) at concurrency well
past the WMS limit, a client-disconnect volley whose aborted requests
must hand their permits back (end-to-end cancellation), a forced
memory-pressure brownout (degraded-but-labelled 200s, clamped
effective limit, page staging declined), and a recovery lap that must
come back clean.  Passes only when zero responses are bare 5xx or
dropped connections, every admission shed is a 503 carrying
``Retry-After``, the AIMD controller made at least one limit
adjustment, at least one cancellation released capacity, and /metrics
exposes the overload families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario overload --seconds 20

``--scenario ingest``: cloud-native ingest (docs/INGEST.md).  A
deterministic pan+zoom walk — two west-east tile rows stepped one tile
at a time, then two zoom-in halvings — replayed against three fresh
servers: a baseline with ingest off (``GSKY_INGEST=0``, whole-scene
decode), a ranged leg with window routing on (chunk-granular reads,
prefetch off) and a prefetch leg (planner on, residency warming).
Passes only when every response across all legs is a 200 PNG (zero
bare 5xx), the ranged leg reads strictly fewer bytes than the
baseline, the planner's hit rate on the walk is >= 50%, and /metrics
exposes the ingest families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario ingest --seconds 20

``--scenario devicechaos``: device supervision & warm recovery
(docs/RESILIENCE.md "Device failures").  Warms a hot tile set so the
page pool holds a known working set (GSKY_PALLAS=interpret engages the
paged pipeline on CPU), then runs four incident phases — crash, hang,
OOM and readback corruption — injected at the real dispatch/readback
sites via ``device:*`` faults.  Per phase every response must be a
clean outcome (2xx, labelled degraded 2xx, or an OGC-XML refusal with
Retry-After); a bare 500 or dropped connection fails the soak.  After
each phase the device must return to ``healthy`` within the recovery
budget (tiny GSKY_DEVICE_REINIT_BACKOFF), and the rebuilt pool must
rehydrate at least half of the pre-incident hot pages from the
residency journal.  /metrics must expose the device families through
the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario devicechaos --seconds 20

``--scenario wave``: wave-level device serving (docs/PERF.md "Wave
dispatch").  ``GSKY_PALLAS=interpret`` engages the paged+wave pipeline
on CPU; a mixed storm of concurrent GetMaps (single-product fused byte
path) and WPS geometryDrill reductions must COALESCE: the wave
scheduler has to show device dispatches well under request count
(>= 3x amortisation) with at least one multi-entry wave, every
response must be a clean 200 (zero bare 5xx), a client-disconnect
volley must drop at least one entry from its wave (the ``cancelled``
counter) while the surviving companions complete, the page pool must
end with ZERO pinned pages, and /metrics must expose the wave
families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario wave --seconds 20

``--scenario mesh``: multi-chip sharded wave dispatch (docs/MESH.md).
Forces 8 virtual host devices on CPU, enables GSKY_MESH=1 with an
operator rule routing scored waves to the ``x`` layout, then runs a
mixed GetMap + WPS-drill + WCS-export storm.  Pass criteria: at least
one wave dispatched under EVERY configured layout (granule byte
waves, time-sharded drills, x-sharded export blocks — all spanning
the full mesh), an injected dispatcher failure leg where every
request still answers 200 via the per-entry failover (zero bare 5xx,
``fallbacks`` counter moves), a GSKY_MESH=0 flip that returns the
SAME PNG bytes for the same tile (escape-hatch byte identity), the
page pool ending with zero pinned pages, and /metrics exposing the
``gsky_mesh_*`` families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario mesh --seconds 20

``--scenario plan``: dataflow autoplanner (docs/PERF.md "Dataflow
planning").  ``GSKY_PALLAS=interpret`` engages the paged+wave pipeline
on CPU; an adjacent-tile GetMap pan-walk storm (neighbouring bboxes
whose gather windows overlap) plus a streamed WCS-export minority must
give the planner real merge opportunities.  Pass criteria: at least
one shared-halo superblock with a gather-dedup ratio > 0 (the planner
saved HBM gather bytes vs independent windows), a concurrent
adjacent-tile volley re-fetched under ``GSKY_PLAN=0`` returning the
SAME PNG bytes (escape-hatch byte identity), every response a clean
200, the page pool ending with ZERO pinned pages, and /metrics
exposing the ``gsky_plan_*`` families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario plan --seconds 20

``--scenario fabric``: cache fabric (docs/FABRIC.md).  Two gateway
replicas (each with a private response cache, joined by the replay
ring) in front of three worker-node processes peered for page RPC
over a shared pool journal.  A Zipf tile storm alternates gateways;
then one gateway "dies" and is replaced by a cold replica, which must
serve at least half of the peer-owned hot set by replaying the
survivor's bytes (``X-Gsky-Cache: peer``) instead of re-rendering;
one worker is SIGKILLed and respawned, and its warm-boot refill must
come from page-peer RPC rather than cold staging; a ``GSKY_FABRIC=0``
leg must be byte-identical to a fabric-less server.  Zero bare 5xx
throughout, and /metrics must round-trip the strict parser with the
fabric families present::

    JAX_PLATFORMS=cpu python tools/soak.py --scenario fabric --seconds 20

``--scenario occupancy``: continuous device occupancy (docs/PERF.md
"Continuous device occupancy").  The same sustained mixed GetMap +
WPS-drill storm is driven twice: first against the synchronous wave
ticker (``GSKY_WAVE_PIPELINE=0`` — planning, param stacking and
uploads all sit on the dispatch critical path), then against the
two-stage pipeline (assembly stages wave N+1 into the donated input
ring while wave N executes).  Pass criteria: zero bare 5xx in both
phases, the pipelined p99 host-side inter-wave dispatch gap below the
synchronous baseline (or already under the 2 ms back-to-back floor),
at least one wave staged ahead of dispatch, the page pool ending with
ZERO pinned pages, and /metrics exposing the ``gsky_wave_gap_ms`` /
``gsky_wave_staged_total`` families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario occupancy --seconds 20

``--scenario elastic``: elastic fleet (docs/FLEET.md "Elastic
fleet").  A two-node preemptible fleet behind the autoscaler control
loop (local-subprocess provider): a load ramp that doubles traffic
must push the smoothed demand signal past the scale-up threshold and
launch capacity that joins the ring only after the warm-readiness
probe; two nodes are then preempted mid-ramp with a short grace
window, and each must drain, ship its scored page-residency journal
to its ring successor, and have at least half of the inherited hot
set refilled from peer HBM over page RPC rather than cold-staged;
the floor is refilled without cooldown; a quiet trickle phase must
produce at least one scale-down.  Pass criteria: zero bare 5xx or
dropped connections across every phase, post-preemption p99 within
budget, >= 1 scale-up and >= 1 scale-down decision, a readiness-gated
join observed, the handoff peer-refill ratio >= 50%, a
``GSKY_ELASTIC=0`` leg whose fixed-fleet responses are byte-identical
with no elastic families in /metrics and no /debug block, and a
strict /metrics parse with the elastic families present::

    JAX_PLATFORMS=cpu python tools/soak.py --scenario elastic --seconds 30

``--scenario algebra``: fused band algebra (docs/KERNELS.md
"Expression epilogue").  ``GSKY_PALLAS=interpret`` engages the
paged+wave pipeline on CPU with ``GSKY_EXPR_FUSE`` on; a storm
rotates across WMS styles carrying 12 single-entry ``name = expr``
band-algebra sources (10 structurally DISTINCT shapes — two styles
are constant/variable-renamed twins of others) plus a WPS drill
minority whose data source also carries expressions.  Pass criteria:
compiles stay bounded (the expression compile cache absorbs the
storm: misses <= the distinct source count, hits dominate) and the
fused epilogue shares programs by structural fingerprint (distinct
fused programs <= distinct structures, so the twins provably share),
a concurrent volley re-fetched under ``GSKY_EXPR_FUSE=0`` returns
the SAME PNG bytes (escape-hatch byte identity) while actually
taking the unfused leg, every response is a clean 200 (zero bare
5xx), the page pool ends with ZERO pinned pages, and /metrics
exposes the ``gsky_expr_*`` families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario algebra --seconds 20

``--scenario animation``: temporal wave serving (docs/PERF.md
"Temporal waves").  ``GSKY_PALLAS=interpret`` engages the paged+wave
pipeline on CPU; a TIME-range GetMap storm requests ``image/apng``
animations (plus a ``video/mp4`` stub minority) whose N frames must
render as lanes of shared wave dispatches — one index pass per
sequence, frames amortised over waves — while a client-disconnect
volley aborts sequences mid-container.  Pass criteria: every storm
response is a clean 200 APNG with the full frame count (zero bare
5xx), the serial warm sequence amortises its frames over at most half
as many wave dispatches, at least one sequence records a
cancellation, the page pool ends with ZERO pinned pages, and /metrics
exposes the ``gsky_anim_*`` families through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario animation --seconds 20

``--scenario dap4``: streamed DAP4 serving (docs/PERF.md "Temporal
waves", DAP4 leg).  Concurrent ``dap4.ce`` constraint-expression
subsets (rotating bands, x-clamps and time filters) against a tiled
coverage frame must take the streamed-spool path: responses arrive
chunked off the export spool with bounded peak buffering instead of
materialising the coverage in RAM.  Pass criteria: every response is
a clean 200 DAP4 body (zero bare 5xx), a ``GSKY_DAP_STREAM=0`` warm
re-fetch is byte-identical (escape hatch), the ``temporal`` debug
block shows streams with a peak rechunk buffer under 2x the DAP4
chunk ceiling, steady-state RSS growth (after the first storm
quarter, which pays compiles and cache fills) stays under
``--max-rss-growth-mb``, and /metrics exposes
``gsky_dap_streamed_bytes_total`` through the strict parser.

    JAX_PLATFORMS=cpu python tools/soak.py --scenario dap4 --seconds 20
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import itertools
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools.sample_archive import (  # noqa: E402
    N_SCENES, SCENE_SIZE, build_archive)


def rss_mb() -> float:
    with open("/proc/self/status") as fp:
        for line in fp:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def check_metrics(host: str,
                  require=("gsky_requests_total", "gsky_request_seconds",
                           "gsky_stage_seconds")) -> dict:
    """Scrape /metrics and run it through the STRICT exposition parser
    (shared with the unit tests): a malformed line or a broken
    histogram invariant raises, a missing family fails the soak."""
    from gsky_tpu.obs.prom import parse_exposition
    with urllib.request.urlopen(f"http://{host}/metrics",
                                timeout=30) as r:
        fams = parse_exposition(r.read().decode())
    return {"families": len(fams),
            "missing": [f for f in require if f not in fams]}


def slowest_trace_report(host: str):
    """Waterfall + critical-path breakdown of the slowest recorded
    request (the flight recorder's reservoir), printed to stdout before
    the JSON result line.  Returns a JSON-able summary (None when the
    recorder has nothing — tracing off or no traffic)."""
    import trace_view as tv
    try:
        with urllib.request.urlopen(
                f"http://{host}/debug/trace?slowest=1", timeout=30) as r:
            trace = json.loads(r.read())
    except Exception:
        return None
    print(tv.render(trace), flush=True)
    return {"trace_id": trace.get("trace_id"),
            "dur_ms": round((trace.get("dur_s") or 0.0) * 1e3, 1),
            "processes": sorted({s.get("process") or "?"
                                 for s in trace.get("spans", [])}),
            "critical_path": tv.critical_breakdown(trace)}


def main(argv=None):
    # GSKY_TSAN=1 (CI wave leg): patch threading.Lock/RLock BEFORE the
    # in-process server builds any lock, run the scenario under lockset
    # tracking, and fail the soak on any race report — the dynamic
    # complement to gskylint's static GSKY-LOCK check.
    from gsky_tpu.obs import tsan
    tsan.maybe_install()
    rc = _run(argv)
    if tsan.installed():
        stats = tsan.tsan_stats()
        print(f"tsan: tracked_vars={stats['tracked_vars']} "
              f"races={stats['races']}", flush=True)
        if tsan.race_count():
            print(tsan.report(), file=sys.stderr)
            print("SOAK FAILED (tsan races)", flush=True)
            return 1
    return rc


def _run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--conc", type=int, default=8)
    ap.add_argument("--max-rss-growth-mb", type=float, default=256.0)
    ap.add_argument("--scenario",
                    choices=("churn", "hot", "wcs", "chaos", "burst",
                             "fleet", "overload", "ingest",
                             "devicechaos", "wave", "mesh", "plan",
                             "fabric", "occupancy", "elastic",
                             "algebra", "animation", "dap4"),
                    default="churn")
    ap.add_argument("--zipf", type=float, default=1.2,
                    help="hot scenario: Zipf exponent of tile popularity")
    ap.add_argument("--max-trace-overhead", type=float, default=2.0,
                    help="hot scenario: max hot-cache p50 regression "
                         "(percent) with tracing on vs GSKY_TRACE=0")
    ap.add_argument("--faults",
                    default="mas:error:0.2,worker:error:0.2,"
                            "decode:error:0.2",
                    help="chaos scenario: GSKY_FAULTS-style spec")
    ap.add_argument("--fault-seed", type=int, default=11)
    args = ap.parse_args(argv)

    if args.scenario == "mesh":
        # the mesh needs >1 chip BEFORE jax initialises: on CPU force
        # the virtual host devices (a no-op on real multi-chip parts)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    from gsky_tpu.device import ensure_platform
    ensure_platform()

    import asyncio
    import tempfile
    import threading

    import numpy as np

    from gsky_tpu.geo.crs import EPSG4326, EPSG3857
    from gsky_tpu.geo.transform import BBox, transform_bbox
    from gsky_tpu.index import MASClient
    from gsky_tpu.server.config import ConfigWatcher
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    root = tempfile.mkdtemp(prefix="gsky_soak_")
    store, utm, paths = build_archive(root)
    mas_client = MASClient(store)
    conf_dir = os.path.join(root, "conf")
    os.makedirs(conf_dir)
    # algebra twin: single-entry `name = expr` styles over two product
    # namespaces — the fused expression epilogue (GSKY_EXPR_FUSE).
    # Ten structurally distinct shapes across twelve sources: nd_rev
    # and mask2 are twins of nd / mask1 (renamed variables, shifted
    # constant) and must SHARE a fused program — the fingerprint, not
    # the source text, keys the compile
    p0, p1 = "LC08_20200110_T1", "LC08_20200111_T1"
    algebra_styles = [
        {"name": name, "rgb_products": [src]} for name, src in (
            ("nd_rev", f"nd_rev = ({p1} - {p0}) / ({p1} + {p0})"),
            ("mask1", f"mask1 = {p0} > 1200 ? {p1} : {p0}"),
            ("mask2", f"mask2 = {p0} > 1800 ? {p1} : {p0}"),
            ("blend", f"blend = 0.5 * {p0} + 0.5 * {p1}"),
            ("root", f"root = sqrt({p0} * {p1})"),
            ("dif", f"dif = abs({p0} - {p1})"),
            ("logr", f"logr = log({p0} + 1000)"),
            ("gate", f"gate = {p0} > 500 && {p1} > 500 "
                     f"? {p0} + {p1} : 0"),
            ("quant", f"quant = floor({p0} / 16) * 16"),
            ("clip", f"clip = min(max({p0}, 400), 2600)"),
            ("curve", f"curve = pow({p0} / 3000, 2) * 3000"),
        )]
    # dap twin needs a coverage frame (default bbox + size): dap4.ce
    # has no bbox/size params, so dap_to_wcs reads them off the layer,
    # and a tile cap below the frame splits the export into >1 staged
    # tile -- the precondition for the streamed-spool DAP4 leg
    dap_span = SCENE_SIZE * 30.0
    dap_core = BBox(590000.0, 6105000.0 - dap_span * 1.3,
                    590000.0 + dap_span * 1.3, 6105000.0)
    dap_ll = transform_bbox(dap_core, utm, EPSG4326)
    with open(os.path.join(conf_dir, "config.json"), "w") as fp:
        json.dump({
            "service_config": {"ows_hostname": "", "mas_address": ""},
            "layers": [{
                "name": "landsat", "title": "soak",
                "data_source": root,
                "rgb_products": [f"LC08_20200{110 + k}_T1"
                                 for k in range(N_SCENES)],
                "time_generator": "mas",
                "wcs_max_width": 4096, "wcs_max_height": 4096,
                "wcs_max_tile_width": 256,
                "wcs_max_tile_height": 256},
                # chaos twin: a short response-cache TTL so entries
                # expire DURING the run and the stale-on-error path
                # (gateway serving an expired tile while a backend is
                # down) actually executes, not just in theory
                {
                "name": "landsat_chaos", "title": "chaos soak",
                "data_source": root,
                "rgb_products": [f"LC08_20200{110 + k}_T1"
                                 for k in range(N_SCENES)],
                "time_generator": "mas",
                "cache_max_age": 3,
                "wcs_max_width": 4096, "wcs_max_height": 4096,
                "wcs_max_tile_width": 256,
                "wcs_max_tile_height": 256},
                # burst twin: a SINGLE product, so the storm also
                # exercises the n_exprs=1 fused composite program, not
                # just the 3-expr RGB one the other layers dispatch
                {
                "name": "landsat_burst", "title": "burst soak",
                "data_source": root,
                "rgb_products": ["LC08_20200110_T1"],
                "time_generator": "mas",
                "wcs_max_width": 4096, "wcs_max_height": 4096,
                "wcs_max_tile_width": 256,
                "wcs_max_tile_height": 256},
                # dap twin: coverage frame for the dap4.ce endpoint,
                # tiled 2x2 so the streamed export engine engages
                # (stream_dap requires len(tiles) > 1)
                {
                "name": "landsat_dap", "title": "dap soak",
                "data_source": root,
                "rgb_products": [f"LC08_20200{110 + k}_T1"
                                 for k in range(N_SCENES)],
                "time_generator": "mas",
                "default_geo_bbox": [dap_ll.xmin, dap_ll.ymin,
                                     dap_ll.xmax, dap_ll.ymax],
                "default_geo_size": [256, 256],
                "wcs_max_width": 4096, "wcs_max_height": 4096,
                "wcs_max_tile_width": 128,
                "wcs_max_tile_height": 128},
                {
                "name": "landsat_algebra", "title": "algebra soak",
                "data_source": root,
                "rgb_products": [f"nd = ({p0} - {p1}) / ({p0} + {p1})"],
                "time_generator": "mas",
                "styles": algebra_styles}],
            # wave scenario: WPS geometryDrill gives the storm a second
            # result KIND, so drill reductions ride the same scheduler
            # ticks as the tile renders (one stacked dispatch per kind)
            "processes": [{
                "identifier": "geometryDrill",
                "title": "Geometry drill",
                "max_area": 10000,
                "data_sources": [{
                    "data_source": root,
                    "rgb_products": [f"LC08_20200{110 + k}_T1"
                                     for k in range(N_SCENES)]}],
                "approx": False},
                # algebra scenario: the drill minority evaluates band
                # expressions per date, so the compile cache absorbs
                # WPS traffic too, not just the styled GetMaps
                {
                "identifier": "algebraDrill",
                "title": "Band-algebra drill",
                "max_area": 10000,
                "data_sources": [{
                    "data_source": root,
                    "rgb_products": [
                        f"nd = ({p0} - {p1}) / ({p0} + {p1})",
                        f"dif = abs({p0} - {p1})"]}],
                "approx": False}],
        }, fp)
    watcher = ConfigWatcher(conf_dir, mas_factory=lambda a: mas_client,
                            install_signal=False)

    def boot(server) -> str:
        """Serve on a private loop/thread; return host:port."""
        loop = asyncio.new_event_loop()
        started = threading.Event()
        host_holder = {}

        def run_server():
            asyncio.set_event_loop(loop)
            from aiohttp import web

            async def _boot():
                # mirror production (server/main.py): without handler
                # cancellation a dropped client never fires the
                # request's cancel token and permits leak for the
                # duration of the render
                runner = web.AppRunner(server.app(),
                                       handler_cancellation=True)
                await runner.setup()
                site = web.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                host_holder["host"] = "127.0.0.1:%d" % \
                    site._server.sockets[0].getsockname()[1]
                started.set()
            loop.run_until_complete(_boot())
            loop.run_forever()

        threading.Thread(target=run_server, daemon=True).start()
        started.wait(30)
        return host_holder["host"]

    span = SCENE_SIZE * 30.0
    core = BBox(590000.0, 6105000.0 - span * 1.3,
                590000.0 + span * 1.3, 6105000.0)
    merc = transform_bbox(transform_bbox(core, utm, EPSG4326),
                          EPSG4326, EPSG3857)

    if args.scenario == "hot":
        return run_hot(args, watcher, mas_client, merc, boot)
    if args.scenario == "wcs":
        return run_wcs(args, watcher, mas_client, merc, boot)
    if args.scenario == "chaos":
        return run_chaos(args, watcher, mas_client, merc, boot)
    if args.scenario == "burst":
        return run_burst(args, watcher, mas_client, merc, boot)
    if args.scenario == "fleet":
        return run_fleet(args, watcher, mas_client, merc, boot)
    if args.scenario == "overload":
        return run_overload(args, watcher, mas_client, merc, boot)
    if args.scenario == "ingest":
        return run_ingest(args, watcher, mas_client, merc, boot)
    if args.scenario == "devicechaos":
        return run_devicechaos(args, watcher, mas_client, merc, boot)
    if args.scenario == "wave":
        return run_wave(args, watcher, mas_client, merc, boot)
    if args.scenario == "mesh":
        return run_mesh(args, watcher, mas_client, merc, boot)
    if args.scenario == "plan":
        return run_plan(args, watcher, mas_client, merc, boot)
    if args.scenario == "fabric":
        return run_fabric(args, watcher, mas_client, merc, boot)
    if args.scenario == "occupancy":
        return run_occupancy(args, watcher, mas_client, merc, boot)
    if args.scenario == "elastic":
        return run_elastic(args, watcher, mas_client, merc, boot)
    if args.scenario == "algebra":
        return run_algebra(args, watcher, mas_client, merc, boot)
    if args.scenario == "animation":
        return run_animation(args, watcher, mas_client, merc, boot)
    if args.scenario == "dap4":
        return run_dap4(args, watcher, mas_client, merc, boot)

    # churn: gateway off — the RSS bound must measure the pipeline
    # tiers, not the response cache legitimately filling its budget
    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger(), gateway=None)
    host = boot(server)

    rng = np.random.default_rng(1)
    counter = itertools.count()

    def one(_):
        # distinct bbox nearly every request: exercises eviction, the
        # ctrl/stride caches and the window machinery, not the LRU hit
        # path
        i = next(counter)
        fx = float(rng.uniform(0.0, 0.75))
        fy = float(rng.uniform(0.0, 0.75))
        w = merc.width * 0.25
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        url = (f"http://{host}/ows?service=WMS&request=GetMap"
               f"&version=1.3.0&layers=landsat&crs=EPSG:3857&bbox={bb}"
               f"&width=256&height=256&format=image/png"
               f"&time=2020-01-{10 + i % N_SCENES:02d}T00:00:00.000Z")
        with urllib.request.urlopen(url, timeout=120) as r:
            body = r.read()
            return r.status == 200 and body[:8] == b"\x89PNG\r\n\x1a\n"

    t_end = time.time() + args.seconds
    n_ok = n_bad = 0
    samples = []
    phase_rss = None
    with cf.ThreadPoolExecutor(args.conc) as ex:
        while time.time() < t_end:
            results = list(ex.map(one, range(args.conc * 4)))
            n_ok += sum(results)
            n_bad += len(results) - sum(results)
            now = time.time()
            samples.append((round(args.seconds - (t_end - now), 1),
                            round(rss_mb(), 1)))
            if phase_rss is None and \
                    now > t_end - args.seconds * 0.75:
                phase_rss = rss_mb()   # steady-state baseline

    with urllib.request.urlopen(f"http://{host}/debug",
                                timeout=30) as r:
        dbg = json.loads(r.read())
    exec_caches = dbg.get("executor", {})
    growth = rss_mb() - (phase_rss or rss_mb())
    out = {
        "requests_ok": n_ok, "requests_failed": n_bad,
        "rss_samples_mb": samples[:3] + samples[-3:],
        "steady_state_rss_growth_mb": round(growth, 1),
        "caches": {k: exec_caches.get(k) for k in
                   ("geo_cache", "stack_cache", "stride_cache")},
        "scene_cache": dbg.get("cache", {}).get("scene"),
    }
    print(json.dumps(out))
    sc = out["scene_cache"] or {}
    ok = (n_bad == 0 and growth <= args.max_rss_growth_mb
          and exec_caches.get("geo_cache", 0) <= 256
          # scenes and the executor's stacks of them share one byte
          # budget (pipeline/scene_cache.py)
          and sc.get("resident_bytes", 0) + sc.get("stack_bytes", 0)
          <= sc.get("budget_bytes", 0))
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_hot(args, watcher, mas_client, merc, boot) -> int:
    """Zipf-popular fixed tile grid vs baseline and gateway servers."""
    import threading

    import numpy as np

    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.serving import ServingGateway

    grid = 8
    frac = np.linspace(0.0, 0.75, grid)
    tiles = [(float(fx), float(fy)) for fx in frac for fy in frac]
    w = merc.width * 0.25
    rng = np.random.default_rng(7)
    # rank -> tile: Zipf mass lands on a fixed handful of hot tiles
    ranks = (rng.zipf(args.zipf, size=200_000) - 1) % len(tiles)

    def url_for(host: str, k: int) -> str:
        fx, fy = tiles[k]
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        return (f"http://{host}/ows?service=WMS&request=GetMap"
                f"&version=1.3.0&layers=landsat&crs=EPSG:3857&bbox={bb}"
                f"&width=256&height=256&format=image/png"
                f"&time=2020-01-10T00:00:00.000Z")

    def phase(host: str, seconds: float):
        counter = itertools.count()
        lats: list = []
        bad = [0]
        lock = threading.Lock()

        def one(_):
            k = int(ranks[next(counter) % len(ranks)])
            t0 = time.time()
            try:
                with urllib.request.urlopen(url_for(host, k),
                                            timeout=120) as r:
                    ok = (r.status == 200
                          and r.read()[:8] == b"\x89PNG\r\n\x1a\n")
            except Exception:
                ok = False
            d = time.time() - t0
            with lock:
                lats.append(d)
                if not ok:
                    bad[0] += 1

        t_end = time.time() + seconds
        with cf.ThreadPoolExecutor(args.conc) as ex:
            while time.time() < t_end:
                list(ex.map(one, range(args.conc * 4)))
        arr = np.array(lats) if lats else np.zeros(1)
        return {"requests": len(lats), "failed": bad[0],
                "rps": round(len(lats) / max(seconds, 1e-9), 1),
                "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 1),
                "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 1)}

    half = args.seconds / 2.0
    base_srv = OWSServer(watcher, mas_factory=lambda a: mas_client,
                         metrics=MetricsLogger(), gateway=None)
    base = phase(boot(base_srv), half)

    gate_srv = OWSServer(watcher, mas_factory=lambda a: mas_client,
                         metrics=MetricsLogger(),
                         gateway=ServingGateway())
    gate_host = boot(gate_srv)
    gate = phase(gate_host, half)

    # tracing overhead guard: with the response cache warm, replay the
    # same Zipf load untraced (GSKY_TRACE=0, read per request) and then
    # traced (default: ring recording on, file sampling off) — the
    # hot-cache p50 must not regress by more than --max-trace-overhead
    # percent (plus a timer-quantisation epsilon; hit-path p50 is ~ms)
    ov_s = max(6.0, args.seconds * 0.25)
    os.environ["GSKY_TRACE"] = "0"
    try:
        untraced = phase(gate_host, ov_s)
    finally:
        os.environ.pop("GSKY_TRACE", None)
    traced = phase(gate_host, ov_s)
    overhead_pct = round(
        (traced["p50_ms"] - untraced["p50_ms"])
        / max(untraced["p50_ms"], 1e-9) * 100.0, 2)
    overhead_ok = traced["p50_ms"] <= (
        untraced["p50_ms"] * (1.0 + args.max_trace_overhead / 100.0)
        + 0.1)

    with urllib.request.urlopen(f"http://{gate_host}/debug",
                                timeout=30) as r:
        serving = json.loads(r.read()).get("serving", {})
    rc = serving.get("response_cache", {})
    hits, misses = rc.get("hits", 0), rc.get("misses", 0)
    gate["hit_rate"] = round(hits / max(hits + misses, 1), 3)
    gate["singleflight_joined"] = serving.get(
        "singleflight", {}).get("joined", 0)
    gate["shed"] = sum(
        c.get("shed", 0) for c in
        serving.get("admission", {}).get("classes", {}).values())

    metrics = check_metrics(gate_host)
    trace_rep = slowest_trace_report(gate_host)

    out = {"scenario": "hot", "tiles": len(tiles),
           "zipf": args.zipf, "baseline": base, "gateway": gate,
           "trace_overhead": {"untraced": untraced, "traced": traced,
                              "p50_overhead_pct": overhead_pct,
                              "ok": overhead_ok},
           "metrics": metrics, "slowest_trace": trace_rep}
    print(json.dumps(out))
    ok = (base["failed"] == 0 and gate["failed"] == 0
          and untraced["failed"] == 0 and traced["failed"] == 0
          and gate["hit_rate"] > 0.3
          and overhead_ok
          and not metrics["missing"])
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_chaos(args, watcher, mas_client, merc, boot) -> int:
    """Mixed GetMap/GetCoverage under deterministic injected faults.

    Outcome classes per request:

    - ``ok``: clean 2xx
    - ``degraded``: 2xx carrying ``X-GSKY-Degraded`` (partial mosaic or
      stale-cache replay — honest, labelled, still useful)
    - ``ogc_error``: OGC ServiceException XML (admission shed, backend
      unavailable after retries, over-budget partial loss, deadline) —
      a *clean* refusal with the right status + Retry-After
    - ``hard_5xx`` / ``transport``: a bare internal 500 or a dropped
      connection.  These fail the soak: the whole point of the
      resilience layer is that injected backend faults never surface as
      unhandled errors.
    """
    import threading

    import numpy as np

    from gsky_tpu.resilience import faults
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.serving import ServingGateway

    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger(), gateway=ServingGateway())
    host = boot(server)

    grid = 4
    frac = np.linspace(0.0, 0.75, grid)
    hot = [(float(fx), float(fy)) for fx in frac for fy in frac]
    w = merc.width * 0.25

    def getmap_url(fx: float, fy: float, date: int) -> str:
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        return (f"http://{host}/ows?service=WMS&request=GetMap"
                f"&version=1.3.0&layers=landsat_chaos&crs=EPSG:3857"
                f"&bbox={bb}&width=256&height=256&format=image/png"
                f"&time=2020-01-{date:02d}T00:00:00.000Z")

    def getcov_url(fx: float, fy: float) -> str:
        cw = merc.width * 0.4
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + cw},"
              f"{merc.ymin + fy * merc.height + cw}")
        return (f"http://{host}/ows?service=WCS&request=GetCoverage"
                f"&coverage=landsat_chaos&crs=EPSG:3857&bbox={bb}"
                f"&width=512&height=512&format=GeoTIFF"
                f"&time=2020-01-10T00:00:00.000Z")

    def classify(url: str) -> str:
        try:
            with urllib.request.urlopen(url, timeout=120) as r:
                degraded = r.headers.get("X-GSKY-Degraded")
                r.read()
                return "degraded" if degraded else "ok"
        except urllib.error.HTTPError as e:
            ctype = e.headers.get("Content-Type", "")
            e.read()
            if e.code == 500 or "vnd.ogc.se_xml" not in ctype:
                return "hard_5xx"
            return "ogc_error"
        except Exception:
            return "transport"

    # warm the hot tiles fault-free so the response cache holds clean
    # bytes; with cache_max_age=3 they expire mid-run and failed
    # re-renders fall back to stale-on-error replay
    warm_bad = sum(classify(getmap_url(fx, fy, 10)) not in ("ok",)
                   for fx, fy in hot)

    faults.configure(args.faults, seed=args.fault_seed)
    rng = np.random.default_rng(args.fault_seed)
    counter = itertools.count()
    counts: dict = {}
    lock = threading.Lock()

    # periodically evict the resident scenes: a warmed scene cache would
    # otherwise absorb every decode after the first minute, and the
    # decode-site faults (plus the partial-mosaic degradation they
    # trigger) would never execute.  Real deployments hit this via LRU
    # pressure; the soak compresses it to a few seconds.
    stop_churn = threading.Event()
    from gsky_tpu.pipeline.scene_cache import default_scene_cache

    def churn_scene_cache():
        while not stop_churn.wait(2.0):
            default_scene_cache.clear()

    threading.Thread(target=churn_scene_cache, daemon=True).start()

    def one(_):
        i = next(counter)
        if i % 6 == 5:
            u = getcov_url(float(rng.uniform(0.0, 0.5)),
                           float(rng.uniform(0.0, 0.5)))
        elif i % 3 == 0:
            fx, fy = hot[i // 3 % len(hot)]
            u = getmap_url(fx, fy, 10)
        else:
            u = getmap_url(float(rng.uniform(0.0, 0.75)),
                           float(rng.uniform(0.0, 0.75)),
                           10 + i % 4)
        c = classify(u)
        with lock:
            counts[c] = counts.get(c, 0) + 1

    t_end = time.time() + args.seconds
    try:
        with cf.ThreadPoolExecutor(args.conc) as ex:
            while time.time() < t_end:
                list(ex.map(one, range(args.conc * 4)))
    finally:
        stop_churn.set()
        faults.reset()

    # deterministic stale-on-error exercise on top of the probabilistic
    # load above: cache one tile cleanly, let its 3s TTL lapse, take the
    # backends down HARD, and require the gateway to answer with the
    # expired bytes as a labelled degraded 200 rather than an error
    u0 = getmap_url(*hot[0], 10)
    # fault-free refresh; "degraded" is legal here too (the load phase
    # may have left the MAS breaker open -> stale replay while it cools)
    refresh_cls = classify(u0)
    time.sleep(3.5)                         # past TTL, within stale grace
    default_scene_cache.clear()
    faults.configure("mas:error:1.0,decode:error:1.0", seed=1)
    try:
        stale_cls = classify(u0)
    finally:
        faults.reset()

    with urllib.request.urlopen(f"http://{host}/debug",
                                timeout=30) as r:
        res = json.loads(r.read()).get("resilience", {})
    breakers = res.get("breakers", {})
    metrics = check_metrics(host)
    trace_rep = slowest_trace_report(host)
    out = {
        "scenario": "chaos", "faults": args.faults,
        "metrics": metrics, "slowest_trace": trace_rep,
        "warm_failures": warm_bad, "responses": counts,
        "stale_on_error": {"refresh": refresh_cls, "replay": stale_cls},
        "resilience": {
            "retries": res.get("retries", {}),
            "retry_exhausted": res.get("retry_exhausted", {}),
            "faults_injected": res.get("faults_injected", {}),
            "degraded_responses": res.get("degraded_responses", 0),
            "breaker_failures": {n: b.get("failures", 0)
                                 for n, b in breakers.items()},
        },
    }
    print(json.dumps(out))
    ok = (warm_bad == 0
          and counts.get("hard_5xx", 0) == 0
          and counts.get("transport", 0) == 0
          and counts.get("ok", 0) > 0
          and refresh_cls in ("ok", "degraded")
          and stale_cls == "degraded"
          and sum(res.get("retries", {}).values()) > 0
          and sum(res.get("faults_injected", {}).values()) > 0
          and res.get("degraded_responses", 0) > 0
          and not metrics["missing"]
          and any(b.get("failures", 0) > 0 for b in breakers.values()))
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_devicechaos(args, watcher, mas_client, merc, boot) -> int:
    """Device supervision & warm recovery under injected TPU incidents.

    Four phases (crash, hang, OOM, readback corruption), each riding
    the REAL supervisor paths — the ``device:*`` fault sites fire
    inside the dispatch watchdog / readback probe, so classification,
    teardown+rebuild, OOM relief+retry and quarantine all execute
    exactly as they would on flaky hardware.  Pass criteria:

    - zero bare 5xx / dropped connections in every phase (every failure
      is a labelled degraded 200 or an OGC-XML refusal with Retry-After)
    - the device returns to ``healthy`` within the recovery budget
      after every phase (backoff compressed via GSKY_DEVICE_REINIT_BACKOFF)
    - the rebuilt pool rehydrates >= 50% of the pre-incident hot pages
    - every incident kind shows up in the supervisor counters, and the
      device /metrics families round-trip the strict parser
    """
    import tempfile
    import threading

    import numpy as np

    from gsky_tpu.resilience import faults
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.serving import ServingGateway

    # the paged pipeline must engage (interpret mode) so the pool holds
    # a working set worth recovering; compress the reinit backoff so
    # recovery fits the soak budget; private journal so a previous
    # run's residency can't leak into this one's rehydration
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_DEVICE_REINIT_BACKOFF": "0.05,0.4",
        "GSKY_POOL_AUDIT": "1",
        "GSKY_POOL_JOURNAL": os.path.join(
            tempfile.mkdtemp(prefix="gsky_devicechaos_"),
            "journal.jsonl"),
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    saved_env["GSKY_DEVICE_HANG_S"] = os.environ.get("GSKY_DEVICE_HANG_S")
    os.environ.update(env_overrides)

    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger(), gateway=ServingGateway())
    host = boot(server)

    grid = 3
    frac = np.linspace(0.0, 0.6, grid)
    hot = [(float(fx), float(fy)) for fx in frac for fy in frac]
    w = merc.width * 0.25

    def getmap_url(fx: float, fy: float, date: int) -> str:
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        return (f"http://{host}/ows?service=WMS&request=GetMap"
                f"&version=1.3.0&layers=landsat_chaos&crs=EPSG:3857"
                f"&bbox={bb}&width=256&height=256&format=image/png"
                f"&time=2020-01-{date:02d}T00:00:00.000Z")

    def getcov_url(fx: float, fy: float) -> str:
        # WCS float export: the readback the corruption probe can
        # actually convict (tile GetMap pulls are uint8 — every byte
        # value is legal, so the inf probe has nothing to catch there)
        cw = merc.width * 0.3
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + cw},"
              f"{merc.ymin + fy * merc.height + cw}")
        return (f"http://{host}/ows?service=WCS&request=GetCoverage"
                f"&coverage=landsat_chaos&crs=EPSG:3857&bbox={bb}"
                f"&width=256&height=256&format=GeoTIFF"
                f"&time=2020-01-10T00:00:00.000Z")

    retry_after_seen = [0]

    def classify(url: str) -> str:
        try:
            with urllib.request.urlopen(url, timeout=120) as r:
                degraded = r.headers.get("X-GSKY-Degraded")
                r.read()
                return "degraded" if degraded else "ok"
        except urllib.error.HTTPError as e:
            ctype = e.headers.get("Content-Type", "")
            if e.headers.get("Retry-After"):
                retry_after_seen[0] += 1
            e.read()
            if e.code == 500 or "vnd.ogc.se_xml" not in ctype:
                return "hard_5xx"
            return "ogc_error"
        except Exception:
            return "transport"

    # warm lap, fault-free: stage the hot working set into the pool
    warm_bad = sum(classify(getmap_url(fx, fy, 10)) not in ("ok",)
                   for fx, fy in hot)
    from gsky_tpu.pipeline import pages
    pool = pages._default
    resident_before = pool.stats()["resident"] if pool is not None else 0

    def device_stats() -> dict:
        with urllib.request.urlopen(f"http://{host}/debug",
                                    timeout=30) as r:
            return json.loads(r.read()).get("device", {})

    rng = np.random.default_rng(args.fault_seed)
    counter = itertools.count()
    lock = threading.Lock()
    phase_s = max(2.0, args.seconds / 8.0)
    recovery_budget_s = 20.0

    use_wcs = [False]

    def one(counts):
        i = next(counter)
        if use_wcs[0]:
            u = getcov_url(float(rng.uniform(0.0, 0.6)),
                           float(rng.uniform(0.0, 0.6)))
        elif i % 2 == 0:
            fx, fy = hot[i // 2 % len(hot)]
            u = getmap_url(fx, fy, 10)
        else:       # cache-busting mix so dispatches keep happening
            u = getmap_url(float(rng.uniform(0.0, 0.6)),
                           float(rng.uniform(0.0, 0.6)), 10 + i % 4)
        c = classify(u)
        with lock:
            counts[c] = counts.get(c, 0) + 1

    def recover() -> float:
        """Drive fresh dispatches (cache-busting bboxes) until the
        supervisor reports healthy; returns seconds taken or -1."""
        t0 = time.time()
        while time.time() - t0 < recovery_budget_s:
            classify(getmap_url(float(rng.uniform(0.0, 0.75)),
                                float(rng.uniform(0.0, 0.75)),
                                10 + next(counter) % 4))
            if device_stats().get("state") == "healthy":
                return round(time.time() - t0, 2)
            time.sleep(0.1)
        return -1.0

    phases = (
        ("crash", "device:crash:0.4", None),
        ("hang", "device:hang:2s:0.4", ("GSKY_DEVICE_HANG_S", "0.3")),
        ("corrupt", "device:corrupt:0.5", None),
        ("oom", "device:oom:0.5", None),
    )
    from gsky_tpu.resilience.pressure import default_monitor
    results = {}
    try:
        for name, spec, extra_env in phases:
            use_wcs[0] = name == "corrupt"
            if extra_env:
                os.environ[extra_env[0]] = extra_env[1]
            faults.configure(spec, seed=args.fault_seed)
            counts: dict = {}
            t_end = time.time() + phase_s
            try:
                with cf.ThreadPoolExecutor(args.conc) as ex:
                    while time.time() < t_end:
                        list(ex.map(one, [counts] * (args.conc * 2)))
            finally:
                faults.reset()
                if extra_env:
                    os.environ.pop(extra_env[0], None)
            took = recover()
            results[name] = {"responses": counts,
                             "recovery_s": took}
            # the OOM relief protocol escalates the pressure monitor
            # with a hold; relax it between phases so the NEXT phase
            # measures its own incident path, not residual brownout
            # (real deployments space incidents out; the soak doesn't)
            default_monitor().reset()
    finally:
        faults.reset()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    dev = device_stats()
    rehydrated = int(dev.get("rehydrated_pages", 0))
    metrics = check_metrics(host, require=(
        "gsky_requests_total", "gsky_device_state",
        "gsky_device_reinits_total", "gsky_device_hangs_total",
        "gsky_device_incidents_total",
        "gsky_pool_rehydrated_pages_total"))
    out = {
        "scenario": "devicechaos", "phases": results,
        "warm_failures": warm_bad,
        "resident_before": resident_before,
        "rehydrated_pages": rehydrated,
        "retry_after_responses": retry_after_seen[0],
        "device": {k: dev.get(k) for k in
                   ("state", "reinits", "reinit_failures", "hangs",
                    "crashes", "ooms", "oom_retries", "corruptions",
                    "quarantined_pages")},
        "metrics": metrics,
    }
    print(json.dumps(out))
    total = {}
    for r in results.values():
        for c, n in r["responses"].items():
            total[c] = total.get(c, 0) + n
    ok = (warm_bad == 0
          and total.get("hard_5xx", 0) == 0
          and total.get("transport", 0) == 0
          and total.get("ok", 0) + total.get("degraded", 0) > 0
          and all(r["recovery_s"] >= 0 for r in results.values())
          and dev.get("state") == "healthy"
          and int(dev.get("reinits", 0)) >= 1
          and int(dev.get("hangs", 0)) >= 1
          and int(dev.get("crashes", 0)) >= 1
          and int(dev.get("ooms", 0)) >= 1
          and int(dev.get("corruptions", 0)) >= 1
          and resident_before > 0
          and rehydrated >= max(1, resident_before // 2)
          and retry_after_seen[0] >= 0
          and not metrics["missing"])
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_burst(args, watcher, mas_client, merc, boot) -> int:
    """Prewarm, one warm lap, then a concurrent GetMap storm of
    HETEROGENEOUS tile footprints (landsat_burst cycles four bbox
    widths; landsat stays fixed): every response must be a clean 200
    PNG, the storm may trigger at most a SMALL CONSTANT of fresh XLA
    compiles (ragged paged rendering serves new window shapes from
    already-compiled programs; the bucketed path would pay one program
    per fresh window bucket), and /debug must show the staged tile
    path's gates and encode pool visibly overlapping."""
    import threading

    import numpy as np

    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.server.prewarm import (compile_count,
                                         install_compile_probe, prewarm)

    # waves ON (this retires the PR 12 caveat that pinned GSKY_WAVES=0
    # here): wave occupancy is runtime-nondeterministic, but the
    # pipelined scheduler pushes FULL pow2 result blocks through its
    # rings, so the compile key is (statics x granule x page-slot x
    # pow2-wave-size) — enumerable ahead of time.  Pinning the wave
    # cap to 4 and the prewarm lattice to the matching 1,2,4 ladder
    # makes every occupancy the ticker can assemble land on a program
    # prewarm already compiled, so the storm stays compile-free.
    os.environ.pop("GSKY_WAVES", None)
    os.environ["GSKY_WAVE_MAX"] = "4"
    os.environ["GSKY_PREWARM_WAVE_SIZES"] = "1,2,4"
    # superblock plans synthesise merged table shapes and sb_of maps
    # prewarm cannot enumerate; the planner's compile story is covered
    # by ``--scenario plan`` — here it would break the zero-compile
    # claim for reasons unrelated to waves
    os.environ["GSKY_PLAN"] = "0"
    install_compile_probe()
    # gateway off: a response-cache hit would bypass the pipeline and
    # the zero-compile claim would be about the cache, not the prewarm
    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger(), gateway=None)
    host = boot(server)

    warm = prewarm(watcher.configs)

    grid = 6
    frac = np.linspace(0.0, 0.75, grid)
    # the scene footprint sits in the TOP ~77% of the soak extent
    # (core is 1.3x the scene span, anchored at ymax), so the y grid
    # starts high enough that even the narrowest width below still
    # intersects data — an all-off-data bbox declines the staged prep
    # and would undercount the tile_stages assertion
    frac_y = np.linspace(0.1, 0.75, grid)
    tiles = [(float(fx), float(fy)) for fx in frac for fy in frac_y]
    # landsat_burst (single product) takes the staged fused path and
    # cycles HETEROGENEOUS bbox widths — four distinct gather-window
    # shapes, the storm the shape-bucketed dispatch recompiled for;
    # landsat's 4 products sit at DISTINCT dates, so at one timestamp
    # the fused prep declines and it exercises the modular fallback at
    # a fixed width — the compile budget below covers BOTH paths
    widths = (0.17, 0.25, 0.33, 0.41)
    layers = ("landsat_burst", "landsat")

    def url_for(layer: str, fx: float, fy: float,
                wf: float = 0.25) -> str:
        w = merc.width * wf
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        return (f"http://{host}/ows?service=WMS&request=GetMap"
                f"&version=1.3.0&layers={layer}&crs=EPSG:3857&bbox={bb}"
                f"&width=256&height=256&format=image/png"
                f"&time=2020-01-10T00:00:00.000Z")

    def fetch(url: str) -> bool:
        try:
            with urllib.request.urlopen(url, timeout=120) as r:
                return (r.status == 200
                        and r.read()[:8] == b"\x89PNG\r\n\x1a\n")
        except Exception:
            return False

    # warm lap: one serial request per layer pays the host-side caches
    # (geo transforms, scene decode+upload) and any residual program
    # prewarm's win=None sweep missed; compiles HERE are reported but
    # allowed — the burst after this line is what must stay compile-free
    warm_lap_bad = sum(not fetch(url_for(lay, *tiles[0]))
                       for lay in layers)
    warm_lap_compiles = compile_count() - warm["compiles"]

    c0 = compile_count()
    counter = itertools.count()
    bad = [0]
    n_by = {lay: 0 for lay in layers}
    lock = threading.Lock()

    def one(_):
        i = next(counter)
        lay = layers[i % len(layers)]
        wf = widths[i % len(widths)] if lay == "landsat_burst" else 0.25
        fx, fy = tiles[i % len(tiles)]
        # keep the footprint inside the mercator extent: off-world
        # tiles short-circuit before the staged path and would
        # undercount the tile_stages assertion below
        fx, fy = min(fx, 1.0 - wf), min(fy, 1.0 - wf)
        ok = fetch(url_for(lay, fx, fy, wf))
        with lock:
            n_by[lay] += 1
            if not ok:
                bad[0] += 1

    t_end = time.time() + args.seconds
    with cf.ThreadPoolExecutor(args.conc) as ex:
        while time.time() < t_end:
            list(ex.map(one, range(args.conc * 4)))
    burst_compiles = compile_count() - c0
    n_done = sum(n_by.values())

    with urllib.request.urlopen(f"http://{host}/debug",
                                timeout=30) as r:
        dbg = json.loads(r.read())
    ts = dbg.get("tile_stages", {})
    gates = ts.get("gates", {})
    pool = ts.get("encode_pool", {})
    overlap_hw = max([g.get("queue_max", 0) for g in gates.values()]
                     + [pool.get("queue_max", 0)] or [0])
    paged_dbg = (dbg.get("executor") or {}).get("paged") or {}
    from gsky_tpu.pipeline.waves import wave_stats
    ws = wave_stats()

    out = {
        "scenario": "burst",
        "prewarm": warm,
        "warm_lap": {"failed": warm_lap_bad,
                     "compiles": warm_lap_compiles},
        "requests": n_by, "failed": bad[0],
        "burst_compiles": burst_compiles,
        "widths": widths,
        "paged": paged_dbg,
        "waves": {k: ws.get(k) for k in
                  ("dispatches", "requests", "occupancy",
                   "staged_waves", "fallbacks")},
        "tile_stages": {
            "tiles": ts.get("tiles", 0),
            "gates": {n: {k: g.get(k) for k in
                          ("limit", "entries", "queue_max")}
                      for n, g in gates.items()},
            "encode_pool": {k: pool.get(k) for k in
                            ("workers", "encoded", "queue_max")},
        },
    }
    print(json.dumps(out))
    # the heterogeneous-width storm may compile a handful of ragged-pad
    # variants (page-slot / batch pow2 points prewarm's sweep missed)
    # but must stay a SMALL CONSTANT, independent of shape diversity
    compile_budget = 4
    # when the paged path can run (pallas on), the storm must actually
    # engage it — otherwise the compile bound is about the wrong path
    from gsky_tpu.ops.paged import paged_enabled
    paged_ok = (not paged_enabled()
                or paged_dbg.get("engaged", 0) > 0)
    # with waves on the staged path's dispatch stage hands tiles to
    # the wave scheduler INSTEAD of the narrow dispatch gate (a gate
    # would serialise the arrivals coalescing needs — tile_stages
    # `_dispatch_stage`), so "dispatch engaged" is the scheduler's
    # dispatch counter; waves off, it is the gate's entry count
    dispatch_ok = (gates.get("dispatch", {}).get("entries", 0) > 0
                   or ws.get("dispatches", 0) > 0)
    ok = (warm["failures"] == 0 and warm_lap_bad == 0
          and n_done > 0 and bad[0] == 0
          and burst_compiles <= compile_budget
          and paged_ok
          and ts.get("tiles", 0) >= n_by["landsat_burst"]
          and gates.get("decode", {}).get("entries", 0) > 0
          and dispatch_ok
          and pool.get("encoded", 0) > 0
          and overlap_hw >= 2)
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_fleet(args, watcher, mas_client, merc, boot) -> int:
    """Multi-process fleet fault tolerance: three real worker-node
    subprocesses behind the consistent-hash router; kill one mid-soak,
    revive it, require zero bare 5xx and >= 90% locality recovery;
    then a direct-dispatch hedge phase against a deliberately slow
    node (see module docstring)."""
    import socket
    import subprocess
    import threading

    import numpy as np

    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.worker import gskyrpc_pb2 as pb
    from gsky_tpu.worker.server import METHOD

    import grpc

    # routing knobs for a fast-converging soak: 1s active probes so a
    # revived node is re-admitted within a couple of beats, and a
    # looser load bound — at soak concurrency (4) over 3 nodes the
    # default c=1.25 caps the home node at 2 in-flight and constantly
    # spills repeat keys, drowning the locality signal being measured
    os.environ.setdefault("GSKY_FLEET_PROBE_S", "1.0")
    os.environ.setdefault("GSKY_FLEET_BOUND", "2.5")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf_dir = watcher.root
    data_root = os.path.dirname(conf_dir)
    base_env = dict(os.environ, PYTHONPATH=repo)
    base_env.setdefault("JAX_PLATFORMS", "cpu")

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    procs: dict = {}

    def spawn(port: int, extra_env=None):
        e = dict(base_env)
        if extra_env:
            e.update(extra_env)
        logf = open(os.path.join(data_root, f"node-{port}.log"), "ab")
        procs[port] = subprocess.Popen(
            [sys.executable, "-m", "gsky_tpu.worker.server",
             "-p", str(port), "-host", "127.0.0.1",
             "-n", "1", "-oom_threshold", "0"],
            env=e, cwd=repo, stdout=logf, stderr=subprocess.STDOUT)
        logf.close()                     # child holds its own fd

    def wait_ready(port: int, deadline_s: float) -> bool:
        """Poll worker_info until the node answers (the node imports
        jax before it listens, which is slow on a starved host).  A
        FRESH channel per attempt: a channel dialled before the node
        listens parks its subchannel in TRANSIENT_FAILURE under gRPC's
        reconnect backoff (minutes at the cap) and every RPC on it
        fails instantly without re-dialling."""
        t_end = time.time() + deadline_s
        while time.time() < t_end:
            if procs[port].poll() is not None:
                return False             # node died during boot
            ch = grpc.insecure_channel(f"127.0.0.1:{port}")
            stub = ch.unary_unary(
                METHOD, request_serializer=pb.Task.SerializeToString,
                response_deserializer=pb.Result.FromString)
            try:
                stub(pb.Task(operation="worker_info"), timeout=2.0)
                return True
            except Exception:
                time.sleep(0.5)
            finally:
                ch.close()
        return False

    ports = [free_port() for _ in range(3)]
    nodes = [f"127.0.0.1:{p}" for p in ports]
    try:
        for p in ports:
            spawn(p)
        boot_deadline = time.time() + 600
        for p in ports:
            if not wait_ready(p, max(boot_deadline - time.time(), 1.0)):
                print(json.dumps({"scenario": "fleet",
                                  "error": f"node :{p} never came up"}))
                print("SOAK FAILED", flush=True)
                return 1

        # the fleet layer lives in its own namespace so its
        # worker_nodes don't leak into the other scenarios' layers
        ns_dir = os.path.join(conf_dir, "fleet")
        os.makedirs(ns_dir, exist_ok=True)
        with open(os.path.join(ns_dir, "config.json"), "w") as fp:
            json.dump({
                "service_config": {"ows_hostname": "", "mas_address": "",
                                   "worker_nodes": nodes},
                "layers": [{
                    "name": "landsat_fleet", "title": "fleet soak",
                    "data_source": data_root,
                    "rgb_products": [f"LC08_20200{110 + k}_T1"
                                     for k in range(N_SCENES)],
                    "time_generator": "mas",
                    "wms_timeout": 120,
                    "wcs_max_width": 4096, "wcs_max_height": 4096,
                    "wcs_max_tile_width": 256,
                    "wcs_max_tile_height": 256}],
            }, fp)
        watcher.reload()

        # gateway off: a response-cache hit would short-circuit the
        # worker RPCs and the locality ledger would measure nothing
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        grid = 3
        frac = np.linspace(0.0, 0.75, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac]
        w = merc.width * 0.25

        def url_for(fx: float, fy: float) -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows/fleet?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat_fleet&crs=EPSG:3857"
                    f"&bbox={bb}&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        def classify(url: str) -> str:
            try:
                with urllib.request.urlopen(url, timeout=180) as r:
                    degraded = r.headers.get("X-GSKY-Degraded")
                    r.read()
                    return "degraded" if degraded else "ok"
            except urllib.error.HTTPError as e:
                ctype = e.headers.get("Content-Type", "")
                e.read()
                if e.code == 500 or "vnd.ogc.se_xml" not in ctype:
                    return "hard_5xx"
                return "ogc_error"
            except Exception:
                return "transport"

        def fleet_block() -> dict:
            with urllib.request.urlopen(f"http://{host}/debug",
                                        timeout=30) as r:
                return json.loads(r.read()).get(
                    "fleet", {}).get("worker", {})

        def loc(fb: dict):
            l = fb.get("locality", {})
            return l.get("hits", 0), l.get("misses", 0)

        def rate(h0, m0, h1, m1) -> float:
            return (h1 - h0) / max((h1 - h0) + (m1 - m0), 1)

        def drive(seconds: float, counts: dict):
            counter = itertools.count()
            lock = threading.Lock()

            def one(_):
                i = next(counter)
                c = classify(url_for(*tiles[i % len(tiles)]))
                with lock:
                    counts[c] = counts.get(c, 0) + 1

            conc = min(args.conc, 4)
            t_end = time.time() + seconds
            with cf.ThreadPoolExecutor(conc) as ex:
                while time.time() < t_end:
                    list(ex.map(one, range(conc * 2)))

        def lap(retries: int = 3) -> int:
            bad = 0
            for fx, fy in tiles:
                for _ in range(retries):
                    if classify(url_for(fx, fy)) in ("ok", "degraded"):
                        break
                else:
                    bad += 1
            return bad

        # warm: the first warp on each node pays its decode child's jax
        # import + the first XLA compiles; retry until the fleet answers
        warm_end = time.time() + 420
        while time.time() < warm_end:
            if classify(url_for(*tiles[0])) == "ok":
                break
            time.sleep(2.0)
        warm_bad = lap()

        # phase A: locality baseline under a healthy fleet
        counts: dict = {}
        h0, m0 = loc(fleet_block())
        drive(max(args.seconds * 0.35, 6.0), counts)
        h1, m1 = loc(fleet_block())
        baseline = rate(h0, m0, h1, m1)

        # phase B: SIGKILL one node mid-load.  Every response must stay
        # clean — the router eats the failure, not the client.
        kill_port = ports[1]
        killed = f"127.0.0.1:{kill_port}"
        procs[kill_port].kill()
        procs[kill_port].wait()
        kill_counts: dict = {}
        drive(max(args.seconds * 0.3, 6.0), kill_counts)

        # revive on the SAME port (the router's channels reconnect),
        # then wait for the phi detector to re-admit it
        spawn(kill_port)
        revived = wait_ready(kill_port, 300)
        state = None
        if revived:
            t_end = time.time() + 120
            while time.time() < t_end:
                state = fleet_block().get("health", {}).get(
                    killed, {}).get("state")
                if state == "healthy":
                    break
                time.sleep(1.0)

        # one uncounted re-home lap flips each key's last-node entry
        # back to its ring home; the measured phase then shows whether
        # locality actually RECOVERED, not the one-off re-home misses
        lap(retries=2)
        h2, m2 = loc(fleet_block())
        drive(max(args.seconds * 0.35, 6.0), counts)
        h3, m3 = loc(fleet_block())
        recovery = rate(h2, m2, h3, m3)
        fb = fleet_block()

        # observability: the fleet path is the one place every process
        # boundary is crossed, so require (a) /metrics to satisfy the
        # strict exposition parser with the worker-RPC family present,
        # and (b) at least one recorded trace to be STITCHED — gateway
        # spans plus worker-process child spans carried back over the
        # RPC's info_json under one trace id
        metrics = check_metrics(
            host, require=("gsky_requests_total", "gsky_request_seconds",
                           "gsky_stage_seconds",
                           "gsky_worker_rpc_seconds"))
        with urllib.request.urlopen(f"http://{host}/debug/trace",
                                    timeout=30) as r:
            listing = json.loads(r.read())
        stitched = [t for t in listing.get("traces", [])
                    if "worker" in (t.get("processes") or [])]
        trace_rep = slowest_trace_report(host)

        # free the fleet before the hedge coda (1-core host): keep one
        # fast node, add one deliberately slow one
        for p in (ports[1], ports[2]):
            procs[p].kill()
            procs[p].wait()

        slow_port = free_port()
        spawn(slow_port,
              extra_env={"GSKY_FAULTS": "node:slow:250ms:1.0"})
        hedge_out = {"ready": wait_ready(slow_port, 300)}
        if hedge_out["ready"]:
            from gsky_tpu.fleet import HedgePolicy
            from gsky_tpu.worker.client import WorkerClient
            pair = [f"127.0.0.1:{ports[0]}", f"127.0.0.1:{slow_port}"]
            keys = [f"soak-hedge-{k}" for k in range(64)]

            def p99_ms(client, n=72) -> float:
                lats = []
                for k in range(n):
                    t0 = time.time()
                    client.process(pb.Task(operation="worker_info"),
                                   route_key=keys[k % len(keys)])
                    lats.append(time.time() - t0)
                return round(float(np.percentile(lats, 99)) * 1e3, 1)

            uh = WorkerClient(pair)
            uh.fleet.hedge_enabled = False
            try:
                hedge_out["unhedged_p99_ms"] = p99_ms(uh)
            finally:
                uh.close()

            hc = WorkerClient(pair)
            # fixed 30ms hedge delay + a budget that cannot run dry
            # mid-phase: the soak shows the mechanism, the unit tests
            # pin the adaptive-delay and token-bucket math
            hc.fleet.hedge = HedgePolicy(min_delay_s=0.03,
                                         initial_delay_s=0.03,
                                         budget=1.0,
                                         min_samples=10 ** 6)
            try:
                hedge_out["hedged_p99_ms"] = p99_ms(hc)
                hedge_out.update({k: hc.fleet.hedge.stats()[k] for k in
                                  ("primaries", "hedges", "hedge_wins")})
            finally:
                hc.close()

        out = {
            "scenario": "fleet", "nodes": nodes, "killed": killed,
            "warm_failures": warm_bad,
            "responses": counts, "kill_phase": kill_counts,
            "locality": {"baseline": round(baseline, 3),
                         "recovery": round(recovery, 3)},
            "rerouted": fb.get("rerouted", 0),
            "routed": fb.get("routed", 0),
            "revived_state": state,
            "hedge": hedge_out,
            "metrics": metrics,
            "stitched_traces": len(stitched),
            "slowest_trace": trace_rep,
        }
        print(json.dumps(out))
        all_counts: dict = {}
        for d in (counts, kill_counts):
            for k, v in d.items():
                all_counts[k] = all_counts.get(k, 0) + v
        ok = (warm_bad == 0
              and all_counts.get("hard_5xx", 0) == 0
              and all_counts.get("transport", 0) == 0
              and all_counts.get("ok", 0) > 0
              and kill_counts.get("ok", 0) > 0
              and fb.get("rerouted", 0) > 0
              and revived and state == "healthy"
              # keyed routing must beat the random-assignment null
              # (1/3 over 3 nodes); it won't reach 1.0 here — bounded
              # load demotes the home node whenever concurrent dispatch
              # piles onto it, and a winning hedge credits the runner-up
              and baseline > 1.0 / 3.0
              and recovery >= 0.9 * baseline
              and not metrics["missing"]
              and len(stitched) > 0
              and hedge_out.get("ready") is True
              and hedge_out.get("hedge_wins", 0) > 0
              and hedge_out.get("hedges", 0)
              <= hedge_out.get("primaries", 0) + 10
              and hedge_out.get("hedged_p99_ms", 1e9)
              < hedge_out.get("unhedged_p99_ms", 0))
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for p, proc in procs.items():
            try:
                proc.kill()
            except Exception:  # process already exited
                pass


def run_overload(args, watcher, mas_client, merc, boot) -> int:
    """Overload survival: adaptive admission under a two-tenant storm,
    client-disconnect cancellation reclaiming permits, forced
    memory-pressure brownout, and clean recovery (see module
    docstring for the pass criteria)."""
    import socket
    import threading

    from gsky_tpu.resilience import cancel_stats
    from gsky_tpu.resilience.pressure import default_monitor
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.serving import default_gateway

    # knobs BEFORE reconfigure(): a small WMS ceiling + short queue
    # deadline so the storm genuinely queues and sheds at soak scale, a
    # fast AIMD cadence so adjustments land within the run, and distinct
    # weights for the two tenants the storm interleaves
    os.environ["GSKY_ADMIT_ADAPTIVE"] = "1"
    os.environ["GSKY_ADMIT_WMS"] = "4"
    os.environ["GSKY_ADMIT_QUEUE_S"] = "1.0"
    os.environ["GSKY_ADMIT_INTERVAL_S"] = "0.2"
    os.environ["GSKY_TENANT_WEIGHTS"] = "key:bulk:0.25,key:premium:4"
    adm = default_gateway.admission
    adm.reconfigure()
    mon = default_monitor()
    mon.force(None)

    # the DEFAULT gateway, not a private one: /metrics'
    # gsky_admit_limit family reads the process-wide instance
    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger(), gateway=default_gateway)
    host = boot(server)

    counter = itertools.count()
    lock = threading.Lock()
    shed_meta = {"sheds": 0, "missing_retry_after": 0}

    def url_for(i: int, px: int = 256) -> str:
        # multiplicative-hash bbox, ~4096 distinct values per axis:
        # every request is an uncached render, so admission gates real
        # work rather than response-cache hits (which bypass it)
        fx = 0.75 * ((i * 2654435761) % 4096) / 4096.0
        fy = 0.75 * ((i * 1597334677) % 4096) / 4096.0
        w = merc.width * 0.22
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        return (f"http://{host}/ows?service=WMS&request=GetMap"
                f"&version=1.3.0&layers=landsat&crs=EPSG:3857&bbox={bb}"
                f"&width={px}&height={px}&format=image/png"
                f"&time=2020-01-{10 + i % 4:02d}T00:00:00.000Z")

    def classify(url: str, headers=None) -> str:
        req = urllib.request.Request(url, headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                degraded = r.headers.get("X-GSKY-Degraded")
                r.read()
                return "degraded" if degraded else "ok"
        except urllib.error.HTTPError as e:
            ctype = e.headers.get("Content-Type", "")
            retry = e.headers.get("Retry-After")
            e.read()
            if e.code == 500 or "vnd.ogc.se_xml" not in ctype:
                return "hard_5xx"
            if e.code == 503:
                # no faults are injected in this scenario, so every 503
                # is an admission shed — it must carry Retry-After
                with lock:
                    shed_meta["sheds"] += 1
                    if not retry:
                        shed_meta["missing_retry_after"] += 1
            return "ogc_error"
        except Exception:
            return "transport"

    def drive(seconds: float, conc: int, counts: dict):
        tenants = ("premium", "bulk")

        def one(_):
            i = next(counter)
            hdrs = {"X-API-Key": tenants[i % len(tenants)]}
            c = classify(url_for(i), hdrs)
            with lock:
                counts[c] = counts.get(c, 0) + 1

        t_end = time.time() + seconds
        with cf.ThreadPoolExecutor(conc) as ex:
            while time.time() < t_end:
                list(ex.map(one, range(conc * 2)))

    # phase 1 — serial warm lap: pays compiles + scene decode and sets
    # the AIMD latency baseline LOW, so the contended storm after it
    # reads as a knee and forces a multiplicative decrease
    warm_counts: dict = {}
    for _ in range(6):
        c = classify(url_for(next(counter)))
        warm_counts[c] = warm_counts.get(c, 0) + 1

    # phase 2 — two-tenant storm at concurrency well past the limit:
    # contended renders inflate service time (decrease), queue waits
    # past the deadline shed as clean 503s
    storm_counts: dict = {}
    drive(max(args.seconds * 0.4, 8.0), max(args.conc, 10), storm_counts)

    # phase 3 — cooldown: light serial load while latency is healthy
    # again gives the controller room for additive recovery
    cool_counts: dict = {}
    t_end = time.time() + max(args.seconds * 0.15, 3.0)
    while time.time() < t_end:
        c = classify(url_for(next(counter)))
        cool_counts[c] = cool_counts.get(c, 0) + 1
    adjustments = adm.total_adjustments

    # phase 4 — client-disconnect volley: renders slowed past every
    # hold time (injected decode latency + a cold scene cache, so a
    # warmed pipeline can't finish before the client departs), then
    # aborted mid-flight; handler cancellation must fire each request's
    # token and hand the permit (or queue slot) back
    h, _, p = host.partition(":")
    fired0 = cancel_stats()["fired"] + adm.total_cancelled

    def disconnect_midflight(hold_s: float):
        i = next(counter)
        # default size (wms_max_width caps at 512; an oversized request
        # would be rejected before admission with nothing to cancel) —
        # the injected decode latency is what outlasts the hold
        path = url_for(i).split(host, 1)[1]
        s = socket.create_connection((h, int(p)), timeout=10)
        try:
            s.sendall((f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                       "Connection: close\r\n\r\n").encode())
            time.sleep(hold_s)
        finally:
            s.close()

    from gsky_tpu.pipeline.scene_cache import default_scene_cache
    from gsky_tpu.resilience import faults
    default_scene_cache.clear()
    faults.configure("decode:latency:400ms:1.0", seed=5)
    try:
        ths = [threading.Thread(target=disconnect_midflight,
                                args=(hold,))
               for hold in (0.3, 0.3, 0.45, 0.45, 0.6, 0.6, 0.75, 0.75)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
    finally:
        faults.reset()
    cancel_seen = 0
    drained = False
    t_end = time.time() + 20
    while time.time() < t_end:
        cancel_seen = (cancel_stats()["fired"] + adm.total_cancelled
                       - fired0)
        cls = adm.stats()["classes"]
        drained = all(c["in_use"] == 0 and c["queued"] == 0
                      for c in cls.values())
        if drained and cancel_seen >= 1:
            break
        time.sleep(0.5)

    # phase 5 — forced brownout: elevated pressure must label fresh
    # renders degraded (and keep them OUT of the response cache);
    # critical pressure must clamp the effective limit and still answer
    mon.force(1)
    brown_hdr = 0
    brown_counts: dict = {}
    crit_counts: dict = {}
    clamped = False
    try:
        for _ in range(4):
            i = next(counter)
            req = urllib.request.Request(url_for(i))
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    tag = r.headers.get("X-GSKY-Degraded") or ""
                    r.read()
                    if "brownout" in tag:
                        brown_hdr += 1
                    brown_counts["degraded" if tag else "ok"] = \
                        brown_counts.get("degraded" if tag else "ok",
                                         0) + 1
            except Exception:
                brown_counts["error"] = brown_counts.get("error", 0) + 1

        mon.force(2)
        wms = adm.stats()["classes"]["WMS"]
        clamped = (wms["effective_limit"]
                   <= max(1, wms["limit"] // 2))
        drive(max(args.seconds * 0.2, 4.0), max(args.conc, 8),
              crit_counts)
    finally:
        mon.force(None)

    # phase 6 — recovery: pressure released; wait out the falling
    # hysteresis (GSKY_PRESSURE_CLEAR_S holds the degraded state for a
    # calm window), then serial renders must come back clean (no
    # degraded label, no shed)
    t_end = time.time() + 15
    while time.time() < t_end and mon.state() != 0:
        # state() (not stats()) — only state() recomputes the
        # falling edge; stats() just reports the latched value
        time.sleep(0.25)
    rec_ok = sum(classify(url_for(next(counter))) == "ok"
                 for _ in range(3))

    metrics = check_metrics(host, require=(
        "gsky_requests_total", "gsky_request_seconds",
        "gsky_stage_seconds", "gsky_admit_limit",
        "gsky_cancelled_total", "gsky_pressure_state"))
    trace_rep = slowest_trace_report(host)

    all_counts: dict = {}
    for d in (warm_counts, storm_counts, cool_counts, brown_counts,
              crit_counts):
        for k, v in d.items():
            all_counts[k] = all_counts.get(k, 0) + v

    out = {
        "scenario": "overload",
        "phases": {"warm": warm_counts, "storm": storm_counts,
                   "cooldown": cool_counts, "brownout": brown_counts,
                   "critical": crit_counts, "recovery_ok": rec_ok},
        "sheds": shed_meta,
        "adjustments": adjustments,
        "cancellation": {"fired": cancel_seen, "drained": drained},
        "brownout_labelled": brown_hdr,
        "pressure_clamped": clamped,
        "admission": adm.stats(),
        "cancel": cancel_stats(),
        "pressure": mon.stats(),
        "metrics": metrics,
        "slowest_trace": trace_rep,
    }
    print(json.dumps(out))
    ok = (all_counts.get("hard_5xx", 0) == 0
          and all_counts.get("transport", 0) == 0
          and all_counts.get("ok", 0) > 0
          and warm_counts.get("ok", 0) == 6
          and shed_meta["sheds"] >= 1
          and shed_meta["missing_retry_after"] == 0
          and adjustments >= 1
          and cancel_seen >= 1
          and drained
          and brown_hdr >= 1
          and clamped
          and rec_ok == 3
          and not metrics["missing"])
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_wcs(args, watcher, mas_client, merc, boot) -> int:
    """Repeated large GetCoverage exports through the staged engine."""
    import numpy as np

    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger(), gateway=None)
    host = boot(server)
    rng = np.random.default_rng(3)

    def one(_):
        # each export covers a random half-extent window: big enough to
        # fan out to a multi-tile plan (1024px / 256px tiles = 16 tiles)
        fx = float(rng.uniform(0.0, 0.5))
        fy = float(rng.uniform(0.0, 0.5))
        w = merc.width * 0.5
        bb = (f"{merc.xmin + fx * merc.width},"
              f"{merc.ymin + fy * merc.height},"
              f"{merc.xmin + fx * merc.width + w},"
              f"{merc.ymin + fy * merc.height + w}")
        url = (f"http://{host}/ows?service=WCS&request=GetCoverage"
               f"&coverage=landsat&crs=EPSG:3857&bbox={bb}"
               f"&width=1024&height=1024&format=GeoTIFF"
               f"&time=2020-01-10T00:00:00.000Z")
        try:
            with urllib.request.urlopen(url, timeout=300) as r:
                body = r.read()
                # classic (II*\x00) little-endian TIFF magic
                return (r.status == 200 and len(body) > 8
                        and body[:4] == b"II*\x00")
        except Exception:
            return False

    t_end = time.time() + args.seconds
    n_ok = n_bad = 0
    lats = []
    phase_rss = None
    with cf.ThreadPoolExecutor(args.conc) as ex:
        while time.time() < t_end:
            t0 = time.time()
            results = list(ex.map(one, range(args.conc)))
            lats.append((time.time() - t0) / max(len(results), 1))
            n_ok += sum(results)
            n_bad += len(results) - sum(results)
            if phase_rss is None and \
                    time.time() > t_end - args.seconds * 0.75:
                phase_rss = rss_mb()

    with urllib.request.urlopen(f"http://{host}/debug",
                                timeout=30) as r:
        dbg = json.loads(r.read())
    ep = dbg.get("export_pipeline", {})
    growth = rss_mb() - (phase_rss or rss_mb())
    out = {
        "scenario": "wcs",
        "exports_ok": n_ok, "exports_failed": n_bad,
        "mean_export_s": round(float(sum(lats) / max(len(lats), 1)), 2),
        "steady_state_rss_growth_mb": round(growth, 1),
        "export_pipeline": {k: ep.get(k) for k in
                            ("exports", "tiles", "index_queries",
                             "scenes_warmed", "dedup_saved", "decode_s",
                             "warp_s", "encode_s", "wall_s")},
    }
    print(json.dumps(out))
    ok = (n_ok > 0 and n_bad == 0
          and growth <= args.max_rss_growth_mb
          and ep.get("exports", 0) >= n_ok
          and ep.get("index_queries", 0) >= n_ok
          and ep.get("decode_s", 0) > 0
          and ep.get("warp_s", 0) > 0
          and ep.get("encode_s", 0) > 0)
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_ingest(args, watcher, mas_client, merc, boot) -> int:
    """Cloud-native ingest: pan+zoom walk x three legs (docs/INGEST.md).

    The walk is deterministic so the planner's hit rate is a property
    of the predictor, not the load generator: two west-east rows
    stepped exactly one tile extent per request (the pan-continuation
    rule must fire), then two in-place halvings of the final tile (the
    zoom-in rule must fire on the second).  Each leg gets a FRESH
    server (fresh scene caches) and a reset ingest ledger, so the byte
    counters compare decode work, not cache luck."""
    from gsky_tpu.ingest import (reset_sources, reset_staging_pool,
                                 stats as ingest_stats)
    from gsky_tpu.ingest.prefetch import (default_planner,
                                          reset_default_planner)
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    # finer-than-bench tiles (1/16 of the extent): a pan step touches a
    # few 256px chunks of each scene, so the ranged leg's byte count is
    # the sparse-access story the whole-file baseline can't tell
    grid = 16
    tw, th = merc.width / grid, merc.height / grid
    j = grid // 2
    boxes = []
    for i in range(4, 12):                 # pan: one row, one visit/tile
        x0, y0 = merc.xmin + i * tw, merc.ymin + j * th
        boxes.append((x0, y0, x0 + tw, y0 + th))
    x0, y0, x1, y1 = boxes[-1]
    for _ in range(2):                     # zoom: halve in place twice
        cx, cy = (x0 + x1) / 2, (y0 + y1) / 2
        w, h = (x1 - x0) / 2, (y1 - y0) / 2
        x0, y0 = cx - w / 2, cy - h / 2
        x1, y1 = cx + w / 2, cy + h / 2
        boxes.append((x0, y0, x1, y1))
    # pacing: three legs must fit --seconds, but each step needs enough
    # air for the background warm to land before the next observation
    pause = min(0.35, max(0.1, args.seconds / (3.0 * len(boxes) * 2.0)))

    _KEYS = ("GSKY_INGEST", "GSKY_PREFETCH", "GSKY_INGEST_WINDOW_FRAC",
             "GSKY_INGEST_WINDOW_PROMOTE")

    def leg(env, prefetch_on=False, scrape_ingest=False):
        from gsky_tpu.pipeline.scene_cache import default_scene_cache
        saved = {k: os.environ.get(k) for k in _KEYS}
        os.environ.update(env)
        try:
            ingest_stats.reset()
            reset_sources()
            reset_staging_pool()
            reset_default_planner()
            # the scene cache is a process-wide singleton: drop leg N-1's
            # residency or leg N measures cache luck, not decode bytes
            default_scene_cache.clear()
            server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                               metrics=MetricsLogger(), gateway=None)
            host = boot(server)

            def url_of(bb):
                # temporal-range mosaic: the walk touches EVERY scene,
                # so the whole-file baseline pays full residency for
                # each while the ranged leg reads only touched chunks
                return (f"http://{host}/ows?service=WMS&request=GetMap"
                        f"&version=1.3.0&layers=landsat&crs=EPSG:3857"
                        f"&bbox={bb[0]},{bb[1]},{bb[2]},{bb[3]}"
                        f"&width=256&height=256&format=image/png"
                        f"&time=2020-01-09T00:00:00.000Z,"
                        f"2020-01-15T00:00:00.000Z")

            if prefetch_on:
                # priming lap: make the scenes resident before the timed
                # walk so background warms race the client's NEXT tile,
                # not a multi-scene cold decode
                try:
                    urllib.request.urlopen(url_of(boxes[0]),
                                           timeout=120).read()
                except Exception:  # priming failures tolerated - the timed walk decides
                    pass
                time.sleep(min(1.0, pause * 4))
            statuses = []
            lats = []
            for bb in boxes:
                url = url_of(bb)
                t0 = time.time()
                try:
                    with urllib.request.urlopen(url, timeout=120) as r:
                        ok = (r.status == 200 and
                              r.read()[:8] == b"\x89PNG\r\n\x1a\n")
                        statuses.append(r.status if ok else -r.status)
                except urllib.error.HTTPError as e:
                    statuses.append(-e.code)
                except Exception:
                    statuses.append(0)
                lats.append(time.time() - t0)
                time.sleep(pause)
            snap = ingest_stats.snapshot()
            require = ["gsky_requests_total", "gsky_request_seconds"]
            if scrape_ingest:
                require += ["gsky_ranged_reads_total",
                            "gsky_ranged_read_bytes_total",
                            "gsky_prefetch_total",
                            "gsky_ingest_overlap_ratio"]
            metrics = check_metrics(host, require=tuple(require))
            out = {
                "requests": len(statuses),
                "failed": sum(1 for s in statuses if s != 200),
                "bare_5xx": sum(1 for s in statuses if -600 < s <= -500),
                "p50_ms": round(sorted(lats)[len(lats) // 2] * 1e3, 1),
                "bytes_read": int(snap["ranged_read_bytes"]
                                  + snap["whole_read_bytes"]),
                "ranged_windows": snap["ranged_windows"],
                "fallbacks": snap["fallbacks"],
                "metrics": metrics,
            }
            if prefetch_on:
                ps = default_planner().stats()
                hits, misses = ps["hit"], ps["miss"]
                ps["hit_rate"] = round(hits / max(hits + misses, 1), 3)
                out["planner"] = ps
            return out
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            reset_default_planner()
            ingest_stats.reset()
            reset_sources()
            reset_staging_pool()

    base = leg({"GSKY_INGEST": "0", "GSKY_PREFETCH": "0",
                "GSKY_INGEST_WINDOW_FRAC": "0",
                "GSKY_INGEST_WINDOW_PROMOTE": "0"})
    ranged = leg({"GSKY_INGEST": "1", "GSKY_PREFETCH": "0",
                  "GSKY_INGEST_WINDOW_FRAC": "0.5",
                  "GSKY_INGEST_WINDOW_PROMOTE": "0"})
    prefetch = leg({"GSKY_INGEST": "1", "GSKY_PREFETCH": "1",
                    "GSKY_INGEST_WINDOW_FRAC": "0",
                    "GSKY_INGEST_WINDOW_PROMOTE": "0"},
                   prefetch_on=True, scrape_ingest=True)

    reduction = (round(1.0 - ranged["bytes_read"]
                       / max(base["bytes_read"], 1), 3)
                 if base["bytes_read"] else None)
    out = {
        "scenario": "ingest", "walk": len(boxes), "pause_s": pause,
        "baseline": base, "ranged": ranged, "prefetch": prefetch,
        "bytes_reduction": reduction,
    }
    print(json.dumps(out))
    ok = (base["failed"] == 0 and ranged["failed"] == 0
          and prefetch["failed"] == 0
          and base["bare_5xx"] == 0 and ranged["bare_5xx"] == 0
          and prefetch["bare_5xx"] == 0
          and ranged["ranged_windows"] > 0
          and ranged["bytes_read"] < base["bytes_read"]
          and prefetch["planner"]["hit_rate"] >= 0.5
          and not base["metrics"]["missing"]
          and not ranged["metrics"]["missing"]
          and not prefetch["metrics"]["missing"])
    print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
    return 0 if ok else 1


def run_wave(args, watcher, mas_client, merc, boot) -> int:
    """Wave-level device serving: a mixed GetMap + WPS-drill storm
    whose per-request device programs must coalesce into shared wave
    dispatches, with a client-disconnect volley dropping entries from
    their wave (see module docstring for the pass criteria)."""
    import socket
    import threading
    import urllib.parse

    import numpy as np

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import transform_bbox
    from gsky_tpu.pipeline.waves import wave_stats
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    # interpret mode engages the paged+wave pipeline on CPU; a wide
    # tick gives concurrent requests a real coalescing window at soak
    # concurrency, and a modest wave cap bounds the pow2-occupancy
    # program lattice the interpret backend pays cold during the storm
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_WAVES": "1",
        "GSKY_WAVE_MAX": "8",
        "GSKY_WAVE_TICK_MS": "100",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    try:
        # gateway off: a response-cache hit would bypass the pipeline
        # and the amortisation ratio would measure the cache, not the
        # wave scheduler
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        # distinct bboxes at ONE pixel shape / layer / timestamp:
        # every tile stages its own page tables but shares the wave
        # statics, so concurrent renders are eligible for the same
        # byte-wave group; the y grid starts high enough to stay on
        # data (the scene footprint anchors at ymax, see run_burst)
        grid = 6
        frac = np.linspace(0.0, 0.6, grid)
        frac_y = np.linspace(0.1, 0.6, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac_y]
        w = merc.width * 0.2

        def getmap_url(fx: float, fy: float) -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat_burst"
                    f"&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        # one small drill polygon over the scene footprint (lon/lat):
        # the drill band axis is pow2-padded and the window bucketed,
        # so every concurrent drill lands in the same reduction shape
        # and stacks into a single (K, B, N) wave group
        ll = transform_bbox(merc, EPSG3857, EPSG4326)
        d = 0.03
        x0 = ll.xmin + 0.35 * (ll.xmax - ll.xmin)
        y0 = ll.ymax - 0.25 * (ll.ymax - ll.ymin)
        geom = json.dumps({
            "type": "FeatureCollection", "features": [{
                "type": "Feature", "geometry": {
                    "type": "Polygon", "coordinates": [[
                        [x0, y0], [x0 + d, y0], [x0 + d, y0 + d],
                        [x0, y0 + d], [x0, y0]]]}}]})
        drill_q = urllib.parse.quote(geom)

        def drill_url(i: int) -> str:
            return (f"http://{host}/ows?service=WPS&request=Execute"
                    f"&identifier=geometryDrill"
                    f"&datainputs=geometry={drill_q}")

        lock = threading.Lock()
        counter = itertools.count()
        errors: list = []

        def fetch(url: str, kind: str) -> bool:
            # no faults are injected in this scenario, so every
            # response must be a flat 200 with the right body — any
            # error (incl. a clean OGC refusal) fails the soak
            try:
                with urllib.request.urlopen(url, timeout=180) as r:
                    body = r.read()
                    if r.status != 200:
                        return False
                    if kind == "map":
                        return body[:8] == b"\x89PNG\r\n\x1a\n"
                    return b"ProcessSucceeded" in body
            except Exception as exc:   # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{kind}: {exc!r:.200}")
                return False

        # warm lap: one serial request per kind pays scene decode and
        # the occupancy-1 programs; the storm then pays the larger
        # pow2-occupancy points as bursts actually materialise (this
        # scenario asserts coalescing, not compile counts — that is
        # run_burst's claim)
        warm_ok = (fetch(getmap_url(*tiles[0]), "map")
                   and fetch(drill_url(0), "wps"))

        bad = [0]
        n_req = {"map": 0, "wps": 0}

        def one(_):
            i = next(counter)
            # drills are a CLUSTERED minority: consecutive counter
            # values run near-simultaneously, so a burst of three
            # drills shares one tick and stacks into one (K, B, N)
            # reduction instead of three single-entry groups
            if i % 24 < 3:
                kind, url = "wps", drill_url(i)
            else:
                kind, url = "map", getmap_url(*tiles[i % len(tiles)])
            ok = fetch(url, kind)
            with lock:
                n_req[kind] += 1
                if not ok:
                    bad[0] += 1

        # concurrency well past the tick rate: per-request latency is
        # dominated by the host-side stages (decode, staging, encode),
        # so filling waves needs enough simultaneous arrivals per
        # coalescing window.  Free-running worker threads, not batched
        # ex.map laps — a batch barrier leaves its stragglers to ride
        # single-entry waves at every batch boundary
        conc = max(args.conc, 16)
        t_end = time.time() + args.seconds

        def storm_worker():
            while time.time() < t_end:
                one(None)

        storm = [threading.Thread(target=storm_worker)
                 for _ in range(conc)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()

        # client-disconnect volley: requests aborted mid-flight must
        # drop out of their wave (assembly skips them and releases
        # their pins; an in-flight wave discards their lane at
        # readback) — the scheduler's `cancelled` counter is the
        # ground truth either way.  Staggered holds cover both the
        # queued-entry and the mid-wave window; retried because the
        # race between token fire and wave assembly is real
        h, _, p = host.partition(":")

        def disconnect_midflight(hold_s: float):
            i = next(counter)
            path = getmap_url(*tiles[i % len(tiles)]).split(host, 1)[1]
            try:
                s = socket.create_connection((h, int(p)), timeout=10)
                try:
                    s.sendall((f"GET {path} HTTP/1.1\r\n"
                               f"Host: {host}\r\n"
                               "Connection: close\r\n\r\n").encode())
                    time.sleep(hold_s)
                finally:
                    s.close()
            except Exception:   # noqa: BLE001 - volley is best-effort
                pass

        cancelled0 = wave_stats().get("cancelled", 0)
        cancel_seen = 0
        volleys = 0
        deadline = time.time() + 30
        while time.time() < deadline and cancel_seen < 1:
            ths = [threading.Thread(target=disconnect_midflight,
                                    args=(hold,))
                   for hold in (0.05, 0.1, 0.2, 0.35, 0.5, 0.8)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            volleys += 1
            time.sleep(1.5)
            cancel_seen = wave_stats().get("cancelled", 0) - cancelled0

        # every page the storm pinned must be back: cancelled entries
        # release at assembly, dispatched waves release after readback
        from gsky_tpu.pipeline import pages
        pinned = -1
        t_end = time.time() + 15
        while time.time() < t_end:
            pool = pages._default
            pinned = (pool.stats().get("pinned", -1)
                      if pool is not None else 0)
            if pinned == 0:
                break
            time.sleep(0.5)

        ws = wave_stats()
        occ = ws.get("occupancy", {})
        max_occ = max([int(k) for k in occ] or [0])
        dispatches = ws.get("dispatches", 0)
        requests = ws.get("requests", 0)
        n_done = sum(n_req.values())
        metrics = check_metrics(host, require=(
            "gsky_requests_total", "gsky_request_seconds",
            "gsky_wave_dispatches_total", "gsky_wave_occupancy",
            "gsky_wave_requests_total"))
        trace_rep = slowest_trace_report(host)

        out = {
            "scenario": "wave",
            "warm_ok": warm_ok,
            "requests": n_req, "failed": bad[0],
            "errors": errors,
            "amortisation_x": round(requests / max(dispatches, 1), 2),
            "cancellation": {"seen": cancel_seen, "volleys": volleys},
            "pool_pinned": pinned,
            "waves": ws,
            "metrics": metrics,
            "slowest_trace": trace_rep,
        }
        print(json.dumps(out))
        ok = (warm_ok and n_done > 0 and bad[0] == 0
              and dispatches >= 1
              and requests >= 3 * dispatches
              and max_occ >= 2
              and cancel_seen >= 1
              and pinned == 0
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_occupancy(args, watcher, mas_client, merc, boot) -> int:
    """Continuous device occupancy (docs/PERF.md "Continuous device
    occupancy"): the SAME sustained mixed GetMap + WPS-drill storm
    driven twice against one server — first with the two-stage wave
    pipeline disabled (GSKY_WAVE_PIPELINE=0, the synchronous ticker
    that plans, stacks, uploads and dispatches on one thread), then
    pipelined (assembly stages wave N+1 into the donated input ring
    while wave N executes).  The scheduler is reset between phases so
    each phase's inter-wave gap histogram is its own.  Pass criteria:
    zero bare 5xx both phases, the pipelined p99 host-side inter-wave
    dispatch gap BELOW the synchronous baseline (or already under the
    2 ms back-to-back floor — on a 1-core host a tiny sync baseline
    can beat the thread handoff noise), at least one wave actually
    staged ahead of dispatch, the page pool ending with ZERO pinned
    pages, and /metrics exposing the ``gsky_wave_gap_ms`` /
    ``gsky_wave_staged_total`` families through the strict parser."""
    import threading
    import urllib.parse

    import numpy as np

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import transform_bbox
    from gsky_tpu.pipeline.waves import reset_waves, wave_stats
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    # a short tick keeps waves frequent (many gap samples); queue
    # depth 2 lets assembly genuinely run ahead in the pipelined phase
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_WAVES": "1",
        "GSKY_WAVE_MAX": "8",
        "GSKY_WAVE_TICK_MS": "10",
        "GSKY_WAVE_QUEUE": "2",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    saved_env["GSKY_WAVE_PIPELINE"] = \
        os.environ.get("GSKY_WAVE_PIPELINE")
    os.environ.update(env_overrides)
    try:
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        grid = 6
        frac = np.linspace(0.0, 0.6, grid)
        frac_y = np.linspace(0.1, 0.6, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac_y]
        w = merc.width * 0.2

        def getmap_url(fx: float, fy: float) -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat_burst"
                    f"&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        ll = transform_bbox(merc, EPSG3857, EPSG4326)
        d = 0.03
        x0 = ll.xmin + 0.35 * (ll.xmax - ll.xmin)
        y0 = ll.ymax - 0.25 * (ll.ymax - ll.ymin)
        geom = json.dumps({
            "type": "FeatureCollection", "features": [{
                "type": "Feature", "geometry": {
                    "type": "Polygon", "coordinates": [[
                        [x0, y0], [x0 + d, y0], [x0 + d, y0 + d],
                        [x0, y0 + d], [x0, y0]]]}}]})
        drill_q = urllib.parse.quote(geom)
        drill_url = (f"http://{host}/ows?service=WPS&request=Execute"
                     f"&identifier=geometryDrill"
                     f"&datainputs=geometry={drill_q}")

        lock = threading.Lock()
        errors: list = []

        def fetch(url: str, kind: str) -> bool:
            try:
                with urllib.request.urlopen(url, timeout=180) as r:
                    body = r.read()
                    if r.status != 200:
                        return False
                    if kind == "map":
                        return body[:8] == b"\x89PNG\r\n\x1a\n"
                    return b"ProcessSucceeded" in body
            except Exception as exc:   # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{kind}: {exc!r:.200}")
                return False

        def storm(seconds: float) -> dict:
            """One sustained mixed phase: free-running workers (a
            batch barrier would park its stragglers in single-entry
            waves at every lap boundary and thin the gap samples)."""
            counter = itertools.count()
            bad = [0]
            n_req = {"map": 0, "wps": 0}

            def one():
                i = next(counter)
                if i % 24 < 3:
                    kind, url = "wps", drill_url
                else:
                    kind, url = \
                        "map", getmap_url(*tiles[i % len(tiles)])
                ok = fetch(url, kind)
                with lock:
                    n_req[kind] += 1
                    if not ok:
                        bad[0] += 1

            t_end = time.time() + seconds

            def worker():
                while time.time() < t_end:
                    one()

            ths = [threading.Thread(target=worker)
                   for _ in range(max(args.conc, 12))]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            return {"http": n_req, "failed": bad[0]}

        half = max(8.0, args.seconds / 2.0)

        # phase 1 — synchronous ticker baseline.  The warm lap pays
        # scene decode + the occupancy-1 programs so neither phase's
        # gap tail is a compile artifact.
        os.environ["GSKY_WAVE_PIPELINE"] = "0"
        warm_ok = (fetch(getmap_url(*tiles[0]), "map")
                   and fetch(drill_url, "wps"))
        sync_load = storm(half)
        ws_sync = wave_stats()
        reset_waves()

        # phase 2 — pipelined ticker, fresh scheduler (its gap
        # histogram must not inherit the baseline's samples)
        os.environ["GSKY_WAVE_PIPELINE"] = "1"
        warm_ok = warm_ok and fetch(getmap_url(*tiles[1]), "map")
        pipe_load = storm(half)
        ws_pipe = wave_stats()

        # every page the storm pinned must be back
        from gsky_tpu.pipeline import pages
        pinned = -1
        t_end = time.time() + 15
        while time.time() < t_end:
            pool = pages._default
            pinned = (pool.stats().get("pinned", -1)
                      if pool is not None else 0)
            if pinned == 0:
                break
            time.sleep(0.5)

        metrics = check_metrics(host, require=(
            "gsky_requests_total", "gsky_wave_dispatches_total",
            "gsky_wave_gap_ms", "gsky_wave_staged_total"))
        trace_rep = slowest_trace_report(host)

        sync_p99 = ws_sync.get("gap_ms_p99", 0.0)
        pipe_p99 = ws_pipe.get("gap_ms_p99", 0.0)
        # the absolute-win guard: under 2 ms the dispatch stage is
        # already enqueueing back-to-back — a sync baseline that tiny
        # means the host, not the pipeline, was the bottleneck
        gap_ok = (pipe_p99 < sync_p99) or (0 < pipe_p99 <= 2.0)
        n_done = (sum(sync_load["http"].values())
                  + sum(pipe_load["http"].values()))
        bad_total = sync_load["failed"] + pipe_load["failed"]

        def gaps(ws):
            return {k: ws.get(k) for k in
                    ("gap_ms_p50", "gap_ms_p99", "gap_samples",
                     "device_idle_fraction", "dispatches",
                     "requests", "occupancy")}

        out = {
            "scenario": "occupancy",
            "warm_ok": warm_ok,
            "synchronous": {**sync_load, **gaps(ws_sync)},
            "pipelined": {**pipe_load, **gaps(ws_pipe),
                          "staged_waves":
                              ws_pipe.get("staged_waves", 0),
                          "staging": ws_pipe.get("staging", {})},
            "gap_p99_reduction_x": (
                round(sync_p99 / pipe_p99, 2) if pipe_p99 else None),
            "errors": errors,
            "pool_pinned": pinned,
            "metrics": metrics,
            "slowest_trace": trace_rep,
        }
        print(json.dumps(out))
        ok = (warm_ok and n_done > 0 and bad_total == 0
              and ws_sync.get("gap_samples", 0) >= 3
              and ws_pipe.get("gap_samples", 0) >= 3
              and ws_pipe.get("staged_waves", 0) >= 1
              and gap_ok
              and pinned == 0
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_mesh(args, watcher, mas_client, merc, boot) -> int:
    """Multi-chip sharded wave dispatch: a mixed GetMap + WPS-drill +
    WCS-export storm where every configured mesh layout must carry at
    least one wave across the full mesh, the injected-failure leg must
    answer 200 via per-entry failover, and GSKY_MESH=0 must return
    byte-identical tiles (see module docstring)."""
    import threading
    import urllib.parse

    import jax

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import transform_bbox
    from gsky_tpu.mesh import dispatch as mesh_dispatch
    from gsky_tpu.pipeline.waves import wave_stats
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    n_devices = len(jax.devices())
    if n_devices < 2:
        print(json.dumps({"scenario": "mesh", "skipped": True,
                          "reason": f"{n_devices} device(s); the mesh "
                          "needs >1 (set XLA_FLAGS on CPU)"}))
        print("SOAK FAILED", flush=True)
        return 1

    # interpret engages paged+wave serving on CPU; GSKY_MESH routes the
    # drained waves through the partition rules, and the operator rule
    # sends scored waves (the WCS export blocks) to the x layout so all
    # three sharded layouts carry load in one storm
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_WAVES": "1",
        "GSKY_WAVE_MAX": "8",
        "GSKY_WAVE_TICK_MS": "100",
        "GSKY_MESH": "1",
        "GSKY_MESH_RULES": "kind=scored=>x",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    mesh_dispatch.reset_mesh()
    try:
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        grid = 6
        import numpy as np
        frac = np.linspace(0.0, 0.6, grid)
        frac_y = np.linspace(0.1, 0.6, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac_y]
        w = merc.width * 0.2

        def getmap_url(fx: float, fy: float) -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat_burst"
                    f"&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        def wcs_url(fx: float, fy: float) -> str:
            ww = merc.width * 0.4
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + ww},"
                  f"{merc.ymin + fy * merc.height + ww}")
            return (f"http://{host}/ows?service=WCS"
                    f"&request=GetCoverage"
                    f"&coverage=landsat_burst&crs=EPSG:3857&bbox={bb}"
                    f"&width=512&height=512&format=GeoTIFF"
                    f"&time=2020-01-10T00:00:00.000Z")

        ll = transform_bbox(merc, EPSG3857, EPSG4326)
        d = 0.03
        x0 = ll.xmin + 0.35 * (ll.xmax - ll.xmin)
        y0 = ll.ymax - 0.25 * (ll.ymax - ll.ymin)
        geom = json.dumps({
            "type": "FeatureCollection", "features": [{
                "type": "Feature", "geometry": {
                    "type": "Polygon", "coordinates": [[
                        [x0, y0], [x0 + d, y0], [x0 + d, y0 + d],
                        [x0, y0 + d], [x0, y0]]]}}]})
        drill_q = urllib.parse.quote(geom)
        drill_url = (f"http://{host}/ows?service=WPS&request=Execute"
                     f"&identifier=geometryDrill"
                     f"&datainputs=geometry={drill_q}")

        lock = threading.Lock()
        counter = itertools.count()
        errors: list = []

        def fetch(url: str, kind: str):
            """(ok, body) — no faults run in the storm, so anything
            but a clean 200 with the right magic fails the soak."""
            try:
                with urllib.request.urlopen(url, timeout=300) as r:
                    body = r.read()
                    if r.status != 200:
                        return False, body
                    if kind == "map":
                        return body[:8] == b"\x89PNG\r\n\x1a\n", body
                    if kind == "wcs":
                        return body[:4] == b"II*\x00", body
                    return b"ProcessSucceeded" in body, body
            except Exception as exc:  # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{kind}: {exc!r:.200}")
                return False, b""

        warm_ok = (fetch(getmap_url(*tiles[0]), "map")[0]
                   and fetch(drill_url, "wps")[0]
                   and fetch(wcs_url(0.1, 0.2), "wcs")[0])

        bad = [0]
        n_req = {"map": 0, "wps": 0, "wcs": 0}

        def one():
            i = next(counter)
            # drills and exports are clustered minorities so their
            # companions share a tick and stack into multi-entry waves
            m = i % 24
            if m < 3:
                kind, url = "wps", drill_url
            elif m < 6:
                kind, url = "wcs", wcs_url(*tiles[i % len(tiles)])
            else:
                kind, url = "map", getmap_url(*tiles[i % len(tiles)])
            ok, _ = fetch(url, kind)
            with lock:
                n_req[kind] += 1
                if not ok:
                    bad[0] += 1

        conc = max(args.conc, 12)
        t_end = time.time() + args.seconds

        def storm_worker():
            while time.time() < t_end:
                one()

        storm = [threading.Thread(target=storm_worker)
                 for _ in range(conc)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()

        mesh_st = mesh_dispatch.mesh_stats()
        layouts = dict(mesh_st.get("waves_by_layout") or {})

        # -- failover leg: the dispatcher itself fails, every request
        # must still answer 200 through the per-entry percall leg
        md = mesh_dispatch._dispatcher()
        fb0 = wave_stats().get("fallbacks", 0)
        inject = [0]

        def boom(sched, kind, es):
            inject[0] += 1
            raise RuntimeError("soak: injected mesh dispatch failure")

        md.dispatch_wave = boom       # instance attr shadows the class
        failover_bad = [0]
        try:
            def failover_one(i):
                ok, _ = fetch(getmap_url(*tiles[i % len(tiles)]),
                              "map")
                if not ok:
                    with lock:
                        failover_bad[0] += 1
            fts = [threading.Thread(target=failover_one, args=(i,))
                   for i in range(6)]
            for t in fts:
                t.start()
            for t in fts:
                t.join()
        finally:
            del md.dispatch_wave
        fallbacks = wave_stats().get("fallbacks", 0) - fb0

        # -- escape hatch: the same tile with GSKY_MESH=0 must be
        # byte-identical (gateway off — no response cache in the loop)
        url_id = getmap_url(*tiles[1])
        ok_a, body_a = fetch(url_id, "map")
        os.environ["GSKY_MESH"] = "0"
        ok_b, body_b = fetch(url_id, "map")
        os.environ["GSKY_MESH"] = "1"
        byte_identical = bool(ok_a and ok_b and body_a == body_b)

        from gsky_tpu.pipeline import pages
        pinned = -1
        t_end = time.time() + 15
        while time.time() < t_end:
            pool = pages._default
            pinned = (pool.stats().get("pinned", -1)
                      if pool is not None else 0)
            if pinned == 0:
                break
            time.sleep(0.5)

        metrics = check_metrics(host, require=(
            "gsky_requests_total",
            "gsky_wave_dispatches_total",
            "gsky_mesh_waves_total", "gsky_mesh_chips",
            "gsky_mesh_chip_occupancy", "gsky_mesh_shard_skew_ms"))

        n_done = sum(n_req.values())
        out = {
            "scenario": "mesh",
            "devices": n_devices,
            "warm_ok": warm_ok,
            "requests": n_req, "failed": bad[0],
            "errors": errors,
            "mesh": mesh_st,
            "layout_waves": layouts,
            "failover": {"injected": inject[0],
                         "fallbacks": fallbacks,
                         "failed": failover_bad[0]},
            "escape_hatch_byte_identical": byte_identical,
            "pool_pinned": pinned,
            "metrics": metrics,
        }
        print(json.dumps(out))
        ok = (warm_ok and n_done > 0 and bad[0] == 0
              and mesh_st.get("chips") == n_devices
              and all(layouts.get(lay, 0) >= 1
                      for lay in ("granule", "time", "x"))
              and inject[0] >= 1 and fallbacks >= 1
              and failover_bad[0] == 0
              and byte_identical
              and pinned == 0
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        mesh_dispatch.reset_mesh()


def run_plan(args, watcher, mas_client, merc, boot) -> int:
    """Dataflow autoplanner: an adjacent-tile GetMap pan-walk storm
    whose overlapping gather windows must merge into shared-halo
    superblocks (gather-dedup ratio > 0), with a streamed WCS-export
    minority riding the same waves, byte parity vs GSKY_PLAN=0, and
    zero pinned pages at exit (see module docstring)."""
    import threading

    import numpy as np

    from gsky_tpu.ops import paged
    from gsky_tpu.pipeline import autoplan
    from gsky_tpu.pipeline.waves import wave_stats
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    # interpret engages paged+wave serving on CPU; a wide tick gives
    # concurrent adjacent tiles a real coalescing window, and a raised
    # slot cap leaves the planner union-table headroom (a merged pair
    # of neighbouring windows needs more page slots than either tile —
    # 16 slots of the default 128x512 page is 4 MiB, well under VMEM)
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_WAVES": "1",
        "GSKY_WAVE_MAX": "8",
        "GSKY_WAVE_TICK_MS": "100",
        "GSKY_PLAN": "1",
        "GSKY_PAGE_SLOTS": "16",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    autoplan.reset_plan_state()
    paged.reset_gather_bytes()
    try:
        # gateway off: a response-cache hit would bypass the pipeline
        # and the dedup ratio would measure the cache, not the planner
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        # pan-walk lattice: windows 12% of the cluster span stepping
        # by 4% — each tile overlaps its neighbour by two thirds, so
        # tiles landing in one wave tick have adjacent page windows
        # the planner can union under the halo cap.  The y rows start
        # high enough to stay on data (scenes anchor at ymax)
        w = merc.width * 0.12
        xs = np.arange(0.0, 0.60, 0.04)
        ys = (0.15, 0.19, 0.35, 0.39)
        tiles = [(float(fx), float(fy)) for fy in ys for fx in xs]

        def getmap_url(fx: float, fy: float) -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat_burst"
                    f"&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        def wcs_url(fx: float, fy: float) -> str:
            ww = merc.width * 0.3
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + ww},"
                  f"{merc.ymin + fy * merc.height + ww}")
            return (f"http://{host}/ows?service=WCS"
                    f"&request=GetCoverage"
                    f"&coverage=landsat_burst&crs=EPSG:3857&bbox={bb}"
                    f"&width=512&height=512&format=GeoTIFF"
                    f"&time=2020-01-10T00:00:00.000Z")

        lock = threading.Lock()
        counter = itertools.count()
        errors: list = []

        def fetch(url: str, kind: str):
            """(ok, body) — no faults run in this scenario, so
            anything but a clean 200 with the right magic fails."""
            try:
                with urllib.request.urlopen(url, timeout=300) as r:
                    body = r.read()
                    if r.status != 200:
                        return False, body
                    if kind == "map":
                        return body[:8] == b"\x89PNG\r\n\x1a\n", body
                    return body[:4] == b"II*\x00", body
            except Exception as exc:  # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{kind}: {exc!r:.200}")
                return False, b""

        warm_ok = (fetch(getmap_url(*tiles[0]), "map")[0]
                   and fetch(wcs_url(0.1, 0.2), "wcs")[0])

        bad = [0]
        n_req = {"map": 0, "wcs": 0}

        def one():
            i = next(counter)
            # exports are a clustered minority; the map majority walks
            # the pan lattice so simultaneous arrivals are neighbours
            if i % 16 < 2:
                kind, url = "wcs", wcs_url(*tiles[i % len(tiles)])
            else:
                kind, url = "map", getmap_url(*tiles[i % len(tiles)])
            ok, _ = fetch(url, kind)
            with lock:
                n_req[kind] += 1
                if not ok:
                    bad[0] += 1

        conc = max(args.conc, 16)
        t_end = time.time() + args.seconds

        def storm_worker():
            while time.time() < t_end:
                one()

        storm = [threading.Thread(target=storm_worker)
                 for _ in range(conc)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()

        st = autoplan.plan_stats()
        gathered = paged.gather_bytes_total()
        saved = st.get("gather_bytes_saved", 0)
        dedup_ratio = saved / max(saved + gathered, 1)

        # -- escape hatch: the SAME concurrent adjacent-tile volley
        # with the planner off must be byte-identical — the plan-on
        # volley is fired concurrently so its entries actually share a
        # wave and can merge, making the parity claim non-trivial
        probe = tiles[1:5]

        def volley():
            bodies: list = [None] * len(probe)

            def grab(k, t):
                bodies[k] = fetch(getmap_url(*t), "map")[1]
            ths = [threading.Thread(target=grab, args=(k, t))
                   for k, t in enumerate(probe)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            return bodies

        bodies_on = volley()
        os.environ["GSKY_PLAN"] = "0"
        bodies_off = volley()
        os.environ["GSKY_PLAN"] = "1"
        byte_identical = (all(b for b in bodies_on)
                          and bodies_on == bodies_off)

        # every page the storm pinned must be back once waves drain
        from gsky_tpu.pipeline import pages
        pinned = -1
        t_end = time.time() + 15
        while time.time() < t_end:
            pool = pages._default
            pinned = (pool.stats().get("pinned", -1)
                      if pool is not None else 0)
            if pinned == 0:
                break
            time.sleep(0.5)

        ws = wave_stats()
        metrics = check_metrics(host, require=(
            "gsky_requests_total", "gsky_wave_dispatches_total",
            "gsky_plan_superblocks_total",
            "gsky_plan_gather_bytes_saved_total",
            "gsky_plan_block_shape", "gsky_plan_route_total"))

        n_done = sum(n_req.values())
        out = {
            "scenario": "plan",
            "warm_ok": warm_ok,
            "requests": n_req, "failed": bad[0],
            "errors": errors,
            "plan": st,
            "gathered_bytes": gathered,
            "dedup_ratio": round(dedup_ratio, 4),
            "escape_hatch_byte_identical": byte_identical,
            "pool_pinned": pinned,
            "waves": {"dispatches": ws.get("dispatches", 0),
                      "requests": ws.get("requests", 0)},
            "metrics": metrics,
        }
        print(json.dumps(out))
        ok = (warm_ok and n_done > 0 and bad[0] == 0
              and st.get("superblocks", 0) >= 1
              and st.get("merged_lanes", 0) >= 1
              and dedup_ratio > 0
              and byte_identical
              and pinned == 0
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        autoplan.reset_plan_state()


def run_fabric(args, watcher, mas_client, merc, boot) -> int:
    """Cache fabric: two gateway replicas on the replay ring over
    three page-peered worker nodes; gateway death -> cold replica
    recovers from the survivor's bytes, worker death -> warm-boot
    refill from page peers, plus a GSKY_FABRIC=0 byte-identity leg
    (see module docstring for the pass criteria)."""
    import socket
    import subprocess
    import threading

    import numpy as np

    import grpc

    from gsky_tpu.fabric.replay import ReplayFabric
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.serving import ServingGateway
    from gsky_tpu.worker import gskyrpc_pb2 as pb
    from gsky_tpu.worker.server import METHOD

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf_dir = watcher.root
    data_root = os.path.dirname(conf_dir)
    journal = os.path.join(data_root, "fabric-journal.jsonl")
    # gateway-side gates (the gateways run in THIS process); the
    # explicit ReplayFabric instances below carry the per-replica ring.
    # The journal + interpret-mode pallas make the in-process paged
    # pipeline stage pages worth peering (same recipe as devicechaos).
    os.environ["GSKY_FABRIC"] = "1"
    os.environ["GSKY_POOL_JOURNAL"] = journal
    os.environ.setdefault("GSKY_PALLAS", "interpret")

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    procs: dict = {}
    ports = [free_port() for _ in range(3)]
    nodes = [f"127.0.0.1:{p}" for p in ports]

    def spawn(port: int, page_peers: str = ""):
        # every worker shares one journal; page peers are config-driven
        peers = page_peers or ",".join(
            n for n in nodes if n != f"127.0.0.1:{port}")
        e = dict(os.environ, PYTHONPATH=repo,
                 GSKY_FABRIC="1", GSKY_FABRIC_PAGE_PEERS=peers,
                 GSKY_POOL_JOURNAL=journal)
        e.setdefault("JAX_PLATFORMS", "cpu")
        logf = open(os.path.join(data_root, f"fab-{port}.log"), "ab")
        procs[port] = subprocess.Popen(
            [sys.executable, "-m", "gsky_tpu.worker.server",
             "-p", str(port), "-host", "127.0.0.1",
             "-n", "1", "-oom_threshold", "0"],
            env=e, cwd=repo, stdout=logf, stderr=subprocess.STDOUT)
        logf.close()                     # child holds its own fd

    def stub_for(port: int):
        ch = grpc.insecure_channel(f"127.0.0.1:{port}")
        return ch, ch.unary_unary(
            METHOD, request_serializer=pb.Task.SerializeToString,
            response_deserializer=pb.Result.FromString)

    def wait_ready(port: int, deadline_s: float) -> bool:
        # fresh channel per attempt: see run_fleet's wait_ready
        t_end = time.time() + deadline_s
        while time.time() < t_end:
            if procs[port].poll() is not None:
                return False
            ch, stub = stub_for(port)
            try:
                stub(pb.Task(operation="worker_info"), timeout=2.0)
                return True
            except Exception:
                time.sleep(0.5)
            finally:
                ch.close()
        return False

    def pages_stats(port: int) -> dict:
        ch, stub = stub_for(port)
        try:
            res = stub(pb.Task(operation="worker_info"), timeout=5.0)
            return json.loads(res.info_json or "{}").get("pages", {})
        except Exception:
            return {}
        finally:
            ch.close()

    try:
        for p in ports:
            spawn(p)
        boot_deadline = time.time() + 600
        for p in ports:
            if not wait_ready(p, max(boot_deadline - time.time(), 1.0)):
                print(json.dumps({"scenario": "fabric",
                                  "error": f"node :{p} never came up"}))
                print("SOAK FAILED", flush=True)
                return 1

        ns_dir = os.path.join(conf_dir, "fabric")
        os.makedirs(ns_dir, exist_ok=True)
        with open(os.path.join(ns_dir, "config.json"), "w") as fp:
            json.dump({
                "service_config": {"ows_hostname": "", "mas_address": "",
                                   "worker_nodes": nodes},
                "layers": [{
                    "name": "landsat_fabric", "title": "fabric soak",
                    "data_source": data_root,
                    "rgb_products": [f"LC08_20200{110 + k}_T1"
                                     for k in range(N_SCENES)],
                    "time_generator": "mas",
                    "wms_timeout": 120,
                    "wcs_max_width": 4096, "wcs_max_height": 4096,
                    "wcs_max_tile_width": 256,
                    "wcs_max_tile_height": 256}],
            }, fp)
        watcher.reload()

        def gateway(fab) -> "OWSServer":
            return OWSServer(watcher, mas_factory=lambda a: mas_client,
                             metrics=MetricsLogger(),
                             gateway=ServingGateway(), fabric=fab)

        # the ring wants each replica's address before it exists; boot
        # with placeholders, then rewire membership (generation bump
        # included — exactly what a real redeploy does)
        fab_a = ReplayFabric("http://pending-a", [])
        fab_b = ReplayFabric("http://pending-b", [])
        host_a = boot(gateway(fab_a))
        host_b = boot(gateway(fab_b))
        url_a, url_b = f"http://{host_a}", f"http://{host_b}"
        fab_a.self_addr = url_a
        fab_a.set_peers([url_b])
        fab_b.self_addr = url_b
        fab_b.set_peers([url_a])

        grid = 4
        frac = np.linspace(0.0, 0.75, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac]
        w = merc.width * 0.25
        rng = np.random.default_rng(7)
        ranks = (rng.zipf(1.2, size=100_000) - 1) % len(tiles)

        def url_for(host: str, k: int) -> str:
            fx, fy = tiles[k]
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows/fabric?service=WMS"
                    f"&request=GetMap&version=1.3.0"
                    f"&layers=landsat_fabric&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        def fetchc(url: str):
            """(class, X-Gsky-Cache, body)."""
            try:
                with urllib.request.urlopen(url, timeout=180) as r:
                    return ("ok", r.headers.get("X-Gsky-Cache", ""),
                            r.read())
            except urllib.error.HTTPError as e:
                ctype = e.headers.get("Content-Type", "")
                e.read()
                if e.code == 500 or "vnd.ogc.se_xml" not in ctype:
                    return "hard_5xx", "", b""
                return "ogc_error", "", b""
            except Exception:
                return "transport", "", b""

        # warm: first warp on each node pays jax import + XLA compiles
        warm_end = time.time() + 420
        while time.time() < warm_end:
            if fetchc(url_for(host_a, 0))[0] == "ok":
                break
            time.sleep(2.0)

        # phase A: Zipf storm alternating gateways — both caches fill,
        # non-owner misses replay across the ring as they go
        counts: dict = {}
        cache_outcomes: dict = {}
        counter = itertools.count()
        lock = threading.Lock()

        def one(_):
            i = next(counter)
            host = host_a if i % 2 == 0 else host_b
            c, src, _body = fetchc(url_for(host, int(ranks[i % len(ranks)])))
            with lock:
                counts[c] = counts.get(c, 0) + 1
                if src:
                    cache_outcomes[src] = cache_outcomes.get(src, 0) + 1

        conc = min(args.conc, 4)
        t_end = time.time() + max(args.seconds * 0.5, 8.0)
        with cf.ThreadPoolExecutor(conc) as ex:
            while time.time() < t_end:
                list(ex.map(one, range(conc * 2)))

        # every hot tile must be resident on gateway B (the survivor)
        # before A dies, or the recovery phase measures luck instead of
        # the fabric
        for k in range(len(tiles)):
            fetchc(url_for(host_b, k))

        # phase B: gateway A "dies"; a cold replica takes its place.
        # its empty cache must refill from B's bytes over the ring, not
        # from re-renders
        fab_a2 = ReplayFabric("http://pending-a2", [])
        host_a2 = boot(gateway(fab_a2))
        fab_a2.self_addr = f"http://{host_a2}"
        fab_a2.set_peers([url_b])
        fab_b.set_peers([f"http://{host_a2}"])   # B re-homes too
        recovery_counts: dict = {}
        peer_served = 0
        for k in list(range(len(tiles))) * 2:
            c, src, _body = fetchc(url_for(host_a2, k))
            recovery_counts[c] = recovery_counts.get(c, 0) + 1
            if src == "peer":
                peer_served += 1
        a2 = fab_a2.stats()["outcomes"]
        probed = (a2.get("hit", 0) + a2.get("miss", 0)
                  + a2.get("error", 0))
        replay_rate = a2.get("hit", 0) / max(probed, 1)

        # phase C: page peering.  The paged pipeline stages pool pages
        # wherever COMPOSITES run — the worker-less default namespace
        # renders in this process — so seed the local pool + shared
        # journal with a lap of /ows renders, expose the pool over the
        # real worker RPC front door, then SIGKILL a worker and require
        # its replacement's warm boot to refill over page-fetch RPC
        # (hottest-first, CRC-checked) instead of cold staging.
        from gsky_tpu.pipeline import pages as _pages
        from gsky_tpu.worker.server import WorkerService, \
            make_grpc_server

        def seed_url(k: int) -> str:
            fx, fy = tiles[k]
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host_b}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat&crs=EPSG:3857"
                    f"&bbox={bb}&width=256&height=256"
                    f"&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        for k in list(range(len(tiles))) * 2:   # twice: stage + heat
            fetchc(seed_url(k))
        seeded = _pages._default.stats() if _pages._default else {}

        peer_port = free_port()
        peer_svc = WorkerService(pool_size=1)
        peer_srv = make_grpc_server(peer_svc,
                                    f"127.0.0.1:{peer_port}")
        peer_srv.start()
        try:
            kill_port = ports[2]
            procs[kill_port].kill()
            procs[kill_port].wait()
            spawn(kill_port,
                  page_peers=f"127.0.0.1:{peer_port}")
            worker_back = wait_ready(kill_port, 300)
            refill: dict = {}
            if worker_back:
                t_end = time.time() + 90
                while time.time() < t_end:
                    refill = pages_stats(kill_port)
                    if refill.get("peer_filled", 0) > 0:
                        break
                    time.sleep(1.0)
                # the poll breaks on the FIRST fill, mid-rehydrate:
                # let the warm boot finish before judging the ratio
                time.sleep(3.0)
                refill = pages_stats(kill_port) or refill
        finally:
            peer_srv.stop(0)
        peer_filled = refill.get("peer_filled", 0)
        rehydrated = refill.get("rehydrated", 0)

        # phase D: the escape hatch.  GSKY_FABRIC=0 must be
        # byte-identical to a fabric-less server, and the fabric object
        # must never probe a peer
        os.environ["GSKY_FABRIC"] = "0"
        try:
            fab_off = ReplayFabric("http://off", [url_b])
            host_off = boot(gateway(fab_off))
            host_plain = boot(gateway(None))
            c_off, src_off, body_off = fetchc(url_for(host_off, 0))
            c_plain, _src, body_plain = fetchc(url_for(host_plain, 0))
            identical = (c_off == c_plain == "ok"
                         and body_off == body_plain
                         and len(body_off) > 0)
            off_outcomes = fab_off.stats()["outcomes"]
            off_dormant = set(off_outcomes) <= {"disabled"}
        finally:
            os.environ["GSKY_FABRIC"] = "1"

        # observability: strict exposition parse with the fabric
        # families present, and the /debug fabric block
        metrics = check_metrics(
            host_b, require=("gsky_requests_total",
                             "gsky_fabric_replay_total",
                             "gsky_fabric_page_fills_total"))
        with urllib.request.urlopen(f"http://{host_b}/debug",
                                    timeout=30) as r:
            debug_fabric = json.loads(r.read()).get("fabric")

        out = {
            "scenario": "fabric", "nodes": nodes,
            "gateways": [host_a, host_b, host_a2],
            "storm": counts, "storm_cache": cache_outcomes,
            "recovery": recovery_counts,
            "recovery_peer_served": peer_served,
            "recovery_replay": {"outcomes": a2,
                                "rate": round(replay_rate, 3)},
            "worker_refill": {"back": worker_back,
                              "seeded": seeded.get("staged", 0),
                              "peer_filled": peer_filled,
                              "rehydrated": rehydrated},
            "fabric_off": {"identical": identical,
                           "outcomes": off_outcomes},
            "metrics": metrics,
            "debug_fabric": bool(debug_fabric),
        }
        print(json.dumps(out))
        hard = sum(d.get(k, 0) for d in (counts, recovery_counts)
                   for k in ("hard_5xx", "transport"))
        ok = (counts.get("ok", 0) > 0
              and hard == 0
              and recovery_counts.get("ok", 0) > 0
              # >= half of the peer-owned hot set came back as replays
              and a2.get("hit", 0) > 0
              and peer_served > 0
              and replay_rate >= 0.5
              # >= half of the worker's warm refill came from peers
              and worker_back
              and seeded.get("staged", 0) > 0
              and peer_filled > 0
              and peer_filled >= rehydrated - peer_filled
              and identical and off_dormant
              and not metrics["missing"]
              and bool(debug_fabric))
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for p, proc in procs.items():
            try:
                proc.kill()
            except Exception:  # process already exited
                pass


def run_elastic(args, watcher, mas_client, merc, boot) -> int:
    """Elastic fleet: the autoscaler control loop over a preemptible
    local-subprocess fleet — load ramp -> readiness-gated scale-up,
    two mid-ramp preemptions with a short grace (drain + scored
    journal handoff + >= 50% peer page refill), floor refill, quiet
    trickle -> scale-down, and a GSKY_ELASTIC=0 byte-identity leg
    (see module docstring for the pass criteria)."""
    import gc
    import threading

    import numpy as np

    from gsky_tpu.fleet import elastic
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.serving import ServingGateway
    from gsky_tpu.worker.server import WorkerService, make_grpc_server

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf_dir = watcher.root
    data_root = os.path.dirname(conf_dir)
    journal = os.path.join(data_root, "elastic-journal.jsonl")
    # same fabric recipe as the fabric scenario: shared pool journal +
    # interpret-mode pallas make pages worth handing off; 1s probes so
    # the monitor sees a draining node within a couple of beats
    os.environ["GSKY_FABRIC"] = "1"
    os.environ["GSKY_POOL_JOURNAL"] = journal
    os.environ.setdefault("GSKY_PALLAS", "interpret")
    os.environ["GSKY_ELASTIC"] = "1"
    os.environ.setdefault("GSKY_FLEET_PROBE_S", "1.0")
    os.environ.setdefault("GSKY_FLEET_BOUND", "2.5")

    # an in-process page server fronts THIS process's page pool (the
    # worker-less default namespace renders here and stages the seed
    # set) so handoff refills and warm boots have a live page peer
    peer_port = elastic.LocalSubprocessProvider.free_port()
    peer_addr = f"127.0.0.1:{peer_port}"

    provider = elastic.LocalSubprocessProvider(
        extra_env={"PYTHONPATH": repo, "JAX_PLATFORMS": "cpu",
                   "GSKY_FABRIC": "1", "GSKY_POOL_JOURNAL": journal,
                   "GSKY_PALLAS": os.environ["GSKY_PALLAS"],
                   "GSKY_FABRIC_PAGE_PEERS": peer_addr},
        pool_size=1, log_dir=data_root)
    autoscaler = None
    peer_srv = None
    try:
        initial = [provider.launch() for _ in range(2)]
        boot_deadline = time.time() + 600
        for addr in initial:
            while time.time() < boot_deadline:
                if not provider.alive(addr):
                    break
                if elastic.probe_info(addr) is not None:
                    break
                time.sleep(0.5)
            if elastic.probe_info(addr) is None:
                print(json.dumps({"scenario": "elastic",
                                  "error": f"{addr} never came up"}))
                print("SOAK FAILED", flush=True)
                return 1

        ns_dir = os.path.join(conf_dir, "elastic")
        os.makedirs(ns_dir, exist_ok=True)
        with open(os.path.join(ns_dir, "config.json"), "w") as fp:
            json.dump({
                "service_config": {"ows_hostname": "", "mas_address": "",
                                   "worker_nodes": initial},
                "layers": [{
                    "name": "landsat_elastic", "title": "elastic soak",
                    "data_source": data_root,
                    "rgb_products": [f"LC08_20200{110 + k}_T1"
                                     for k in range(N_SCENES)],
                    "time_generator": "mas",
                    "wms_timeout": 120,
                    "wcs_max_width": 4096, "wcs_max_height": 4096,
                    "wcs_max_tile_width": 256,
                    "wcs_max_tile_height": 256}],
            }, fp)
        watcher.reload()

        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(),
                           gateway=ServingGateway())
        host = boot(server)

        grid = 3
        frac = np.linspace(0.0, 0.75, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac]
        w = merc.width * 0.25

        def bbox_for(fx: float, fy: float) -> str:
            return (f"{merc.xmin + fx * merc.width},"
                    f"{merc.ymin + fy * merc.height},"
                    f"{merc.xmin + fx * merc.width + w},"
                    f"{merc.ymin + fy * merc.height + w}")

        def url_for(fx: float, fy: float, salt: int = 0) -> str:
            # salt shifts the bbox in steps of ~2 response-cache quanta
            # (the key quantises to 1/256 px — see quantise_bbox): every
            # driven request is a distinct cache key, so the load
            # reaches the worker fleet and the demand signal sees it,
            # while the few-pixel drift stays on the same staged pages
            step = 0.25 / 256.0 / 128.0
            fx += (salt % 997) * step
            fy += (salt // 997 % 997) * step
            return (f"http://{host}/ows/elastic?service=WMS"
                    f"&request=GetMap&version=1.3.0"
                    f"&layers=landsat_elastic&crs=EPSG:3857"
                    f"&bbox={bbox_for(fx, fy)}&width=256&height=256"
                    f"&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        def seed_url(fx: float, fy: float) -> str:
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat&crs=EPSG:3857"
                    f"&bbox={bbox_for(fx, fy)}&width=256&height=256"
                    f"&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        def fetch(url: str):
            """(class, body)."""
            try:
                with urllib.request.urlopen(url, timeout=180) as r:
                    return "ok", r.read()
            except urllib.error.HTTPError as e:
                ctype = e.headers.get("Content-Type", "")
                e.read()
                if e.code == 500 or "vnd.ogc.se_xml" not in ctype:
                    return "hard_5xx", b""
                return "ogc_error", b""
            except Exception:
                return "transport", b""

        # warm: first warp on each node pays jax import + XLA compiles
        warm_end = time.time() + 420
        while time.time() < warm_end:
            if fetch(url_for(*tiles[0]))[0] == "ok":
                break
            time.sleep(2.0)

        # seed the in-process pool + shared journal (twice: stage +
        # heat), then expose it over the real page-fetch RPC
        for fx, fy in tiles * 2:
            fetch(seed_url(fx, fy))
        peer_svc = WorkerService(pool_size=1)
        peer_srv = make_grpc_server(peer_svc, f"127.0.0.1:{peer_port}")
        peer_srv.start()

        # the gateway's WorkerClient for the elastic namespace IS the
        # routing surface being scaled
        client = None
        for _settings, pipe in server._pipelines.values():
            if pipe.remote is not None:
                client = pipe.remote
        assert client is not None, "elastic namespace never dispatched"

        autoscaler = elastic.Autoscaler(
            provider, client, name="soak",
            min_nodes=2, max_nodes=4, interval_s=0.5,
            up=0.5, down=0.2, up_ticks=2, down_ticks=4,
            cooldown_s=4.0, ready_timeout_s=150.0, drain_grace_s=8.0,
            demand=elastic.DemandSignal(
                admission=server.gateway.admission,
                # per-node target of 1: soak renders are page-cache
                # warm, so the worker RPC is a small slice of each
                # request's wall time and sampled in-flight stays low
                router=client.fleet, node_conc=1))
        autoscaler.start()

        counts: dict = {}
        lats: dict = {"ramp": [], "preempt": [], "steady": []}
        lock = threading.Lock()
        counter = itertools.count()   # shared: no URL repeats across phases

        def drive_bg(conc: int, phase: str):
            """Background load at fixed concurrency until stopped."""
            stop_ev = threading.Event()

            def one(_):
                i = next(counter)
                t0 = time.time()
                c, _b = fetch(url_for(*tiles[i % len(tiles)], salt=i))
                dt = time.time() - t0
                with lock:
                    counts[c] = counts.get(c, 0) + 1
                    if c == "ok":
                        lats[phase].append(dt)

            def loop():
                with cf.ThreadPoolExecutor(conc) as ex:
                    while not stop_ev.is_set():
                        list(ex.map(one, range(conc)))

            th = threading.Thread(target=loop, daemon=True)
            th.start()
            return stop_ev, th

        def wait_for(pred, timeout_s: float) -> bool:
            t_end = time.time() + timeout_s
            while time.time() < t_end:
                if pred():
                    return True
                time.sleep(1.0)
            return bool(pred())

        def joined() -> int:
            return sum(1 for d in autoscaler.decisions
                       if d["dir"] == "join")

        # phase A: ramp — double traffic twice; the demand signal must
        # cross the scale-up threshold and launch
        ev, th = drive_bg(2, "ramp")
        time.sleep(max(args.seconds * 0.1, 4.0))
        ev.set()
        th.join(30)
        ev, th = drive_bg(4, "ramp")
        time.sleep(max(args.seconds * 0.1, 4.0))
        ev.set()
        th.join(30)
        ev, th = drive_bg(8, "ramp")
        up_seen = wait_for(
            lambda: any(d["dir"] == "up" for d in autoscaler.decisions),
            60.0)
        # keep ramp load on while the launch boots; membership join is
        # gated on the warm-readiness probe
        join_seen = wait_for(lambda: joined() >= 1, 300.0)
        ev.set()
        th.join(30)

        # phase B: two preemptions mid-ramp, short grace, explicit
        # successor.  Load stays on — every response must stay clean
        ev, th = drive_bg(4, "preempt")
        handoff_notes = []
        for victim in initial:
            # the victim must leave a live successor behind: wait for
            # at least two ACTIVE members (joins, not just launches)
            wait_for(lambda: len(client.nodes) >= 2, 300.0)
            live = list(client.nodes)
            if victim not in live:
                break
            succ = client.fleet.ring.successor(victim) or \
                next((n for n in live if n != victim), None)
            peers = [n for n in live if n != victim] + [peer_addr]
            noticed = provider.preempt(victim, 6.0, successor=succ,
                                       peers=peers)
            gone = wait_for(lambda: victim not in client.nodes, 60.0)
            handoff_notes.append({"victim": victim, "successor": succ,
                                  "noticed": noticed, "purged": gone})
        # recovery: the fleet must be back at (or above) the floor,
        # with >= 3 nodes so the quiet phase has something to shed
        refilled = wait_for(lambda: len(client.nodes) >= 2, 300.0)
        wait_for(lambda: len(client.nodes) >= 3, 240.0)
        ev.set()
        th.join(30)

        # aggregate the warm-handoff outcome across the surviving fleet
        def handoff_totals() -> dict:
            tot = {"entries": 0, "filled": 0, "cold": 0, "active": 0}
            for n in list(client.nodes):
                info = elastic.probe_info(n) or {}
                h = (info.get("elastic") or {}).get("handoff") or {}
                for k in tot:
                    tot[k] += int(h.get(k, 0))
            return tot

        wait_for(lambda: (handoff_totals()["entries"] > 0
                          and handoff_totals()["active"] == 0), 90.0)
        handoff = handoff_totals()

        # phase C: steady load on the recovered fleet (the p99 sample),
        # then a quiet trickle that must produce a scale-down
        ev, th = drive_bg(4, "steady")
        time.sleep(max(args.seconds * 0.2, 8.0))
        ev.set()
        th.join(30)
        down_seen = wait_for(
            lambda: any(d["dir"] == "down"
                        for d in autoscaler.decisions), 120.0)

        # observability while the subsystem is live: strict exposition
        # parse with the elastic families, and the /debug block
        metrics = check_metrics(
            host, require=("gsky_requests_total",
                           "gsky_elastic_nodes",
                           "gsky_elastic_decisions_total",
                           "gsky_preemptions_total",
                           "gsky_handoff_pages_total"))
        with urllib.request.urlopen(f"http://{host}/debug",
                                    timeout=30) as r:
            debug_elastic = json.loads(r.read()).get("elastic")

        decisions = list(autoscaler.decisions)
        counters = elastic.counters()
        ready_joins = [d for d in decisions
                       if d["dir"] == "join" and d["reason"] == "ready"]
        autoscaler.stop()
        final_nodes = list(client.nodes)

        # phase D: the escape hatch.  GSKY_ELASTIC=0 on a fixed fleet:
        # same bytes as a server that never imported elastic, no
        # elastic families in /metrics, no /debug block
        os.environ["GSKY_ELASTIC"] = "0"
        autoscaler = None                 # WeakSet registry drops it
        elastic.reset_stats()
        gc.collect()
        # a retire thread may briefly keep the scaler referenced
        t_end = time.time() + 30
        while not elastic.dormant() and time.time() < t_end:
            time.sleep(1.0)
            elastic.reset_stats()
            gc.collect()
        host_off = boot(OWSServer(watcher,
                                  mas_factory=lambda a: mas_client,
                                  metrics=MetricsLogger(),
                                  gateway=None))
        host_plain = boot(OWSServer(watcher,
                                    mas_factory=lambda a: mas_client,
                                    metrics=MetricsLogger(),
                                    gateway=None))
        su = seed_url(*tiles[0])
        c_off, body_off = fetch(su.replace(f"http://{host}",
                                           f"http://{host_off}"))
        c_plain, body_plain = fetch(su.replace(f"http://{host}",
                                               f"http://{host_plain}"))
        identical = (c_off == c_plain == "ok"
                     and body_off == body_plain and len(body_off) > 0)
        with urllib.request.urlopen(f"http://{host_off}/metrics",
                                    timeout=30) as r:
            off_expo = r.read().decode()
        with urllib.request.urlopen(f"http://{host_off}/debug",
                                    timeout=30) as r:
            off_debug = json.loads(r.read())
        off_dormant = ("gsky_elastic" not in off_expo
                       and "gsky_preemptions" not in off_expo
                       and "elastic" not in off_debug)

        p99_budget_s = 90.0
        p99 = {ph: (round(float(np.percentile(v, 99)), 3) if v
                    else None) for ph, v in lats.items()}
        out = {
            "scenario": "elastic", "initial": initial,
            "final_nodes": final_nodes,
            "responses": counts, "p99_s": p99,
            "decisions": [{k: d.get(k) for k in
                           ("dir", "reason", "node")}
                          for d in decisions],
            "counters": counters,
            "handoff": handoff, "handoff_notes": handoff_notes,
            "ready_joins": len(ready_joins),
            "elastic_off": {"identical": identical,
                            "dormant": off_dormant},
            "metrics": metrics,
            "debug_elastic": bool(debug_elastic),
        }
        print(json.dumps(out))
        ok = (counts.get("ok", 0) > 0
              and counts.get("hard_5xx", 0) == 0
              and counts.get("transport", 0) == 0
              and up_seen and join_seen and down_seen
              and counters["decisions"]["up"] >= 1
              and counters["decisions"]["down"] >= 1
              # readiness gate observed: at least one join waited for
              # the warm probe rather than the deadline
              and len(ready_joins) >= 1
              and all(n["noticed"] and n["purged"]
                      for n in handoff_notes)
              and len(handoff_notes) == 2
              # both injected preemptions observed; at least one was
              # seen in its draining window (a starved host can miss
              # the other's probe beat and classify it dead)
              and (counters["preemptions"]["graceful"]
                   + counters["preemptions"]["nograce"]) >= 2
              and counters["preemptions"]["graceful"] >= 1
              and refilled
              # >= 50% of the inherited hot set came from peer HBM
              and handoff["entries"] > 0
              and handoff["filled"] >= handoff["cold"]
              and lats["steady"]
              and p99["steady"] is not None
              and p99["steady"] < p99_budget_s
              and identical and off_dormant
              and not metrics["missing"]
              and bool(debug_elastic))
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        if peer_srv is not None:
            peer_srv.stop(0)
        provider.close()
        os.environ["GSKY_ELASTIC"] = "0"


def run_algebra(args, watcher, mas_client, merc, boot) -> int:
    """Fused band algebra: a styled-expression GetMap storm plus a WPS
    drill minority must keep compiles bounded (the compile cache and
    structural-fingerprint sharing absorb the source variety), stay
    byte-identical under GSKY_EXPR_FUSE=0, and leave zero pinned pages
    (see module docstring for the pass criteria)."""
    import threading
    import urllib.parse

    import numpy as np

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import transform_bbox
    from gsky_tpu.ops import paged
    from gsky_tpu.ops.expr import (expr_cache_stats, fingerprint,
                                   parse_band_expressions,
                                   reset_expr_cache)
    from gsky_tpu.pipeline.waves import wave_stats
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    # interpret engages paged+wave serving on CPU; a wide tick lets
    # concurrent styled tiles with one structural fingerprint stack
    # into a single fused wave dispatch
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_WAVES": "1",
        "GSKY_WAVE_MAX": "8",
        "GSKY_WAVE_TICK_MS": "100",
        "GSKY_EXPR_FUSE": "1",
        "GSKY_PAGE_SLOTS": "16",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    reset_expr_cache()
    paged.reset_expr_fused_stats()
    paged.reset_gather_bytes()
    try:
        # gateway off: a response-cache hit would bypass the pipeline
        # and the bounded-compile claim would measure the cache
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        # the storm's source inventory comes from the shared config —
        # the soak can't drift from what the server actually serves
        cfg = next(iter(watcher.configs.values()))
        lay = cfg.layer("landsat_algebra")
        styles = [""] + [s.name for s in lay.styles]
        sources = ([lay.rgb_products[0]]
                   + [s.rgb_products[0] for s in lay.styles])
        drill_sources = list(
            cfg.process("algebraDrill").data_sources[0].rgb_products)
        n_structures = len({
            fingerprint(parse_band_expressions([s]).expressions[0]).hash
            for s in sources})
        n_sources = len(set(sources) | set(drill_sources))

        # tiles sit where BOTH referenced scenes have data (the scenes
        # anchor at ymax and step diagonally, so the pair's overlap is
        # the middle of the cluster): fused nodata semantics — valid
        # iff valid in every referenced variable — still leaves real
        # pixels on every tile
        w = merc.width * 0.12
        xs = np.arange(0.30, 0.62, 0.04)
        ys = (0.32, 0.44, 0.56, 0.68)
        tiles = [(float(fx), float(fy)) for fy in ys for fx in xs]

        def getmap_url(style: str, fx: float, fy: float) -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat_algebra"
                    f"&styles={style}"
                    f"&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format=image/png"
                    f"&time=2020-01-10T00:00:00.000Z")

        # one small drill polygon inside the scene-pair overlap
        ll = transform_bbox(merc, EPSG3857, EPSG4326)
        d = 0.03
        x0 = ll.xmin + 0.40 * (ll.xmax - ll.xmin)
        y0 = ll.ymax - 0.45 * (ll.ymax - ll.ymin)
        geom = json.dumps({
            "type": "FeatureCollection", "features": [{
                "type": "Feature", "geometry": {
                    "type": "Polygon", "coordinates": [[
                        [x0, y0], [x0 + d, y0], [x0 + d, y0 + d],
                        [x0, y0 + d], [x0, y0]]]}}]})
        drill_q = urllib.parse.quote(geom)
        drill_url = (f"http://{host}/ows?service=WPS&request=Execute"
                     f"&identifier=algebraDrill"
                     f"&datainputs=geometry={drill_q}")

        lock = threading.Lock()
        counter = itertools.count()
        errors: list = []

        def fetch(url: str, kind: str):
            """(ok, body) — no faults run in this scenario, so
            anything but a clean 200 with the right body fails."""
            try:
                with urllib.request.urlopen(url, timeout=300) as r:
                    body = r.read()
                    if r.status != 200:
                        return False, body
                    if kind == "map":
                        return body[:8] == b"\x89PNG\r\n\x1a\n", body
                    return b"ProcessSucceeded" in body, body
            except Exception as exc:  # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{kind}: {exc!r:.200}")
                return False, b""

        # warm lap: every style once (each structure compiles its one
        # fused program here) plus one drill
        warm_ok = all(fetch(getmap_url(s, *tiles[k]), "map")[0]
                      for k, s in enumerate(styles))
        warm_ok = fetch(drill_url, "drill")[0] and warm_ok

        bad = [0]
        n_req = {"map": 0, "drill": 0}

        def one():
            i = next(counter)
            # the drill minority rides the same compile cache; the
            # map majority rotates styles so concurrent arrivals mix
            # fingerprints and the scheduler groups them per structure
            if i % 16 == 7:
                kind, url = "drill", drill_url
            else:
                kind, url = "map", getmap_url(
                    styles[i % len(styles)], *tiles[i % len(tiles)])
            ok, _ = fetch(url, kind)
            with lock:
                n_req[kind] += 1
                if not ok:
                    bad[0] += 1

        conc = max(args.conc, 12)
        t_end = time.time() + args.seconds

        def storm_worker():
            while time.time() < t_end:
                one()

        storm = [threading.Thread(target=storm_worker)
                 for _ in range(conc)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()

        cs = expr_cache_stats()
        ef = paged.expr_fused_stats()
        fused_n = sum(v for k, v in ef["paths"].items()
                      if k != "unfused")
        # bounded compiles: the cache's miss count is the number of
        # DISTINCT sources ever compiled — a storm that recompiled per
        # request would blow far past it; the fused program count is
        # capped by structural identity, so the twin styles provably
        # shared a program instead of minting their own
        compiles_bounded = (0 < cs["misses"] <= n_sources
                            and cs["hits"] > cs["misses"])
        sharing_ok = 1 <= ef["programs"] <= n_structures

        # -- escape hatch: the SAME concurrent styled volley with
        # fusion off must be byte-identical and actually take the
        # unfused leg (the counter moves)
        probe = [(styles[k % len(styles)], tiles[(5 + 3 * k) %
                                                 len(tiles)])
                 for k in range(6)]

        def volley():
            bodies: list = [None] * len(probe)

            def grab(k, s, t):
                bodies[k] = fetch(getmap_url(s, *t), "map")[1]
            ths = [threading.Thread(target=grab, args=(k, s, t))
                   for k, (s, t) in enumerate(probe)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            return bodies

        bodies_on = volley()
        unfused_before = ef["paths"].get("unfused", 0)
        os.environ["GSKY_EXPR_FUSE"] = "0"
        bodies_off = volley()
        os.environ["GSKY_EXPR_FUSE"] = "1"
        unfused_after = paged.expr_fused_stats()["paths"].get(
            "unfused", 0)
        byte_identical = (all(b for b in bodies_on)
                          and bodies_on == bodies_off)
        unfused_engaged = unfused_after > unfused_before

        # every page the storm pinned must be back once waves drain
        from gsky_tpu.pipeline import pages
        pinned = -1
        t_end = time.time() + 15
        while time.time() < t_end:
            pool = pages._default
            pinned = (pool.stats().get("pinned", -1)
                      if pool is not None else 0)
            if pinned == 0:
                break
            time.sleep(0.5)

        ws = wave_stats()
        metrics = check_metrics(host, require=(
            "gsky_requests_total", "gsky_wave_dispatches_total",
            "gsky_expr_fused_total", "gsky_expr_cache_hits_total",
            "gsky_expr_programs"))

        n_done = sum(n_req.values())
        out = {
            "scenario": "algebra",
            "warm_ok": warm_ok,
            "requests": n_req, "failed": bad[0],
            "errors": errors,
            "sources": n_sources, "structures": n_structures,
            "expr_cache": cs,
            "fused": {"programs": ef["programs"], "paths": ef["paths"],
                      "dispatches": fused_n},
            "compiles_bounded": compiles_bounded,
            "fingerprint_sharing_ok": sharing_ok,
            "escape_hatch_byte_identical": byte_identical,
            "escape_hatch_unfused_engaged": unfused_engaged,
            "pool_pinned": pinned,
            "waves": {"dispatches": ws.get("dispatches", 0),
                      "requests": ws.get("requests", 0)},
            "metrics": metrics,
        }
        print(json.dumps(out))
        ok = (warm_ok and n_done > 0 and bad[0] == 0
              and fused_n > 0
              and compiles_bounded
              and sharing_ok
              and byte_identical
              and unfused_engaged
              and pinned == 0
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        reset_expr_cache()
        paged.reset_expr_fused_stats()


def run_animation(args, watcher, mas_client, merc, boot) -> int:
    """Temporal wave serving: a TIME-range APNG storm whose N-frame
    sequences must amortise their frame renders over shared wave
    dispatches, plus a client-disconnect volley aborting sequences
    mid-container (see module docstring for the pass criteria)."""
    import socket
    import threading

    import numpy as np

    from gsky_tpu.obs import metrics as om
    from gsky_tpu.pipeline.waves import wave_stats
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    # interpret mode engages the paged+wave pipeline on CPU; a wide
    # tick gives the frame lanes of each sequence a real coalescing
    # window, and GSKY_ANIM=1 pins the temporal path on even if the
    # ambient environment flipped the hatch
    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_WAVES": "1",
        "GSKY_WAVE_MAX": "8",
        "GSKY_WAVE_TICK_MS": "100",
        "GSKY_ANIM": "1",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    try:
        # gateway off: animations are never cached by design, but the
        # warm amortisation lap below must measure the wave scheduler,
        # not any response-cache short-circuit of its single frames
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        n_frames = N_SCENES
        time_list = ",".join(f"2020-01-{10 + k:02d}T00:00:00.000Z"
                             for k in range(n_frames))
        grid = 5
        frac = np.linspace(0.0, 0.6, grid)
        frac_y = np.linspace(0.1, 0.6, grid)
        tiles = [(float(fx), float(fy)) for fx in frac for fy in frac_y]
        w = merc.width * 0.2

        def anim_url(fx: float, fy: float,
                     fmt: str = "image/apng") -> str:
            bb = (f"{merc.xmin + fx * merc.width},"
                  f"{merc.ymin + fy * merc.height},"
                  f"{merc.xmin + fx * merc.width + w},"
                  f"{merc.ymin + fy * merc.height + w}")
            return (f"http://{host}/ows?service=WMS&request=GetMap"
                    f"&version=1.3.0&layers=landsat"
                    f"&crs=EPSG:3857&bbox={bb}"
                    f"&width=256&height=256&format={fmt}"
                    f"&time={time_list}")

        lock = threading.Lock()
        counter = itertools.count()
        errors: list = []

        def fetch(url: str, kind: str) -> bool:
            # no faults are injected, so every response must be a flat
            # 200 APNG (PNG signature + acTL animation-control chunk)
            # carrying the full frame count; the mp4 stub must be
            # honestly labelled as APNG bytes
            try:
                with urllib.request.urlopen(url, timeout=180) as r:
                    body = r.read()
                    if r.status != 200:
                        return False
                    if body[:8] != b"\x89PNG\r\n\x1a\n" \
                            or b"acTL" not in body[:256]:
                        return False
                    if r.headers.get("X-Gsky-Anim-Frames") \
                            != str(n_frames):
                        return False
                    if kind == "mp4":
                        return r.headers.get("X-Gsky-Anim-Container") \
                            == "apng-stub"
                    return True
            except Exception as exc:   # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{kind}: {exc!r:.200}")
                return False

        # serial warm lap: with no concurrent traffic the wave-
        # dispatch delta each sequence records is ITS OWN, so this is
        # where the amortisation claim is measured (the storm's deltas
        # are inflated by overlapping requests — telemetry only there)
        om.reset_temporal()
        warm_ok = fetch(anim_url(*tiles[0]), "apng")
        # the server records the sequence after the container's final
        # write — a beat after the client finishes reading it
        st_warm = om.temporal_stats()
        t_w = time.time() + 10
        while time.time() < t_w and st_warm.get("sequences", 0) < 1:
            time.sleep(0.1)
            st_warm = om.temporal_stats()
        warm_frames = int(st_warm.get("frames", 0))
        warm_waves = int(st_warm.get("waves", 0))
        warm_amort_ok = (warm_frames == n_frames
                         and warm_waves * 2 <= warm_frames)

        bad = [0]
        n_req = {"apng": 0, "mp4": 0}

        def one(_):
            i = next(counter)
            if i % 10 == 0:
                kind = "mp4"
                url = anim_url(*tiles[i % len(tiles)], fmt="video/mp4")
            else:
                kind = "apng"
                url = anim_url(*tiles[i % len(tiles)])
            ok = fetch(url, kind)
            with lock:
                n_req[kind] += 1
                if not ok:
                    bad[0] += 1

        conc = max(args.conc, 8)
        t_end = time.time() + args.seconds

        def storm_worker():
            while time.time() < t_end:
                one(None)

        storm = [threading.Thread(target=storm_worker)
                 for _ in range(conc)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()

        # client-disconnect volley: a sequence aborted mid-flight must
        # be recorded cancelled — either in the APNG streaming loop
        # (the sequence counter's cancelled outcome) or earlier, where
        # the request scope's cancel token drops its frame lanes from
        # the wave (the scheduler's cancelled counter).  Staggered
        # holds cover prep, render and container-streaming windows
        h, _, p = host.partition(":")

        def disconnect_midflight(hold_s: float):
            i = next(counter)
            path = anim_url(*tiles[i % len(tiles)]).split(host, 1)[1]
            try:
                s = socket.create_connection((h, int(p)), timeout=10)
                try:
                    s.sendall((f"GET {path} HTTP/1.1\r\n"
                               f"Host: {host}\r\n"
                               "Connection: close\r\n\r\n").encode())
                    time.sleep(hold_s)
                finally:
                    s.close()
            except Exception:   # noqa: BLE001 - volley is best-effort
                pass

        anim_c0 = om.temporal_stats().get("cancelled", 0)
        wave_c0 = wave_stats().get("cancelled", 0)
        cancel_seen = 0
        volleys = 0
        deadline = time.time() + 30
        while time.time() < deadline and cancel_seen < 1:
            ths = [threading.Thread(target=disconnect_midflight,
                                    args=(hold,))
                   for hold in (0.05, 0.15, 0.35, 0.7, 1.2, 2.0)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            volleys += 1
            time.sleep(1.5)
            cancel_seen = int(
                om.temporal_stats().get("cancelled", 0) - anim_c0
                + wave_stats().get("cancelled", 0) - wave_c0)

        # every page the storm pinned must be back: cancelled lanes
        # release at wave assembly, dispatched waves after readback
        from gsky_tpu.pipeline import pages
        pinned = -1
        t_end = time.time() + 15
        while time.time() < t_end:
            pool = pages._default
            pinned = (pool.stats().get("pinned", -1)
                      if pool is not None else 0)
            if pinned == 0:
                break
            time.sleep(0.5)

        st = om.temporal_stats()
        n_done = sum(n_req.values())
        metrics = check_metrics(host, require=(
            "gsky_requests_total", "gsky_request_seconds",
            "gsky_anim_sequences_total", "gsky_anim_frames_per_wave",
            "gsky_wave_dispatches_total"))
        trace_rep = slowest_trace_report(host)

        out = {
            "scenario": "animation",
            "warm_ok": warm_ok,
            "warm_amortisation": {"frames": warm_frames,
                                  "waves": warm_waves,
                                  "ok": warm_amort_ok},
            "requests": n_req, "failed": bad[0],
            "errors": errors,
            "cancellation": {"seen": cancel_seen, "volleys": volleys},
            "pool_pinned": pinned,
            "temporal": st,
            "metrics": metrics,
            "slowest_trace": trace_rep,
        }
        print(json.dumps(out))
        ok = (warm_ok and warm_amort_ok
              and n_done > 0 and bad[0] == 0
              and st.get("sequences", 0) >= 1
              and cancel_seen >= 1
              and pinned == 0
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        om.reset_temporal()


def run_dap4(args, watcher, mas_client, merc, boot) -> int:
    """Streamed DAP4 serving: concurrent constraint-expression
    subsets against a tiled coverage frame must stream off the export
    spool with bounded buffering and bounded process RSS (see module
    docstring for the pass criteria)."""
    import threading
    import urllib.parse

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import transform_bbox
    from gsky_tpu.obs import metrics as om
    from gsky_tpu.server import dap4
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer

    env_overrides = {
        "GSKY_PALLAS": "interpret",
        "GSKY_DAP_STREAM": "1",
    }
    saved_env = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)
    try:
        # gateway off: the RSS ceiling must measure the export path,
        # not a response cache legitimately retaining coverage bodies
        server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                           metrics=MetricsLogger(), gateway=None)
        host = boot(server)

        bands = [f"LC08_20200{110 + k}_T1" for k in range(N_SCENES)]
        ll = transform_bbox(merc, EPSG3857, EPSG4326)
        # x-clamp fractions stay well inside the coverage frame so the
        # filter survives dap_to_wcs's in-bbox validity check
        fracs = (0.0, 0.15, 0.3, 0.45)

        def ce_url(i: int) -> str:
            # rotate band AND x subset; the time filter names the
            # band's own acquisition date so every subset has granules
            k = i % len(bands)
            x_lo = ll.xmin + fracs[i % len(fracs)] * (ll.xmax - ll.xmin)
            ce = (f"landsat_dap{{{bands[k]}}} | x >= {x_lo:.6f}, "
                  f"time >= 2020-01-{10 + k:02d}T00:00:00.000Z")
            return (f"http://{host}/ows?dap4.ce="
                    + urllib.parse.quote(ce))

        lock = threading.Lock()
        counter = itertools.count()
        errors: list = []
        peak_rss = [0.0]

        def fetch(url: str, want_body: bool = False):
            # every response must be a flat 200 DAP4 body: the typed
            # content-type, a leading DMR chunk naming a Float32 var,
            # and (streamed leg) chunked transfer off the spool
            try:
                req = urllib.request.Request(url)
                with urllib.request.urlopen(req, timeout=180) as r:
                    body = r.read()
                    if r.status != 200:
                        return None
                    if r.headers.get_content_type() != dap4.CONTENT_TYPE:
                        return None
                    if b"Float32" not in body[:2048]:
                        return None
                    return body if want_body else True
            except Exception as exc:   # noqa: BLE001 - reported below
                with lock:
                    if len(errors) < 5:
                        errors.append(f"{exc!r:.200}")
                return None

        # warm lap + escape hatch: the same CE fetched streamed and
        # with GSKY_DAP_STREAM=0 (in-RAM encode) must be byte-identical
        # — the stream changes WHERE bytes buffer, never the bytes
        om.reset_temporal()
        warm_streamed = fetch(ce_url(0), want_body=True)
        warm_ok = warm_streamed is not None
        streams_warm = om.temporal_stats().get("dap_streams", 0)
        os.environ["GSKY_DAP_STREAM"] = "0"
        try:
            warm_ram = fetch(ce_url(0), want_body=True)
        finally:
            os.environ["GSKY_DAP_STREAM"] = "1"
        byte_identical = (warm_ok and warm_ram is not None
                          and warm_streamed == warm_ram)

        bad = [0]
        n_done = [0]
        # steady-state RSS bound (matches churn): the first quarter
        # pays compiles + decode-cache fills; growth is measured from
        # the quarter mark so it bounds the export path, not warmup
        rss_base = [None]
        quarter = time.time() + args.seconds / 4.0

        def one(_):
            i = next(counter)
            ok = fetch(ce_url(i))
            with lock:
                n_done[0] += 1
                if not ok:
                    bad[0] += 1
                if time.time() >= quarter:
                    r = rss_mb()
                    if rss_base[0] is None:
                        rss_base[0] = r
                    peak_rss[0] = max(peak_rss[0], r)

        conc = max(args.conc, 8)
        t_end = time.time() + args.seconds

        def storm_worker():
            while time.time() < t_end:
                one(None)

        storm = [threading.Thread(target=storm_worker)
                 for _ in range(conc)]
        for t in storm:
            t.start()
        for t in storm:
            t.join()

        st = om.temporal_stats()
        rss0 = rss_base[0] if rss_base[0] is not None else rss_mb()
        rss_growth = max(0.0, peak_rss[0] - rss0)
        rss_ok = rss_growth <= args.max_rss_growth_mb
        # the rechunker may hold one full chunk plus the row batch in
        # flight; 2x the chunk ceiling bounds it with margin — an
        # in-RAM materialisation of concurrent coverages would not fit
        peak_buf = st.get("dap_peak_buffer_bytes", 0)
        buffer_ok = 0 < peak_buf <= 2 * dap4.MAX_CHUNK
        streamed_ok = (streams_warm >= 1
                       and st.get("dap_streams", 0) > streams_warm
                       and st.get("dap_streamed_bytes", 0) > 0)
        metrics = check_metrics(host, require=(
            "gsky_requests_total", "gsky_request_seconds",
            "gsky_dap_streamed_bytes_total"))

        out = {
            "scenario": "dap4",
            "warm_ok": warm_ok,
            "escape_hatch_byte_identical": byte_identical,
            "requests": n_done[0], "failed": bad[0],
            "errors": errors,
            "rss": {"baseline_mb": round(rss0, 1),
                    "peak_mb": round(peak_rss[0], 1),
                    "growth_mb": round(rss_growth, 1),
                    "ok": rss_ok},
            "temporal": st,
            "buffer_ok": buffer_ok,
            "metrics": metrics,
        }
        print(json.dumps(out))
        ok = (warm_ok and byte_identical
              and n_done[0] > 0 and bad[0] == 0
              and streamed_ok
              and buffer_ok
              and rss_ok
              and not metrics["missing"])
        print("SOAK PASSED" if ok else "SOAK FAILED", flush=True)
        return 0 if ok else 1
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        om.reset_temporal()


if __name__ == "__main__":
    sys.exit(main())
