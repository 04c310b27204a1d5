"""Default metric families and scrape-time collectors.

Two sourcing rules keep ``/metrics`` honest:

* Distributions (latency, stage durations, RPC times) are observed at
  the exact measurement points that already feed ``/debug`` — in
  ``server/metrics.py`` fold-in, the worker client, and the encode
  pool — never from a second clock.
* Monotonic counters and level gauges that already exist as live stats
  objects (caches, fleet router, resilience registry, encode pool,
  compile probe, flight recorder) are *collected at scrape time* from
  those objects, so there is one counter, not two copies to drift.

Everything registers against ``prom.default_registry()``; the OWS
``/metrics`` route just calls ``render_metrics()``.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Tuple

from .prom import default_registry, log_buckets

_REG = default_registry()

REQUESTS = _REG.counter(
    "gsky_requests_total", "OWS requests by service class and status.",
    ["service", "status"])
REQUEST_SECONDS = _REG.histogram(
    "gsky_request_seconds", "End-to-end OWS request latency.",
    ["service"], buckets=log_buckets(0.002, 120.0))
STAGE_SECONDS = _REG.histogram(
    "gsky_stage_seconds",
    "Per-stage durations (tile pipeline, export pipeline, worker side).",
    ["stage"], buckets=log_buckets(0.0005, 60.0))
RPC_SECONDS = _REG.histogram(
    "gsky_worker_rpc_seconds", "Worker RPC round-trip by op and outcome.",
    ["op", "outcome"], buckets=log_buckets(0.001, 60.0))
ENCODE_SECONDS = _REG.histogram(
    "gsky_encode_seconds", "Encode-pool time by phase (wait vs cpu).",
    ["phase"], buckets=log_buckets(0.0005, 10.0))
WAVE_DISPATCHES = _REG.counter(
    "gsky_wave_dispatches_total",
    "Wave-scheduler device program invocations by result kind.",
    ["kind"])
WAVE_OCCUPANCY = _REG.histogram(
    "gsky_wave_occupancy",
    "Requests coalesced per wave dispatch.",
    buckets=[1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0])
WAVE_ASSEMBLY_MS = _REG.histogram(
    "gsky_wave_assembly_ms",
    "Wave assembly + dispatch-enqueue time (milliseconds).",
    buckets=log_buckets(0.01, 100.0))
WAVE_GAP_MS = _REG.histogram(
    "gsky_wave_gap_ms",
    "Host-side idle gap between consecutive wave dispatch enqueues "
    "(milliseconds) - the inter-wave stutter the pipelined scheduler "
    "closes (docs/PERF.md 'Continuous device occupancy').",
    buckets=log_buckets(0.01, 1000.0))
WAVE_STAGED = _REG.counter(
    "gsky_wave_staged_total",
    "Wave groups staged ahead of dispatch by the assembly stage "
    "(double-buffered input ring uploads).")
MESH_WAVES = _REG.counter(
    "gsky_mesh_waves_total",
    "Mesh wave dispatches by partition layout.",
    ["layout"])
MESH_CHIP_OCCUPANCY = _REG.histogram(
    "gsky_mesh_chip_occupancy",
    "Wave entries landing on each chip per mesh dispatch.",
    buckets=[1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0])
MESH_SHARD_SKEW_MS = _REG.histogram(
    "gsky_mesh_shard_skew_ms",
    "Per-chip readback readiness spread per mesh wave (milliseconds).",
    buckets=log_buckets(0.01, 1000.0))
TRACE_EVENTS = _REG.counter(
    "gsky_trace_events_total",
    "Cross-cutting events (retry, breaker_open, hedge, reroute, shed).",
    ["kind"])
PLAN_SUPERBLOCKS = _REG.counter(
    "gsky_plan_superblocks_total",
    "Shared-halo superblocks dispatched by the dataflow autoplanner.")
PLAN_BYTES_SAVED = _REG.counter(
    "gsky_plan_gather_bytes_saved_total",
    "HBM gather bytes the superblock plan avoided vs per-tile windows.")
PLAN_BLOCK_SHAPE = _REG.counter(
    "gsky_plan_block_shape",
    "Cost-model Pallas block-shape decisions by chosen shape.",
    ["shape"])
PLAN_ROUTE = _REG.counter(
    "gsky_plan_route_total",
    "Autoplanner group routing between ragged slot pad and bucketed "
    "pulls (the PR 8 crossover).",
    ["path"])
FABRIC_REPLAY = _REG.counter(
    "gsky_fabric_replay_total",
    "Gateway peer-replay fetch outcomes (docs/FABRIC.md): hit/miss/"
    "error/deadline/breaker_open/owner_local/disabled.",
    ["outcome"])
FABRIC_PAGE_FILLS = _REG.counter(
    "gsky_fabric_page_fills_total",
    "Page-pool fills by source: peer (fabric page RPC) vs cold "
    "(decode + stage from storage).",
    ["source"])

Rows = Iterable[Tuple[Dict[str, str], float]]


def _g(name: str, help_: str, rows: Rows):
    return (name, "gauge", help_, list(rows))


def _c(name: str, help_: str, rows: Rows):
    return (name, "counter", help_, list(rows))


def _collect_caches():
    """Hit/miss counters for every process-wide cache tier, lifted from
    the same ``cache_stats()`` block `/debug` folds into its records."""
    out: List = []
    try:
        from ..server.metrics import cache_stats
        hits, misses = [], []
        for cache, st in (cache_stats() or {}).items():
            if "hits" not in st:    # mas_sql counts statements, not hits
                continue
            hits.append(({"cache": cache}, float(st.get("hits", 0))))
            misses.append(({"cache": cache}, float(st.get("misses", 0))))
        if hits:
            out.append(_c("gsky_cache_hits_total",
                          "Cache hits by cache tier.", hits))
            out.append(_c("gsky_cache_misses_total",
                          "Cache misses by cache tier.", misses))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    try:
        from ..serving import default_gateway
        st = default_gateway.stats()
        fl = st.get("singleflight") or {}
        out.append(_c("gsky_singleflight_total",
                      "Single-flight render outcomes.",
                      [({"outcome": "leader"}, float(fl.get("leaders", 0))),
                       ({"outcome": "joined"}, float(fl.get("joined", 0)))]))
        adm = (st.get("admission") or {}).get("classes") or {}
        if adm:
            out.append(_g("gsky_admission_in_use",
                          "In-flight admitted requests.",
                          [({"service": s}, float(c.get("in_use", 0)))
                           for s, c in adm.items()]))
            out.append(_g("gsky_admission_queued",
                          "Requests queued at admission.",
                          [({"service": s}, float(c.get("queued", 0)))
                           for s, c in adm.items()]))
            out.append(_c("gsky_admission_shed_total",
                          "Requests shed at admission.",
                          [({"service": s}, float(c.get("shed", 0)))
                           for s, c in adm.items()]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_fleet():
    out: List = []
    try:
        from ..fleet import fleet_stats
        stats = fleet_stats() or {}
        nodes_rows, routed, rerouted, hedge_rows = [], [], [], []
        for name, st in stats.items():
            health = st.get("health") or {}
            states: Dict[str, int] = {}
            for _, h in health.items():
                s = (h or {}).get("state", "unknown")
                states[s] = states.get(s, 0) + 1
            for s, n in states.items():
                nodes_rows.append(({"router": name, "state": s}, float(n)))
            routed.append(({"router": name}, float(st.get("routed", 0))))
            rerouted.append(({"router": name},
                             float(st.get("rerouted", 0))))
            hg = st.get("hedge") or {}
            for outcome, key in (("fired", "hedges"), ("won", "hedge_wins"),
                                 ("denied", "hedges_denied")):
                hedge_rows.append(({"router": name, "outcome": outcome},
                                   float(hg.get(key, 0))))
        if stats:
            out.append(_g("gsky_fleet_nodes",
                          "Fleet nodes by router and health state.",
                          nodes_rows))
            out.append(_c("gsky_fleet_routed_total",
                          "Tasks routed by the fleet router.", routed))
            out.append(_c("gsky_fleet_rerouted_total",
                          "Tasks rerouted off their preferred node.",
                          rerouted))
            out.append(_c("gsky_fleet_hedges_total",
                          "Hedged RPCs by outcome.", hedge_rows))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_resilience():
    out: List = []
    try:
        from ..resilience import registry as _rr
        st = _rr.stats()
        out.append(_c("gsky_retries_total", "Retries by site.",
                      [({"site": s}, float(n))
                       for s, n in (st.get("retries") or {}).items()]))
        out.append(_c("gsky_retry_exhausted_total",
                      "Retry budgets exhausted by site.",
                      [({"site": s}, float(n))
                       for s, n in (st.get("retry_exhausted") or {})
                       .items()]))
        out.append(_c("gsky_degraded_responses_total",
                      "Responses served degraded.",
                      [({}, float(st.get("degraded_responses", 0)))]))
        out.append(_c("gsky_deadline_exhausted_total",
                      "Requests that ran out of deadline budget.",
                      [({}, float(st.get("deadline_exhausted", 0)))]))
        breakers = st.get("breakers") or {}
        if breakers:
            out.append(_g("gsky_breaker_open",
                          "Circuit breaker state (1 = open/half-open).",
                          [({"site": s},
                            0.0 if (b or {}).get("state") == "closed"
                            else 1.0)
                           for s, b in breakers.items()]))
            out.append(_c("gsky_breaker_opens_total",
                          "Circuit breaker trips by site.",
                          [({"site": s}, float((b or {}).get("opens", 0)))
                           for s, b in breakers.items()]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_runtime():
    out: List = []
    try:
        from ..server.prewarm import compile_count
        out.append(_c("gsky_compiles_total",
                      "Backend compiles observed by the jax.monitoring "
                      "probe.", [({}, float(compile_count()))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    try:
        from ..io.png import encode_pool_stats
        st = encode_pool_stats() or {}
        out.append(_g("gsky_encode_pool_pending",
                      "Encode jobs queued or running on the pool.",
                      [({}, float(st.get("pending", 0)))]))
        out.append(_g("gsky_encode_pool_workers",
                      "Encode-pool worker threads.",
                      [({}, float(st.get("workers", 0)))]))
        out.append(_c("gsky_encode_pool_encoded_total",
                      "Encode jobs completed.",
                      [({}, float(st.get("encoded", 0)))]))
        out.append(_c("gsky_encode_pool_errors_total",
                      "Encode jobs that raised.",
                      [({}, float(st.get("errors", 0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    try:
        from .recorder import default_recorder
        st = default_recorder().stats()
        out.append(_c("gsky_traces_recorded_total",
                      "Traces captured by the flight recorder.",
                      [({}, float(st.get("recorded", 0)))]))
        out.append(_c("gsky_traces_slo_violations_total",
                      "Traces past the SLO threshold.",
                      [({}, float(st.get("slo_violations", 0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_paged():
    """Paged-path engagement and the page-pool residency stats (the
    ragged paged rendering telemetry, docs/KERNELS.md)."""
    out: List = []
    try:
        from ..pipeline.executor import default_executor
        out.append(_c("gsky_paged_dispatches_total",
                      "Executor dispatches served by the paged path vs "
                      "declined to buckets.",
                      [({"outcome": "engaged"},
                        float(default_executor.paged_engaged)),
                       ({"outcome": "declined"},
                        float(default_executor.paged_declined))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    try:
        from ..pipeline import pages
        if pages._default is not None:   # don't allocate just to report
            st = pages._default.stats()
            out.append(_g("gsky_page_pool_resident",
                          "Pages resident in the pool.",
                          [({}, float(st.get("resident", 0)))]))
            out.append(_g("gsky_page_pool_capacity",
                          "Page pool capacity (pages).",
                          [({}, float(st.get("capacity", 0)))]))
            out.append(_c("gsky_page_pool_staged_total",
                          "Pages staged into the pool.",
                          [({}, float(st.get("staged", 0)))]))
            out.append(_c("gsky_page_pool_hits_total",
                          "Page-table hits on already-staged pages.",
                          [({}, float(st.get("hits", 0)))]))
            out.append(_c("gsky_page_pool_evictions_total",
                          "LRU page evictions.",
                          [({}, float(st.get("evictions", 0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_overload():
    """Overload-survival surfaces (docs/RESILIENCE.md "Overload &
    brownout"): the adaptive admission limits the AIMD controller is
    running at, per-tenant queue depths behind them, cancellation
    counts by pipeline stage, and the memory-pressure state driving
    brownout."""
    out: List = []
    try:
        from ..serving import default_gateway
        st = default_gateway.admission.stats()
        classes = st.get("classes") or {}
        if classes:
            out.append(_g("gsky_admit_limit",
                          "Current adaptive admission limit per "
                          "service class.",
                          [({"class": s}, float(c.get("limit", 0)))
                           for s, c in classes.items()]))
        tenants = st.get("tenants") or {}
        if tenants:
            out.append(_g("gsky_admit_queue_depth",
                          "Requests queued at admission per "
                          "tenant/service-class pair.",
                          [({"tenant_class": k}, float(v))
                           for k, v in tenants.items()]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    try:
        from ..resilience import cancel_stats
        stages = (cancel_stats() or {}).get("stages") or {}
        if stages:
            out.append(_c("gsky_cancelled_total",
                          "Request cancellations observed per "
                          "pipeline stage.",
                          [({"stage": s}, float(v))
                           for s, v in stages.items()]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    try:
        from ..resilience.pressure import default_monitor
        out.append(_g("gsky_pressure_state",
                      "Memory-pressure state (0 nominal, 1 brownout, "
                      "2 critical).",
                      [({}, float(default_monitor().stats()
                                  .get("state", 0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_ingest():
    """Cloud-native ingest surfaces (docs/INGEST.md): ranged-read
    volume, prefetch outcome counts, and how much of the ranged-read
    time hid under an in-flight device dispatch."""
    out: List = []
    try:
        from ..ingest import stats as ingest_stats
        st = ingest_stats.snapshot()
        out.append(_c("gsky_ranged_reads_total",
                      "Coalesced byte-range requests issued by the "
                      "ingest read path.",
                      [({}, float(st.get("ranged_reads", 0)))]))
        out.append(_c("gsky_ranged_read_bytes_total",
                      "Bytes fetched through ranged reads.",
                      [({}, float(st.get("ranged_read_bytes", 0)))]))
        pf = st.get("prefetch") or {}
        out.append(_c("gsky_prefetch_total",
                      "Prefetch outcomes: predicted-and-used (hit), "
                      "requested-but-not-ready (miss), warmed-but-"
                      "expired (wasted).",
                      [({"outcome": k}, float(pf.get(k, 0)))
                       for k in ("hit", "miss", "wasted")]))
        out.append(_g("gsky_ingest_overlap_ratio",
                      "Fraction of ranged-read seconds spent while a "
                      "device dispatch was in flight.",
                      [({}, float(st.get("overlap_ratio", 0.0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_device():
    """Device-guard surfaces (docs/RESILIENCE.md "Device failures"):
    the supervisor's state machine position, incident counters, and the
    warm-recovery (journal rehydration) volume."""
    out: List = []
    try:
        from ..device_guard import default_supervisor
        st = default_supervisor().stats()
        out.append(_g("gsky_device_state",
                      "Device supervisor state (0 healthy, 1 suspect, "
                      "2 reinitializing, 3 dead).",
                      [({}, float(st.get("state_code", 0)))]))
        out.append(_c("gsky_device_reinits_total",
                      "Device teardown+rebuild cycles.",
                      [({}, float(st.get("reinits", 0)))]))
        out.append(_c("gsky_device_hangs_total",
                      "Dispatches abandoned by the hang watchdog.",
                      [({}, float(st.get("hangs", 0)))]))
        out.append(_c("gsky_device_incidents_total",
                      "Device incidents by kind.",
                      [({"kind": "crash"}, float(st.get("crashes", 0))),
                       ({"kind": "oom"}, float(st.get("ooms", 0))),
                       ({"kind": "corrupt"},
                        float(st.get("corruptions", 0)))]))
        out.append(_c("gsky_pool_rehydrated_pages_total",
                      "Hot pages re-staged into a rebuilt page pool "
                      "from the residency journal.",
                      [({}, float(st.get("rehydrated_pages", 0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_waves():
    """Wave-scheduler surfaces (docs/PERF.md "Wave-level serving"):
    readback-queue level plus the counters already kept on the live
    scheduler object — collected at scrape time, never a second copy.
    The dispatch/occupancy/assembly distributions are the module-level
    families above, observed at the dispatch site itself."""
    out: List = []
    try:
        from ..pipeline import waves
        if waves._default is not None:   # don't boot threads to report
            st = waves._default.stats()
            out.append(_g("gsky_wave_readback_queue_depth",
                          "Wave result blocks awaiting async readback.",
                          [({}, float(st.get("readback_queue_depth",
                                             0)))]))
            out.append(_c("gsky_wave_requests_total",
                          "Requests submitted to the wave scheduler.",
                          [({}, float(st.get("requests", 0)))]))
            out.append(_c("gsky_wave_fallbacks_total",
                          "Wave entries served via their per-call leg "
                          "after a device incident.",
                          [({}, float(st.get("fallbacks", 0)))]))
            out.append(_c("gsky_wave_cancelled_total",
                          "Wave entries dropped at assembly or "
                          "readback for request cancellation.",
                          [({}, float(st.get("cancelled", 0)))]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_mesh():
    """Mesh-serving surfaces (docs/MESH.md): chip count and per-layout
    entry totals from the live dispatcher — collected at scrape time
    so there is one counter, not two copies to drift.  The per-wave
    layout/occupancy/skew distributions are the module-level families
    above, observed at the dispatch site itself."""
    out: List = []
    try:
        from ..mesh.dispatch import active_mesh
        md = active_mesh()
        if md is not None:   # don't build a mesh to report
            st = md.stats()
            out.append(_g("gsky_mesh_chips",
                          "Chips in the serving mesh.",
                          [({}, float(st.get("chips", 0)))]))
            ent = st.get("entries_by_layout") or {}
            if ent:
                out.append(_c("gsky_mesh_entries_total",
                              "Wave entries dispatched by layout.",
                              [({"layout": k}, float(v))
                               for k, v in sorted(ent.items())]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_expr():
    """Fused band-algebra surfaces (docs/KERNELS.md "Expression
    epilogue"): compile-cache traffic, distinct fused programs, and
    how expression renders routed.  Rendered only once the expression
    tier has seen traffic — a process that never parses an expression
    keeps its exposition byte-identical."""
    out: List = []
    try:
        from ..ops.expr import expr_cache_stats
        from ..ops.paged import expr_fused_stats
        cs = expr_cache_stats()
        fs = expr_fused_stats()
        live = (cs.get("hits", 0) or cs.get("misses", 0)
                or fs.get("programs", 0) or fs.get("paths"))
        if live:
            out.append(_c("gsky_expr_cache_hits_total",
                          "Expression compile-cache hits.",
                          [({}, float(cs.get("hits", 0)))]))
            out.append(_c("gsky_expr_cache_misses_total",
                          "Expression compile-cache misses (fresh "
                          "parses).",
                          [({}, float(cs.get("misses", 0)))]))
            out.append(_g("gsky_expr_programs",
                          "Distinct expression fingerprints with a "
                          "fused paged program this process.",
                          [({}, float(fs.get("programs", 0)))]))
            paths = fs.get("paths") or {}
            if paths:
                out.append(_c("gsky_expr_fused_total",
                              "Expression renders by dispatch path.",
                              [({"path": k}, float(v))
                               for k, v in sorted(paths.items())]))
    except Exception:  # subsystem unbooted - skip its families, a scrape never fails
        pass
    return out


def _collect_tsan():
    """Lockset race-sanitizer surfaces (docs/ANALYSIS.md): only the
    race count — a non-zero value fails the GSKY_TSAN=1 CI soak leg,
    and scraping it keeps the family parser-proven like every other."""
    out: List = []
    try:
        from .tsan import tsan_stats
        st = tsan_stats()
        if st.get("installed") or st.get("enabled"):
            out.append(_c("gsky_tsan_races_total",
                          "Data races reported by the lockset "
                          "sanitizer (GSKY_TSAN=1).",
                          [({}, float(st.get("races", 0)))]))
            out.append(_g("gsky_tsan_tracked_vars",
                          "Shared variables under lockset tracking.",
                          [({}, float(st.get("tracked_vars", 0)))]))
    except Exception:
        # scrape-time collectors must never break /metrics
        pass
    return out


def _collect_fabric():
    """Cache-fabric surfaces (docs/FABRIC.md): the replica-page gauge
    from the popularity-weighted replication planner.  Reported when
    the fabric is on or has ever planned — a fabric-less process keeps
    its exposition byte-identical."""
    out: List = []
    try:
        from .. import fabric
        from ..fabric import replicate
        st = replicate.stats()
        if fabric.fabric_enabled() or st.get("rounds"):
            out.append(_g("gsky_fabric_replica_pages",
                          "Pages this node holds (or is due to hold) "
                          "under the popularity-weighted replication "
                          "plan.",
                          [({}, float(st.get("replica_pages", 0)))]))
    except Exception:
        # scrape-time collectors must never break /metrics
        pass
    return out


def _collect_elastic():
    """Elastic-fleet surfaces (docs/FLEET.md "Elastic fleet"): node
    counts by lifecycle state, scale decisions, preemption notices and
    warm-handoff page outcomes.  Reported only when elastic has left a
    trace in this process (``GSKY_ELASTIC=1``, a live autoscaler, or a
    non-zero counter) — a fixed fleet keeps its exposition
    byte-identical."""
    out: List = []
    try:
        from ..fleet import elastic
        if elastic.dormant():
            return out
        counts: Dict[str, float] = {}
        for a in elastic.autoscalers():
            for state, n in a.node_counts().items():
                counts[state] = counts.get(state, 0) + n
        if counts:
            out.append(_g("gsky_elastic_nodes",
                          "Worker nodes by elastic lifecycle state.",
                          [({"state": s}, float(n))
                           for s, n in sorted(counts.items())]))
        c = elastic.counters()
        out.append(_c("gsky_elastic_decisions_total",
                      "Autoscaler scale decisions by direction.",
                      [({"dir": d}, float(n))
                       for d, n in sorted(c["decisions"].items())]))
        out.append(_c("gsky_preemptions_total",
                      "Preemption notices handled, by whether a grace "
                      "window allowed the drain + journal handoff.",
                      [({"graceful": "true"},
                        float(c["preemptions"]["graceful"])),
                       ({"graceful": "false"},
                        float(c["preemptions"]["nograce"]))]))
        out.append(_c("gsky_handoff_pages_total",
                      "Hot pages inherited on preemption handoff: "
                      "refilled from peer HBM vs left to cold staging.",
                      [({"source": s}, float(n))
                       for s, n in sorted(
                           c["handoff_pages"].items())]))
    except Exception:
        # scrape-time collectors must never break /metrics
        pass
    return out


# -- temporal serving (animation waves + streamed DAP4) ----------------
#
# Recorded by the OWS animation handler and the DAP4 streaming leg
# (docs/PERF.md "Temporal waves"); collected at scrape time from this
# one copy.  A process that never served an animation or a streamed
# DAP4 response keeps its exposition byte-identical.

_TEMPORAL_LOCK = threading.Lock()
_TEMPORAL: Dict[str, float] = {
    "sequences": 0, "frames": 0, "waves": 0, "cancelled": 0,
    "degraded": 0, "dap_streams": 0, "dap_streamed_bytes": 0,
    "dap_peak_buffer_bytes": 0}


def record_anim_sequence(frames: int, waves: int,
                         degraded: bool = False,
                         cancelled: bool = False) -> None:
    """One animation sequence completed: ``frames`` rendered across
    ``waves`` wave dispatches (the amortisation the temporal path
    exists for)."""
    with _TEMPORAL_LOCK:
        _TEMPORAL["sequences"] += 1
        _TEMPORAL["frames"] += int(frames)
        _TEMPORAL["waves"] += int(waves)
        if degraded:
            _TEMPORAL["degraded"] += 1
        if cancelled:
            _TEMPORAL["cancelled"] += 1


def record_dap_stream(nbytes: int, peak_buffer: int) -> None:
    """One streamed DAP4 response: bytes on the wire and the largest
    resident buffer the rechunker held (the bounded-RSS evidence)."""
    with _TEMPORAL_LOCK:
        _TEMPORAL["dap_streams"] += 1
        _TEMPORAL["dap_streamed_bytes"] += int(nbytes)
        _TEMPORAL["dap_peak_buffer_bytes"] = max(
            _TEMPORAL["dap_peak_buffer_bytes"], int(peak_buffer))


def temporal_stats() -> Dict[str, float]:
    """The /debug ``temporal`` block (and the test hook)."""
    with _TEMPORAL_LOCK:
        st = dict(_TEMPORAL)
    st["frames_per_wave"] = round(
        st["frames"] / st["waves"], 4) if st["waves"] else 0.0
    return st


def reset_temporal() -> None:
    """Test hook: zero the temporal counters."""
    with _TEMPORAL_LOCK:
        for k in _TEMPORAL:
            _TEMPORAL[k] = 0


def _collect_temporal():
    """Temporal-serving surfaces (docs/PERF.md "Temporal waves"):
    animation sequence/frame amortisation and streamed-DAP4 volume.
    Rendered only once either path has served — exposition stays
    byte-identical otherwise."""
    out: List = []
    try:
        st = temporal_stats()
        if not (st["sequences"] or st["dap_streams"]):
            return out
        out.append(_c("gsky_anim_sequences_total",
                      "Animation sequences served by the temporal "
                      "wave path, by outcome.",
                      [({"outcome": "ok"},
                        float(st["sequences"] - st["cancelled"])),
                       ({"outcome": "cancelled"},
                        float(st["cancelled"]))]))
        out.append(_g("gsky_anim_frames_per_wave",
                      "Mean animation frames amortised per wave "
                      "dispatch (frames / waves, process lifetime).",
                      [({}, float(st["frames_per_wave"]))]))
        out.append(_c("gsky_dap_streamed_bytes_total",
                      "Bytes streamed by the bounded-RSS DAP4 export "
                      "leg (GSKY_DAP_STREAM).",
                      [({}, float(st["dap_streamed_bytes"]))]))
    except Exception:
        # scrape-time collectors must never break /metrics
        pass
    return out


def _collect_gc():
    """The cyclic collector's runs and pauses by generation, from the
    watch `/debug` `process.gc` reads (`obs/process.py`).  Rendered only
    where the server installed it — exposition stays byte-identical
    otherwise."""
    out: List = []
    try:
        from .process import gc_stats
        st = gc_stats()
        if st is not None:
            out.append(_c("gsky_gc_collections_total",
                          "Cyclic garbage collections by generation.",
                          [({"generation": str(g)}, float(n))
                           for g, n in enumerate(st["collections"])]))
            out.append(_c("gsky_gc_pause_seconds_total",
                          "Seconds the cyclic collector held the "
                          "interpreter, by generation.",
                          [({"generation": str(g)}, float(s))
                           for g, s in enumerate(st["pause_s"])]))
    except Exception:
        # scrape-time collectors must never break /metrics
        pass
    return out


for _fn in (_collect_caches, _collect_fleet, _collect_resilience,
            _collect_runtime, _collect_paged, _collect_overload,
            _collect_ingest, _collect_device, _collect_waves,
            _collect_mesh, _collect_expr, _collect_tsan,
            _collect_fabric, _collect_elastic, _collect_temporal,
            _collect_gc):
    _REG.register_collector(_fn)


def render_metrics() -> str:
    return default_registry().render()
