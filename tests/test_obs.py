"""Observability layer: prometheus registry math and strict exposition
round-trip, trace context propagation (asyncio tasks, to_thread, the
encode pool, the gRPC metadata hop), the flight recorder's ring /
reservoir / SLO file export, and the trace_view waterfall."""

import asyncio
import contextvars
import importlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from gsky_tpu import obs
from gsky_tpu.obs.prom import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    log_buckets,
    parse_exposition,
)
from gsky_tpu.obs.recorder import FlightRecorder, reset_recorder

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_view  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_recorder():
    reset_recorder()
    yield
    reset_recorder()


# ---------------------------------------------------------------------------
# prometheus primitives


def test_log_buckets_125_ladder():
    assert log_buckets(0.001, 1.0) == (
        0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)


def test_log_buckets_rejects_bad_range():
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)


def test_counter_rejects_negative():
    c = Counter("t_c", "h")
    c.inc(2)
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.samples() == [("t_c", [], 2.0)]


def test_metric_rejects_bad_names():
    with pytest.raises(ValueError):
        Counter("bad-name", "h")
    with pytest.raises(ValueError):
        Counter("ok", "h", labelnames=("bad-label",))


def test_labels_create_children_and_validate():
    c = Counter("t_lbl", "h", labelnames=("op",))
    c.labels(op="warp").inc()
    c.labels(op="warp").inc()
    c.labels(op="drill").inc()
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.inc()                      # unlabelled use of a labelled metric
    vals = {tuple(lb): v for _, lb, v in c.samples()}
    assert vals[(("op", "warp"),)] == 2.0
    assert vals[(("op", "drill"),)] == 1.0


def test_histogram_cumulative_buckets_sum_count():
    h = Histogram("t_h", "h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    by_name = {}
    for name, labels, value in h.samples():
        by_name[(name, dict(labels).get("le"))] = value
    assert by_name[("t_h_bucket", "0.01")] == 1
    assert by_name[("t_h_bucket", "0.1")] == 3
    assert by_name[("t_h_bucket", "1")] == 4
    assert by_name[("t_h_bucket", "+Inf")] == 5
    assert by_name[("t_h_count", None)] == 5
    assert by_name[("t_h_sum", None)] == pytest.approx(5.605)


def test_render_parse_roundtrip():
    reg = Registry()
    reg.counter("gsky_t_requests_total", "reqs", ("route",)) \
        .labels(route="wms").inc(3)
    reg.gauge("gsky_t_depth", "queue depth").set(7)
    h = reg.histogram("gsky_t_lat", "latency", ("op",),
                      buckets=(0.001, 0.01, 0.1))
    h.labels(op="warp").observe(0.004)
    h.labels(op="warp").observe(0.04)
    reg.register_collector(lambda: [
        ("gsky_t_extra", "gauge", "from collector",
         [({"k": 'va"l'}, 1.5)]),
    ])
    fams = parse_exposition(reg.render())
    assert fams["gsky_t_requests_total"]["type"] == "counter"
    assert fams["gsky_t_requests_total"]["samples"][
        ("gsky_t_requests_total", (("route", "wms"),))] == 3.0
    assert fams["gsky_t_depth"]["samples"][("gsky_t_depth", ())] == 7.0
    hs = fams["gsky_t_lat"]["samples"]
    assert hs[("gsky_t_lat_bucket",
               (("le", "0.01"), ("op", "warp")))] == 1.0
    assert hs[("gsky_t_lat_bucket",
               (("le", "0.1"), ("op", "warp")))] == 2.0
    assert hs[("gsky_t_lat_count", (("op", "warp"),))] == 2.0
    # collector family survives with escaped label value
    assert fams["gsky_t_extra"]["samples"][
        ("gsky_t_extra", (("k", 'va\\"l'),))] == 1.5


def test_registry_dedupes_by_name():
    reg = Registry()
    a = reg.counter("t_same", "h")
    b = reg.counter("t_same", "other help")
    assert a is b


def test_parser_rejects_sample_without_type():
    with pytest.raises(ValueError):
        parse_exposition("orphan_metric 1\n")


def test_parser_rejects_duplicate_series():
    text = ("# TYPE t_dup counter\n"
            "t_dup 1\n"
            "t_dup 2\n")
    with pytest.raises(ValueError):
        parse_exposition(text)


def test_parser_rejects_malformed_sample():
    with pytest.raises(ValueError):
        parse_exposition("# TYPE t_bad gauge\nt_bad one_point_five\n")


def test_parser_rejects_nonmonotonic_histogram():
    text = ("# TYPE t_hist histogram\n"
            't_hist_bucket{le="0.1"} 5\n'
            't_hist_bucket{le="1"} 3\n'
            't_hist_bucket{le="+Inf"} 5\n'
            "t_hist_count 5\n"
            "t_hist_sum 1\n")
    with pytest.raises(ValueError):
        parse_exposition(text)


def test_parser_rejects_inf_count_mismatch():
    text = ("# TYPE t_hist histogram\n"
            't_hist_bucket{le="+Inf"} 5\n'
            "t_hist_count 6\n"
            "t_hist_sum 1\n")
    with pytest.raises(ValueError):
        parse_exposition(text)


def test_default_registry_renders_parseable():
    # the real module families (requests, stages, rpc...) must always
    # round-trip through the strict parser, even before any traffic
    from gsky_tpu.obs.metrics import render_metrics
    fams = parse_exposition(render_metrics())
    assert "gsky_request_seconds" in fams
    assert "gsky_stage_seconds" in fams


def test_wave_families_render_parse_roundtrip():
    """The wave-scheduler families — kind-labelled dispatch counter,
    occupancy/assembly histograms, and the collector-backed queue
    depth + totals that only report while a scheduler is live — must
    round-trip the strict parser with correct types and values."""
    from gsky_tpu.obs.metrics import (WAVE_ASSEMBLY_MS, WAVE_DISPATCHES,
                                      WAVE_OCCUPANCY, render_metrics)
    from gsky_tpu.pipeline import waves
    waves.reset_waves()
    # module families accumulate for the process: assert on deltas
    base = parse_exposition(render_metrics())
    assert "gsky_wave_readback_queue_depth" not in base  # no scheduler

    def val(fams, fam, name, labels=()):
        if fam not in fams:
            return 0.0
        return fams[fam]["samples"].get((name, labels), 0.0)

    WAVE_DISPATCHES.labels(kind="byte").inc()
    WAVE_DISPATCHES.labels(kind="drill").inc(2)
    WAVE_OCCUPANCY.observe(3.0)
    WAVE_ASSEMBLY_MS.observe(0.5)
    try:
        waves.default_waves()    # threads stay down until a submit
        fams = parse_exposition(render_metrics())
    finally:
        waves.reset_waves()
    disp = "gsky_wave_dispatches_total"
    assert fams[disp]["type"] == "counter"
    assert val(fams, disp, disp, (("kind", "byte"),)) \
        - val(base, disp, disp, (("kind", "byte"),)) == 1.0
    assert val(fams, disp, disp, (("kind", "drill"),)) \
        - val(base, disp, disp, (("kind", "drill"),)) == 2.0
    occ = "gsky_wave_occupancy"
    assert fams[occ]["type"] == "histogram"
    # 3.0 lands in le=4 (cumulative) but not le=2
    for le, d in (("2", 0.0), ("4", 1.0), ("+Inf", 1.0)):
        key = (occ + "_bucket", (("le", le),))
        assert val(fams, occ, *key) - val(base, occ, *key) == d
    asm = "gsky_wave_assembly_ms"
    assert fams[asm]["type"] == "histogram"
    assert val(fams, asm, asm + "_count") \
        - val(base, asm, asm + "_count") == 1.0
    assert fams["gsky_wave_readback_queue_depth"]["type"] == "gauge"
    assert fams["gsky_wave_readback_queue_depth"]["samples"][
        ("gsky_wave_readback_queue_depth", ())] == 0.0
    # the fresh scheduler's lifetime counters all scrape as zero
    for fam in ("gsky_wave_requests_total", "gsky_wave_fallbacks_total",
                "gsky_wave_cancelled_total"):
        assert fams[fam]["type"] == "counter"
        assert fams[fam]["samples"][(fam, ())] == 0.0


def test_mesh_families_render_parse_roundtrip():
    """The mesh families — layout-labelled wave counter, chip
    occupancy / shard skew histograms, and the collector-backed chip
    gauge + per-layout entry totals that only report while a
    dispatcher is live — round-trip the strict parser."""
    from gsky_tpu.mesh import dispatch as MD
    from gsky_tpu.obs.metrics import (MESH_CHIP_OCCUPANCY,
                                      MESH_SHARD_SKEW_MS, MESH_WAVES,
                                      render_metrics)
    MD.reset_mesh()
    base = parse_exposition(render_metrics())
    assert "gsky_mesh_chips" not in base     # no live dispatcher

    def val(fams, fam, name, labels=()):
        if fam not in fams:
            return 0.0
        return fams[fam]["samples"].get((name, labels), 0.0)

    MESH_WAVES.labels(layout="granule").inc()
    MESH_WAVES.labels(layout="time").inc(2)
    MESH_CHIP_OCCUPANCY.observe(2.0)
    MESH_SHARD_SKEW_MS.observe(0.5)
    try:
        md = MD._dispatcher()                # collectors come alive
        md.entries_by_layout["granule"] = 3  # as if one wave ran
        fams = parse_exposition(render_metrics())
    finally:
        MD.reset_mesh()
    waves = "gsky_mesh_waves_total"
    assert fams[waves]["type"] == "counter"
    assert val(fams, waves, waves, (("layout", "granule"),)) \
        - val(base, waves, waves, (("layout", "granule"),)) == 1.0
    assert val(fams, waves, waves, (("layout", "time"),)) \
        - val(base, waves, waves, (("layout", "time"),)) == 2.0
    occ = "gsky_mesh_chip_occupancy"
    assert fams[occ]["type"] == "histogram"
    # 2.0 lands in le=2 (cumulative) but not le=1
    for le, d in (("1", 0.0), ("2", 1.0), ("+Inf", 1.0)):
        key = (occ + "_bucket", (("le", le),))
        assert val(fams, occ, *key) - val(base, occ, *key) == d
    skew = "gsky_mesh_shard_skew_ms"
    assert fams[skew]["type"] == "histogram"
    assert val(fams, skew, skew + "_count") \
        - val(base, skew, skew + "_count") == 1.0
    chips = fams["gsky_mesh_chips"]
    assert chips["type"] == "gauge"
    assert chips["samples"][("gsky_mesh_chips", ())] >= 1.0
    ent = "gsky_mesh_entries_total"
    assert fams[ent]["type"] == "counter"
    assert fams[ent]["samples"][(ent, (("layout", "granule"),))] == 3.0


def test_temporal_families_render_parse_roundtrip():
    """The temporal-serving families — outcome-labelled animation
    sequence counter, frames-per-wave gauge and streamed-DAP4 byte
    counter — render only once either path has served, and round-trip
    the strict parser with correct types and values."""
    from gsky_tpu.obs.metrics import (record_anim_sequence,
                                      record_dap_stream, render_metrics,
                                      reset_temporal, temporal_stats)
    reset_temporal()
    try:
        base = parse_exposition(render_metrics())
        # liveness gating: no sequence and no stream served -> the
        # exposition carries none of the temporal families
        for fam in ("gsky_anim_sequences_total",
                    "gsky_anim_frames_per_wave",
                    "gsky_dap_streamed_bytes_total"):
            assert fam not in base
        record_anim_sequence(24, 2)
        record_anim_sequence(12, 1, degraded=True, cancelled=True)
        record_dap_stream(1 << 20, 4096)
        record_dap_stream(1 << 10, 65536)
        fams = parse_exposition(render_metrics())
        seq = "gsky_anim_sequences_total"
        assert fams[seq]["type"] == "counter"
        assert fams[seq]["samples"][(seq, (("outcome", "ok"),))] == 1.0
        assert fams[seq]["samples"][
            (seq, (("outcome", "cancelled"),))] == 1.0
        fpw = "gsky_anim_frames_per_wave"
        assert fams[fpw]["type"] == "gauge"
        assert fams[fpw]["samples"][(fpw, ())] == 12.0   # 36 / 3
        dap = "gsky_dap_streamed_bytes_total"
        assert fams[dap]["type"] == "counter"
        assert fams[dap]["samples"][(dap, ())] == float(
            (1 << 20) + (1 << 10))
        st = temporal_stats()
        assert st["frames_per_wave"] == 12.0
        assert st["dap_peak_buffer_bytes"] == 65536   # max-tracked
        assert st["degraded"] == 1
    finally:
        reset_temporal()


def test_plan_families_render_parse_roundtrip():
    """The autoplanner families — superblock/bytes-saved counters plus
    the shape- and path-labelled decision counters — must round-trip
    the strict parser.  All four register at import, so their HELP/
    TYPE headers are present even before any planning ran."""
    from gsky_tpu.obs.metrics import (PLAN_BLOCK_SHAPE, PLAN_BYTES_SAVED,
                                      PLAN_ROUTE, PLAN_SUPERBLOCKS,
                                      render_metrics)
    base = parse_exposition(render_metrics())
    for fam in ("gsky_plan_superblocks_total",
                "gsky_plan_gather_bytes_saved_total",
                "gsky_plan_block_shape", "gsky_plan_route_total"):
        assert base[fam]["type"] == "counter"

    def val(fams, fam, name, labels=()):
        if fam not in fams:
            return 0.0
        return fams[fam]["samples"].get((name, labels), 0.0)

    PLAN_SUPERBLOCKS.inc(2.0)
    PLAN_BYTES_SAVED.inc(4096.0)
    PLAN_BLOCK_SHAPE.labels(shape="256x256").inc()
    PLAN_ROUTE.labels(path="ragged").inc()
    PLAN_ROUTE.labels(path="bucketed").inc(2)
    fams = parse_exposition(render_metrics())
    sb = "gsky_plan_superblocks_total"
    assert val(fams, sb, sb) - val(base, sb, sb) == 2.0
    sv = "gsky_plan_gather_bytes_saved_total"
    assert val(fams, sv, sv) - val(base, sv, sv) == 4096.0
    sh = "gsky_plan_block_shape"
    assert val(fams, sh, sh, (("shape", "256x256"),)) \
        - val(base, sh, sh, (("shape", "256x256"),)) == 1.0
    rt = "gsky_plan_route_total"
    assert val(fams, rt, rt, (("path", "ragged"),)) \
        - val(base, rt, rt, (("path", "ragged"),)) == 1.0
    assert val(fams, rt, rt, (("path", "bucketed"),)) \
        - val(base, rt, rt, (("path", "bucketed"),)) == 2.0


def test_fabric_families_render_parse_roundtrip(monkeypatch):
    """The cache-fabric families — outcome-labelled replay counter,
    source-labelled page-fill counter, and the replication gauge —
    must round-trip the strict parser.  The gauge only renders with
    the fabric on (or after a replication round), keeping fabric-less
    exposition byte-identical."""
    from gsky_tpu.fabric import replicate
    from gsky_tpu.obs.metrics import (FABRIC_PAGE_FILLS, FABRIC_REPLAY,
                                      render_metrics)
    base = parse_exposition(render_metrics())
    for fam in ("gsky_fabric_replay_total",
                "gsky_fabric_page_fills_total"):
        assert base[fam]["type"] == "counter"
    assert "gsky_fabric_replica_pages" not in base  # fabric off: absent

    def val(fams, fam, name, labels=()):
        if fam not in fams:
            return 0.0
        return fams[fam]["samples"].get((name, labels), 0.0)

    monkeypatch.setenv("GSKY_FABRIC", "1")
    FABRIC_REPLAY.labels(outcome="hit").inc()
    FABRIC_REPLAY.labels(outcome="breaker_open").inc(3)
    FABRIC_PAGE_FILLS.labels(source="peer").inc(2)
    FABRIC_PAGE_FILLS.labels(source="cold").inc()
    fams = parse_exposition(render_metrics())
    rp = "gsky_fabric_replay_total"
    assert val(fams, rp, rp, (("outcome", "hit"),)) \
        - val(base, rp, rp, (("outcome", "hit"),)) == 1.0
    assert val(fams, rp, rp, (("outcome", "breaker_open"),)) \
        - val(base, rp, rp, (("outcome", "breaker_open"),)) == 3.0
    pf = "gsky_fabric_page_fills_total"
    assert val(fams, pf, pf, (("source", "peer"),)) \
        - val(base, pf, pf, (("source", "peer"),)) == 2.0
    assert val(fams, pf, pf, (("source", "cold"),)) \
        - val(base, pf, pf, (("source", "cold"),)) == 1.0
    rg = "gsky_fabric_replica_pages"
    assert fams[rg]["type"] == "gauge"
    assert val(fams, rg, rg) == float(
        replicate.stats()["replica_pages"])


def test_expr_families_render_parse_roundtrip():
    """The fused band-algebra families — compile-cache counters, the
    distinct-program gauge and the path-labelled dispatch counter —
    render only once the expression tier has seen traffic (an
    expression-free process keeps its exposition byte-identical) and
    round-trip the strict parser."""
    from gsky_tpu.obs.metrics import render_metrics
    from gsky_tpu.ops import paged
    from gsky_tpu.ops.expr import compile_expr, reset_expr_cache
    reset_expr_cache()
    paged.reset_expr_fused_stats()
    base = parse_exposition(render_metrics())
    assert "gsky_expr_programs" not in base
    assert "gsky_expr_cache_hits_total" not in base
    assert "gsky_expr_fused_total" not in base
    try:
        compile_expr("a / (b + 1.5)")           # miss
        compile_expr("a / (b + 1.5)")           # hit
        paged.note_expr_program("cafe01234567")
        paged.note_expr_fused("wave")
        paged.note_expr_fused("wave")
        paged.note_expr_fused("unfused")
        fams = parse_exposition(render_metrics())
    finally:
        reset_expr_cache()
        paged.reset_expr_fused_stats()
    hits = "gsky_expr_cache_hits_total"
    miss = "gsky_expr_cache_misses_total"
    assert fams[hits]["type"] == "counter"
    assert fams[hits]["samples"][(hits, ())] == 1.0
    assert fams[miss]["type"] == "counter"
    assert fams[miss]["samples"][(miss, ())] == 1.0
    prog = "gsky_expr_programs"
    assert fams[prog]["type"] == "gauge"
    assert fams[prog]["samples"][(prog, ())] == 1.0
    fused = "gsky_expr_fused_total"
    assert fams[fused]["type"] == "counter"
    assert fams[fused]["samples"][(fused, (("path", "wave"),))] == 2.0
    assert fams[fused]["samples"][
        (fused, (("path", "unfused"),))] == 1.0


# ---------------------------------------------------------------------------
# trace context


def test_span_nesting_parent_ids():
    with obs.start_trace("req", process="gateway") as tr:
        assert obs.current_trace_id() == tr.trace_id
        with obs.span("outer") as outer:
            with obs.span("inner") as inner:
                obs.set_attr(deep=True)
            assert inner.parent_id == outer.span_id
        assert outer.parent_id == tr.root.span_id
    spans = {s["name"]: s for s in tr.span_dicts()}
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["outer"]["parent_id"] == spans["req"]["span_id"]
    assert spans["inner"]["attrs"]["deep"] is True
    assert all(s["dur_s"] is not None for s in spans.values())
    assert obs.current_trace_id() is None      # context restored


def test_span_records_error_attr():
    with obs.start_trace("req") as tr:
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("nope")
    sp = [s for s in tr.span_dicts() if s["name"] == "boom"][0]
    assert sp["attrs"]["error"] == "RuntimeError"


def test_event_lands_on_root():
    with obs.start_trace("req") as tr:
        with obs.span("child"):
            obs.event("retry", site="mas")
    root = tr.span_dicts()[0]
    assert root["events"][0]["name"] == "retry"
    assert root["events"][0]["site"] == "mas"


def test_record_span_closed_interval():
    with obs.start_trace("req") as tr:
        obs.record_span("admission.wait", 0.25, queued=3)
    sp = [s for s in tr.span_dicts() if s["name"] == "admission.wait"][0]
    assert sp["dur_s"] == 0.25
    assert sp["attrs"]["queued"] == 3


def test_seconds_by_name_sums_same_named_spans_and_skips_open_ones():
    with obs.start_trace("req") as tr:
        obs.record_span("drill.prepare", 0.25)
        obs.record_span("drill.prepare", 0.5)
        obs.record_span("drill.index", 0.125)
        with obs.span("wps.format"):
            with obs.span("inner"):
                pass
            # wps.format is still open here: in nobody's sum yet
            mid = tr.seconds_by_name()
            assert tr.count("wps.format") == 0
    assert mid["drill.prepare"] == 0.75 and mid["drill.index"] == 0.125
    assert "wps.format" not in mid and "req" not in mid
    assert mid["inner"] >= 0
    done = tr.seconds_by_name()
    assert done["wps.format"] >= done["inner"]
    assert tr.count("drill.prepare") == 2 and tr.count("wps.format") == 1
    assert tr.age_s() >= done["wps.format"]


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    from gsky_tpu.obs import trace as trace_mod
    monkeypatch.setattr(_Annotation, "log", [])
    monkeypatch.setattr(trace_mod, "_ANNOTATION", _Annotation)
    return _Annotation.log


def test_span_holds_one_annotation_of_its_name(annotations):
    with obs.start_trace("req"):
        with obs.span("drill.device", bands=3):
            assert annotations == [("enter", "drill.device")]
    assert annotations == [("enter", "drill.device"),
                           ("exit", "drill.device")]


def test_span_leaves_its_annotation_on_an_exception(annotations):
    with obs.start_trace("req") as tr:
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("nope")
    assert annotations == [("enter", "boom"), ("exit", "boom")]
    sp = [s for s in tr.span_dicts() if s["name"] == "boom"][0]
    assert sp["attrs"]["error"] == "RuntimeError"


def test_untraced_span_opens_no_annotation(annotations, monkeypatch):
    with obs.span("orphan"):
        pass
    monkeypatch.setenv("GSKY_TRACE", "0")
    with obs.start_trace("req"):
        with obs.span("child"):
            pass
    assert annotations == []


def test_the_annotation_is_the_profilers(monkeypatch):
    """Resolved by the first traced span, not at import."""
    import jax.profiler
    from gsky_tpu.obs import trace as trace_mod
    monkeypatch.setattr(trace_mod, "_ANNOTATION", None)
    with obs.start_trace("req"):
        with obs.span("child"):
            pass
    assert trace_mod._ANNOTATION is jax.profiler.TraceAnnotation


def test_trace_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("GSKY_TRACE", "0")
    rec = obs.default_recorder()
    before = rec.stats()["recorded"]
    with obs.start_trace("req") as tr:
        assert tr is None
        with obs.span("child") as sp:
            sp.set(ignored=1)        # no-op handle must accept set/event
            sp.event("x")
        assert obs.current_trace_id() is None
        assert obs.traceparent() is None
        obs.event("retry")           # must not raise untraced
        obs.record_span("x", 0.1)
    assert rec.stats()["recorded"] == before


def test_untraced_span_is_null_handle():
    with obs.span("orphan") as sp:
        sp.set(a=1)
    assert obs.current_trace_id() is None


def test_completed_trace_reaches_recorder():
    with obs.start_trace("req") as tr:
        tr.status = 200
    got = obs.default_recorder().lookup(tr.trace_id)
    assert got is not None and got["status"] == 200


def test_async_task_and_to_thread_propagation():
    async def main():
        with obs.start_trace("req") as tr:
            async def subtask():
                with obs.span("task.child"):
                    await asyncio.sleep(0)
                return obs.current_trace_id()

            def thread_work():
                with obs.span("thread.child"):
                    return obs.current_trace_id()

            tid_task = await asyncio.create_task(subtask())
            tid_thread = await asyncio.to_thread(thread_work)
            return tr, tid_task, tid_thread

    tr, tid_task, tid_thread = asyncio.run(main())
    assert tid_task == tr.trace_id
    assert tid_thread == tr.trace_id
    names = {s["name"] for s in tr.span_dicts()}
    assert {"task.child", "thread.child"} <= names


def test_raw_thread_starts_empty_and_bind_restores():
    seen = {}

    def worker(ctx):
        seen["bare"] = obs.current_trace_id()
        with obs.bind(ctx):
            seen["bound"] = obs.current_trace_id()
        seen["after"] = obs.current_trace_id()

    with obs.start_trace("req") as tr:
        t = threading.Thread(target=worker, args=(obs.current_context(),))
        t.start()
        t.join()
    assert seen["bare"] is None
    assert seen["bound"] == tr.trace_id
    assert seen["after"] is None


def test_copy_context_per_job_fanout():
    # the worker client's warp_many idiom: one copy_context() per job,
    # copied in the caller, entered in the pool thread
    from concurrent.futures import ThreadPoolExecutor

    def job(_):
        with obs.span("fan.child"):
            return obs.current_trace_id()

    with obs.start_trace("req") as tr:
        with ThreadPoolExecutor(max_workers=4) as pool:
            args = [(contextvars.copy_context(), i) for i in range(8)]
            tids = list(pool.map(lambda a: a[0].run(job, a[1]), args))
    assert set(tids) == {tr.trace_id}
    fan = [s for s in tr.span_dicts() if s["name"] == "fan.child"]
    assert len(fan) == 8


def test_encode_pool_carries_trace():
    from gsky_tpu.io.png import encode_png, encode_async, reset_encode_pool
    reset_encode_pool()
    arr = np.zeros((4, 4), dtype=np.uint8)

    async def main():
        with obs.start_trace("req") as tr:
            out = await encode_async(encode_png, [arr, arr, arr])
        return tr, out

    tr, out = asyncio.run(main())
    assert out[:4] == b"\x89PNG"
    enc = [s for s in tr.span_dicts() if s["name"] == "encode"]
    assert len(enc) == 1 and "cpu_s" in enc[0]["attrs"]
    reset_encode_pool()


def test_traceparent_and_remote_trace_roundtrip():
    with obs.start_trace("req") as tr:
        header = obs.traceparent()
        assert header == f"{tr.trace_id}-{tr.root.span_id}"
    with obs.remote_trace(header, "worker.warp") as wt:
        assert wt.trace_id == tr.trace_id
        assert wt.root.parent_id == tr.root.span_id
        with obs.span("worker.decode"):
            pass
    shipped = wt.span_dicts()
    assert [s["name"] for s in shipped] == ["worker.warp", "worker.decode"]
    assert all(s["process"] == "worker" for s in shipped)


def test_remote_trace_rejects_bad_headers():
    for header in (None, "", "justonepart", "-", "tid-"):
        with obs.remote_trace(header, "worker.warp") as wt:
            assert wt is None


def test_adopt_spans_stitches_into_live_trace():
    foreign = [{"span_id": "f1", "parent_id": "p0", "name": "worker.warp",
                "process": "worker", "t0": 1.0, "dur_s": 0.5}]
    with obs.start_trace("req") as tr:
        obs.adopt_spans(foreign)
        obs.adopt_spans(None)        # tolerated
    assert any(s["name"] == "worker.warp" and s["process"] == "worker"
               for s in tr.span_dicts())
    obs.adopt_spans(foreign)         # untraced: silently dropped


def test_resilience_note_event_ticks_counter_and_trace():
    rr = importlib.import_module("gsky_tpu.resilience.registry")
    from gsky_tpu.obs.metrics import TRACE_EVENTS
    child = TRACE_EVENTS.labels(kind="retry")
    before = child.value
    with obs.start_trace("req") as tr:
        rr.note_event("retry", site="mas")
    assert child.value == before + 1
    root = tr.span_dicts()[0]
    assert any(e["name"] == "retry" and e.get("site") == "mas"
               for e in root["events"])


def test_breaker_open_emits_trace_event():
    from gsky_tpu.resilience.breaker import CircuitBreaker
    br = CircuitBreaker("t-node", failure_threshold=2, register=False)
    with obs.start_trace("req") as tr:
        br.record_failure()
        br.record_failure()          # trips open
        br.record_failure()          # already open: no second event
    root = tr.span_dicts()[0]
    opens = [e for e in root.get("events", ())
             if e["name"] == "breaker_open"]
    assert len(opens) == 1 and opens[0]["site"] == "t-node"


# ---------------------------------------------------------------------------
# flight recorder


def _mk_trace(tid, dur_s, status=200, degraded=(), spans=None):
    return {"trace_id": tid, "name": "req", "t0": 100.0, "dur_s": dur_s,
            "status": status, "degraded": list(degraded),
            "spans": spans or [{"span_id": tid + "-r", "parent_id": None,
                                "name": "req", "process": "gateway",
                                "t0": 100.0, "dur_s": dur_s}]}


def test_ring_eviction_counts():
    rec = FlightRecorder(capacity=4, reservoir=2, slo_s=10.0, sample=0.0)
    for i in range(10):
        rec.record(_mk_trace(f"t{i}", 0.01))
    st = rec.stats()
    assert st["recorded"] == 10
    assert st["retained"] == 4
    assert st["evicted"] == 6
    assert st["reservoir"] == 0      # all fast and healthy
    assert [t["trace_id"] for t in rec.traces()] == ["t6", "t7", "t8", "t9"]
    assert rec.lookup("t0") is None
    assert rec.lookup("t9") is not None


def test_reservoir_keeps_slowest_interesting():
    rec = FlightRecorder(capacity=2, reservoir=2, slo_s=0.5, sample=0.0)
    for i, dur in enumerate((0.6, 0.9, 0.7)):   # all violate the SLO
        rec.record(_mk_trace(f"slow{i}", dur))
    for i in range(5):                          # fast burst evicts the ring
        rec.record(_mk_trace(f"fast{i}", 0.01))
    st = rec.stats()
    assert st["slo_violations"] == 3
    assert st["reservoir"] == 2
    kept = {t["trace_id"] for t in rec.traces()}
    # ring holds the two newest; reservoir held the two *slowest*
    assert {"fast3", "fast4", "slow1", "slow2"} <= kept
    assert "slow0" not in kept                  # fastest interesting evicted
    assert rec.slowest()["trace_id"] == "slow1"
    assert rec.lookup("slow1")["dur_s"] == 0.9


def test_degraded_and_5xx_are_interesting():
    rec = FlightRecorder(capacity=1, reservoir=4, slo_s=10.0, sample=0.0)
    rec.record(_mk_trace("deg", 0.01, degraded=["mas"]))
    rec.record(_mk_trace("err", 0.01, status=503))
    rec.record(_mk_trace("ok", 0.01))
    kept = {t["trace_id"] for t in rec.traces()}
    assert {"deg", "err"} <= kept               # survived ring eviction
    summ = {r["trace_id"]: r for r in rec.summary()}
    assert summ["deg"]["degraded"] == ["mas"]
    assert summ["deg"]["processes"] == ["gateway"]


def test_slo_file_export(tmp_path):
    path = tmp_path / "traces.jsonl"
    rec = FlightRecorder(capacity=4, reservoir=2, slo_s=0.5,
                         trace_file=str(path), sample=0.0)
    rec.record(_mk_trace("fast", 0.01))         # not sampled, not slow
    rec.record(_mk_trace("slow", 0.8))          # SLO violation: always dumped
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [t["trace_id"] for t in lines] == ["slow"]
    # sample=1.0 writes healthy traffic too
    rec2 = FlightRecorder(capacity=4, reservoir=2, slo_s=0.5,
                          trace_file=str(path), sample=1.0)
    rec2.record(_mk_trace("sampled", 0.01))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [t["trace_id"] for t in lines] == ["slow", "sampled"]


def test_recorder_env_knobs(monkeypatch, tmp_path):
    monkeypatch.setenv("GSKY_TRACE_RING", "7")
    monkeypatch.setenv("GSKY_TRACE_RESERVOIR", "3")
    monkeypatch.setenv("GSKY_TRACE_SLO_S", "1.5")
    monkeypatch.setenv("GSKY_TRACE_FILE", str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("GSKY_TRACE_SAMPLE", "0.25")
    reset_recorder()
    rec = obs.default_recorder()
    assert rec.capacity == 7
    assert rec.reservoir_cap == 3
    assert rec.slo_s == 1.5
    assert rec.trace_file == str(tmp_path / "t.jsonl")
    assert rec.sample == 0.25


def test_dump_jsonl_roundtrip():
    rec = FlightRecorder(capacity=4, reservoir=2, slo_s=10.0, sample=0.0)
    rec.record(_mk_trace("a", 0.01))
    rec.record(_mk_trace("b", 0.02))
    docs = [json.loads(ln) for ln in rec.dump_jsonl().splitlines()]
    assert [d["trace_id"] for d in docs] == ["a", "b"]


# ---------------------------------------------------------------------------
# gRPC metadata hop (fake worker echoes the header and ships spans back)


class _EchoService:
    """Stands in for WorkerService: reads x-gsky-trace off the call
    metadata, opens worker-side spans under remote_trace, and ships
    them back in the Result's info envelope — the real backhaul path."""

    def process(self, task, ctx=None):
        from gsky_tpu.worker import gskyrpc_pb2 as pb
        header = None
        if ctx is not None:
            for k, v in ctx.invocation_metadata():
                if k == "x-gsky-trace":
                    header = v
        res = pb.Result()
        with obs.remote_trace(header, "worker.warp") as wtrace:
            with obs.span("worker.decode") as sp:
                sp.set(bytes_read=123)
            env = {"echo": header}
            if wtrace is not None:
                env["spans"] = wtrace.span_dicts()
        res.info_json = json.dumps(env)
        return res


@pytest.fixture
def echo_worker():
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from gsky_tpu.worker.server import make_grpc_server
    svc = _EchoService()
    server = make_grpc_server(svc, "127.0.0.1:0")
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    yield f"127.0.0.1:{port}"
    server.stop(grace=None)


def _warp_task():
    from gsky_tpu.worker import gskyrpc_pb2 as pb
    return pb.Task(operation="warp")


def test_grpc_hop_stitches_worker_spans(echo_worker):
    from gsky_tpu.worker.client import WorkerClient
    client = WorkerClient([echo_worker])
    try:
        with obs.start_trace("req") as tr:
            expected = obs.traceparent()
            res = client.process(_warp_task())
        env = json.loads(res.info_json)
        assert env["echo"] == expected           # header crossed the wire
        spans = tr.span_dicts()
        worker = {s["name"]: s for s in spans if s["process"] == "worker"}
        assert set(worker) == {"worker.warp", "worker.decode"}
        assert worker["worker.warp"]["parent_id"] == expected.split("-")[1]
        assert worker["worker.decode"]["parent_id"] == \
            worker["worker.warp"]["span_id"]
        assert worker["worker.decode"]["attrs"]["bytes_read"] == 123
        # the client's own rpc span is part of the same tree
        assert any(s["name"] == "rpc.worker" for s in spans)
    finally:
        client.close()


def test_grpc_hop_untraced_sends_no_header(echo_worker, monkeypatch):
    monkeypatch.setenv("GSKY_TRACE", "0")
    from gsky_tpu.worker.client import WorkerClient
    client = WorkerClient([echo_worker])
    try:
        with obs.start_trace("req") as tr:
            assert tr is None
            res = client.process(_warp_task())
        assert json.loads(res.info_json)["echo"] is None
    finally:
        client.close()


# ---------------------------------------------------------------------------
# trace_view waterfall


def _synthetic_trace():
    # root 100ms; two children: fetch ends at 60ms, render ends at 95ms
    # with a nested device span — critical path is root -> render -> device
    return {
        "trace_id": "abc123", "name": "ows.request", "t0": 10.0,
        "dur_s": 0.1, "status": 200, "degraded": [],
        "spans": [
            {"span_id": "r", "parent_id": None, "name": "ows.request",
             "process": "gateway", "t0": 10.0, "dur_s": 0.1},
            {"span_id": "a", "parent_id": "r", "name": "fetch",
             "process": "gateway", "t0": 10.01, "dur_s": 0.05},
            {"span_id": "b", "parent_id": "r", "name": "render",
             "process": "gateway", "t0": 10.02, "dur_s": 0.075,
             "attrs": {"error": "TimeoutError"}},
            {"span_id": "c", "parent_id": "b", "name": "worker.dispatch",
             "process": "worker", "t0": 10.03, "dur_s": 0.05},
        ],
    }


def test_critical_path_latest_end_chain():
    path = trace_view.critical_path(_synthetic_trace())
    assert [s["name"] for s in path] == \
        ["ows.request", "render", "worker.dispatch"]


def test_critical_breakdown_exclusive_ms():
    bd = {d["name"]: d["exclusive_ms"]
          for d in trace_view.critical_breakdown(_synthetic_trace())}
    assert bd["ows.request"] == pytest.approx(25.0)   # 100 - 75
    assert bd["render"] == pytest.approx(25.0)        # 75 - 50
    assert bd["worker.dispatch"] == pytest.approx(50.0)


def test_render_waterfall_text():
    out = trace_view.render(_synthetic_trace(), width=20)
    lines = out.splitlines()
    assert lines[0].startswith("trace abc123  ows.request  100.0ms")
    assert "status=200" in lines[0]
    body = "\n".join(lines)
    assert "!TimeoutError" in body                    # error flag shown
    assert "worker" in body                           # process column
    # critical-path rows are starred; fetch is off-path
    starred = [ln for ln in lines if " * " in ln]
    assert len(starred) == 3
    assert not any("fetch" in ln for ln in starred)
    assert lines[-1].startswith("critical path (exclusive ms):")
    assert "worker/worker.dispatch 50.00" in lines[-1]


def test_render_orphan_spans_hang_off_root():
    tr = _synthetic_trace()
    tr["spans"].append({"span_id": "x", "parent_id": "gone",
                        "name": "orphan", "process": "worker",
                        "t0": 10.04, "dur_s": 0.01})
    out = trace_view.render(tr)
    assert "orphan" in out                            # not silently dropped


def test_render_events_line():
    tr = _synthetic_trace()
    tr["spans"][0]["events"] = [
        {"name": "retry", "t": 10.01, "site": "mas"},
        {"name": "hedge", "t": 10.02}]
    out = trace_view.render(tr)
    assert "events: retry(mas), hedge" in out


def test_load_trace_rejects_listing(tmp_path):
    p = tmp_path / "listing.json"
    p.write_text(json.dumps({"traces": [{"trace_id": "a"}]}))
    with pytest.raises(SystemExit):
        trace_view.load_trace(str(p))


def test_load_trace_file(tmp_path):
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(_synthetic_trace()))
    doc = trace_view.load_trace(str(p))
    assert doc["trace_id"] == "abc123"


def test_overload_series_roundtrip_strict_parser():
    """The overload-survival collector families (adaptive limits,
    per-tenant queue depth, cancellations by stage, pressure state)
    must round-trip the strict parser with live data behind them."""
    from gsky_tpu.obs.metrics import render_metrics
    from gsky_tpu.resilience import reset_cancel_stats
    from gsky_tpu.resilience.cancel import CancelToken, RequestCancelled
    from gsky_tpu.resilience.pressure import default_monitor
    from gsky_tpu.serving import default_gateway

    reset_cancel_stats()
    tok = CancelToken()
    tok.cancel("test")
    with pytest.raises(RequestCancelled):
        tok.check("decode")
    default_monitor().force(1)
    adm = default_gateway.admission
    st = adm._state("WMS")
    try:
        with adm._lock:
            st.tenant_queued["10.0.0.9"] = 3
        fams = parse_exposition(render_metrics())
        assert fams["gsky_admit_limit"]["type"] == "gauge"
        limits = fams["gsky_admit_limit"]["samples"]
        assert limits[("gsky_admit_limit",
                       (("class", "WMS"),))] == float(st.limit)
        depth = fams["gsky_admit_queue_depth"]["samples"]
        assert depth[("gsky_admit_queue_depth",
                      (("tenant_class", "10.0.0.9/WMS"),))] == 3.0
        cancelled = fams["gsky_cancelled_total"]
        assert cancelled["type"] == "counter"
        assert cancelled["samples"][
            ("gsky_cancelled_total", (("stage", "decode"),))] == 1.0
        assert fams["gsky_pressure_state"]["samples"][
            ("gsky_pressure_state", ())] == 1.0
    finally:
        with adm._lock:
            st.tenant_queued.pop("10.0.0.9", None)
        default_monitor().force(None)
        default_monitor().reset()
        reset_cancel_stats()


def test_device_series_roundtrip_strict_parser():
    """The device-guard collector families (supervisor state, rebuild
    and hang counters, incident kinds, warm-recovery volume) must
    round-trip the strict parser with live supervisor state behind
    them."""
    from gsky_tpu import device_guard as dg
    from gsky_tpu.obs.metrics import render_metrics

    sup = dg.default_supervisor()
    sup.reset()
    try:
        sup.record_hang("t.obs")
        sup.record_oom("t.obs", RuntimeError("RESOURCE_EXHAUSTED: x"))
        fams = parse_exposition(render_metrics())

        state = fams["gsky_device_state"]
        assert state["type"] == "gauge"
        assert state["samples"][("gsky_device_state", ())] == 1.0
        assert fams["gsky_device_reinits_total"]["type"] == "counter"
        assert fams["gsky_device_reinits_total"]["samples"][
            ("gsky_device_reinits_total", ())] == 0.0
        hangs = fams["gsky_device_hangs_total"]
        assert hangs["type"] == "counter"
        assert hangs["samples"][("gsky_device_hangs_total", ())] == 1.0
        inc = fams["gsky_device_incidents_total"]["samples"]
        assert inc[("gsky_device_incidents_total",
                    (("kind", "oom"),))] == 1.0
        assert inc[("gsky_device_incidents_total",
                    (("kind", "crash"),))] == 0.0
        rehyd = fams["gsky_pool_rehydrated_pages_total"]
        assert rehyd["type"] == "counter"
        assert rehyd["samples"][
            ("gsky_pool_rehydrated_pages_total", ())] == 0.0
    finally:
        sup.reset()


def test_ingest_series_roundtrip_strict_parser():
    """The ingest collector families (ranged-read volume, prefetch
    outcomes, overlap ratio) must round-trip the strict parser with
    live ledger data behind them."""
    from gsky_tpu.ingest import stats as ingest_stats
    from gsky_tpu.obs.metrics import render_metrics

    ingest_stats.reset()
    try:
        ingest_stats.record_ranged(3, 4096, seconds=0.2)
        with ingest_stats.dispatch_inflight():
            ingest_stats.record_ranged(1, 1024, seconds=0.1)
        ingest_stats.record_prefetch("hit", 2)
        ingest_stats.record_prefetch("miss")
        ingest_stats.record_prefetch("wasted", 3)
        fams = parse_exposition(render_metrics())

        assert fams["gsky_ranged_reads_total"]["type"] == "counter"
        assert fams["gsky_ranged_reads_total"]["samples"][
            ("gsky_ranged_reads_total", ())] == 4.0
        assert fams["gsky_ranged_read_bytes_total"]["samples"][
            ("gsky_ranged_read_bytes_total", ())] == 5120.0
        pf = fams["gsky_prefetch_total"]
        assert pf["type"] == "counter"
        assert pf["samples"][
            ("gsky_prefetch_total", (("outcome", "hit"),))] == 2.0
        assert pf["samples"][
            ("gsky_prefetch_total", (("outcome", "miss"),))] == 1.0
        assert pf["samples"][
            ("gsky_prefetch_total", (("outcome", "wasted"),))] == 3.0
        ratio = fams["gsky_ingest_overlap_ratio"]
        assert ratio["type"] == "gauge"
        got = ratio["samples"][("gsky_ingest_overlap_ratio", ())]
        # 0.1 of 0.3 read-seconds overlapped a dispatch
        assert got == pytest.approx(0.1 / 0.3, rel=1e-4)
    finally:
        ingest_stats.reset()


def test_elastic_families_render_parse_roundtrip():
    """The elastic-fleet families — node-state gauge, direction-labelled
    decision counter, graceful-labelled preemption counter, and the
    source-labelled handoff-page counter — round-trip the strict
    parser, and are ABSENT while the subsystem is dormant so a fixed
    fleet's exposition stays byte-identical."""
    from gsky_tpu.fleet import elastic
    from gsky_tpu.obs.metrics import render_metrics

    elastic.reset_stats()
    base = parse_exposition(render_metrics())
    for fam in ("gsky_elastic_nodes", "gsky_elastic_decisions_total",
                "gsky_preemptions_total", "gsky_handoff_pages_total"):
        assert fam not in base                 # dormant: absent

    class _Scaler:                             # quacks like Autoscaler
        name = "t-obs"

        def node_counts(self):
            return {"active": 3, "pending": 1, "leaving": 0}

    scaler = _Scaler()                         # keep alive: WeakSet
    elastic.register_autoscaler(scaler)
    elastic.note_decision("up")
    elastic.note_decision("up")
    elastic.note_decision("down")
    elastic.note_preemption(graceful=True)
    elastic.note_preemption(graceful=False)
    elastic.note_handoff_pages("peer", 40)
    elastic.note_handoff_pages("cold", 8)
    try:
        fams = parse_exposition(render_metrics())

        def val(fam, labels=()):
            return fams[fam]["samples"].get((fam, labels))

        ng = "gsky_elastic_nodes"
        assert fams[ng]["type"] == "gauge"
        assert val(ng, (("state", "active"),)) == 3.0
        assert val(ng, (("state", "pending"),)) == 1.0
        dc = "gsky_elastic_decisions_total"
        assert fams[dc]["type"] == "counter"
        assert val(dc, (("dir", "up"),)) == 2.0
        assert val(dc, (("dir", "down"),)) == 1.0
        pc = "gsky_preemptions_total"
        assert val(pc, (("graceful", "true"),)) == 1.0
        assert val(pc, (("graceful", "false"),)) == 1.0
        hp = "gsky_handoff_pages_total"
        assert val(hp, (("source", "peer"),)) == 40.0
        assert val(hp, (("source", "cold"),)) == 8.0
    finally:
        elastic.reset_stats()
    # counters zeroed and the scaler garbage-collectable -> dormant
    # again once the registry drops it (WeakSet); force it
    import gc
    del scaler
    gc.collect()
    after = parse_exposition(render_metrics())
    assert "gsky_elastic_decisions_total" not in after
