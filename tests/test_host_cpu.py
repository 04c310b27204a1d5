"""What a host stage ran and what it waited: a span's thread CPU
(`obs/trace.py`), its folds into `/debug` `tile_stages` and
`drill_stages` (`server/metrics.py`), the encode job's CPU
(`io/png.py`), the dispatch gate's wait (`pipeline/tile_stages.py`) and
the cyclic collector's pauses (`obs/process.py`)."""

import asyncio
import gc
import json
import threading
import time

import numpy as np
import pytest

from gsky_tpu import obs
from gsky_tpu.obs import process
from gsky_tpu.server.metrics import MetricsLogger


_thread_time = time.thread_time    # the clock, whatever a test patches


def _spin(seconds):
    """Burn this thread's CPU for about `seconds` of it."""
    c0 = _thread_time()
    x = 0
    while _thread_time() - c0 < seconds:
        x += 1
    return x


def _span(tr, name):
    return [s for s in tr.span_dicts() if s["name"] == name]


def _in_thread(fn):
    out = {}

    def run():
        out["value"] = fn()
    t = threading.Thread(target=run)
    t.start()
    t.join(30)
    assert not t.is_alive()
    return out.get("value")


# -- a span's thread CPU ---------------------------------------------------

def test_a_span_that_spins_on_a_worker_thread_is_cpu_for_its_length():
    with obs.start_trace("req") as tr:
        ctx = obs.current_context()

        def work():
            with obs.bind(ctx), obs.span("tile.dispatch") as sp:
                _spin(0.05)
            return sp
        sp = _in_thread(work)
    assert sp.cpu_s >= 0.05
    # the thread spun the whole span, so its CPU is its wall or a shade
    # under where the scheduler took the core away for a moment
    assert 0.5 * sp.dur_s <= sp.cpu_s <= sp.dur_s + 1e-3
    assert _span(tr, "tile.dispatch")[0]["cpu_s"] == sp.cpu_s


def test_a_span_that_sleeps_runs_no_cpu():
    with obs.start_trace("req") as tr:
        ctx = obs.current_context()

        def work():
            with obs.bind(ctx), obs.span("tile.readback") as sp:
                time.sleep(0.1)
            return sp
        sp = _in_thread(work)
    assert sp.dur_s >= 0.1
    assert 0.0 <= sp.cpu_s < 0.02


def test_a_span_on_an_event_loop_thread_reads_no_cpu_clock(monkeypatch):
    reads = []
    real = time.thread_time
    loop_thread = []

    def counted():
        if loop_thread and threading.get_ident() == loop_thread[0]:
            reads.append(1)
        return real()
    monkeypatch.setattr(time, "thread_time", counted)

    async def handler():
        loop_thread.append(threading.get_ident())
        with obs.start_trace("req") as tr:
            with obs.span("encode") as sp:
                _spin(0.01)
                await asyncio.sleep(0)
        return tr, sp
    tr, sp = asyncio.run(handler())
    assert reads == []
    assert sp.cpu_s is None
    assert "cpu_s" not in _span(tr, "encode")[0]
    assert tr.cpu_by_name() == {}


def test_a_span_closed_on_another_thread_carries_no_cpu():
    with obs.start_trace("req") as tr:
        with obs.span("tile.plan") as sp:
            _in_thread(sp.close)
    assert sp.dur_s is not None and sp.cpu_s is None
    assert "cpu_s" not in _span(tr, "tile.plan")[0]


def test_a_parent_holds_its_childrens_cpu_and_recorded_spans_carry_none():
    def work():
        with obs.span("tile.plan") as plan:
            _spin(0.01)
            with obs.span("tile.index") as index:
                _spin(0.02)
        return plan, index
    with obs.start_trace("req") as tr:
        plan, index = work()
        obs.record_span("gateway.admission", 0.5)
    assert plan.cpu_s >= index.cpu_s + 0.01
    assert tr.root.cpu_s is None
    rec = _span(tr, "gateway.admission")[0]
    assert rec["dur_s"] == 0.5 and "cpu_s" not in rec


def test_cpu_by_name_sums_the_spans_that_carry_cpu():
    with obs.start_trace("req") as tr:
        for _ in range(2):
            with obs.span("drill.device"):
                _spin(0.005)
        obs.record_span("drill.device", 1.0)        # a wall, no CPU
        with obs.span("drill.merge"):
            with obs.span("inner"):
                # still open: in nobody's sum yet
                assert "drill.merge" not in tr.cpu_by_name()
    cpu = tr.cpu_by_name()
    spans = [s for s in tr.span_dicts() if s["name"] == "drill.device"]
    assert cpu["drill.device"] == pytest.approx(
        sum(s.get("cpu_s", 0.0) for s in spans))
    assert cpu["drill.device"] >= 0.01
    assert tr.seconds_by_name()["drill.device"] >= 1.0
    assert set(cpu) == {"drill.device", "drill.merge", "inner"}
    assert "req" not in cpu


def test_an_untraced_span_reads_no_clock(monkeypatch):
    reads = []
    monkeypatch.setattr(time, "thread_time", lambda: reads.append(1) or 0.0)
    with obs.span("orphan"):
        pass
    monkeypatch.setenv("GSKY_TRACE", "0")
    with obs.start_trace("req"):
        with obs.span("child"):
            pass
    assert reads == []


# -- the folds ----------------------------------------------------------------

TILE = {"plan_s": 0.001, "index_s": 0.004, "decode_s": 0.003,
        "dispatch_s": 0.012, "readback_s": 0.002, "encode_s": 0.010,
        "encode_cpu_s": 0.003, "granules": 2, "decode_queue_max": 1,
        "dispatch_queue_max": 2, "encode_queue_max": 1}
TILE_CPU = {"tile.plan": 0.005, "tile.index": 0.0035, "tile.decode": 0.001,
            "tile.dispatch": 0.004, "tile.readback": 0.0005,
            "render": 0.02}


@pytest.mark.parametrize("cpu, wall_s", [(None, None), (TILE_CPU, 0.05)])
def test_record_tile_keeps_every_wall_key_and_adds_cpu_and_wall(cpu, wall_s):
    plain, traced = MetricsLogger(), MetricsLogger()
    for _ in range(2):
        plain.record_tile(dict(TILE))
        traced.record_tile(dict(TILE), cpu, wall_s)
    a = plain.summary()["tile_stages"]
    b = traced.summary()["tile_stages"]
    for k in ("tiles", "plan_s", "index_s", "decode_s", "dispatch_s",
              "readback_s", "encode_s", "granules", "decode_queue_max",
              "dispatch_queue_max", "encode_queue_max", "encode_cpu_s"):
        assert a[k] == b[k], k
    # the encode job measures its own CPU, traced or not
    assert b["encode_cpu_s"] == pytest.approx(0.006)
    new = ("plan_cpu_s", "index_cpu_s", "decode_cpu_s", "dispatch_cpu_s",
           "readback_cpu_s", "wall_s")
    if cpu is None:
        assert not set(new) & set(b)
        assert not set(new) & set(b["last"])
        return
    # plan's CPU is tile.plan's less the index query inside it
    assert b["plan_cpu_s"] == pytest.approx(2 * 0.0015)
    assert b["index_cpu_s"] == pytest.approx(2 * 0.0035)
    assert b["decode_cpu_s"] == pytest.approx(2 * 0.001)
    assert b["dispatch_cpu_s"] == pytest.approx(2 * 0.004)
    assert b["readback_cpu_s"] == pytest.approx(2 * 0.0005)
    assert b["wall_s"] == pytest.approx(2 * 0.05)
    assert b["last"]["wall_s"] == 0.05
    assert "render" not in b and "render_cpu_s" not in b


def test_record_tile_adds_no_stage_series_for_cpu_or_wall():
    m = MetricsLogger()
    m.record_tile(dict(TILE), TILE_CPU, 0.05)
    text = obs.render_metrics()
    for stage in ("plan_cpu", "dispatch_cpu", "encode_cpu", "wall"):
        assert f'gsky_stage_seconds_count{{stage="{stage}"}}' not in text
    assert 'gsky_stage_seconds_count{stage="dispatch"}' in text


DRILL = {"wps.parse": 0.001, "gateway.admission": 0.02, "drill.index": 0.005,
         "drill.prepare": 0.01, "drill.device": 0.06, "drill.merge": 0.012,
         "wps.format": 0.009}
DRILL_CPU = {"drill.index": 0.004, "drill.prepare": 0.008,
             "drill.device": 0.02, "drill.merge": 0.011, "inner": 1.0}


def test_record_drill_adds_cpu_beside_every_wall_key():
    plain, traced = MetricsLogger(), MetricsLogger()
    plain.record_drill(DRILL, 0.15, files=3, windows=1)
    traced.record_drill(DRILL, 0.15, files=3, windows=1, cpu=DRILL_CPU)
    a = plain.summary()["drill_stages"]
    b = traced.summary()["drill_stages"]
    assert {k: b[k] for k in a if k != "last"} == \
        {k: v for k, v in a.items() if k != "last"}
    assert b["device_cpu_s"] == 0.02 and b["index_cpu_s"] == 0.004
    assert b["prepare_cpu_s"] == 0.008 and b["merge_cpu_s"] == 0.011
    # the loop thread's stages and a recorded wait carry no CPU
    for k in ("parse_cpu_s", "format_cpu_s", "admission_cpu_s",
              "host_read_cpu_s", "inner_cpu_s"):
        assert k not in b
    assert b["last"]["device_cpu_s"] == 0.02
    assert 'stage="drill_device_cpu"' not in obs.render_metrics()


# -- a staged tile through the server -----------------------------------------

@pytest.fixture(scope="module")
def tile_server(tmp_path_factory):
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index import MASClient, MASStore
    from gsky_tpu.index.crawler import extract
    from gsky_tpu.io import write_geotiff
    from gsky_tpu.server.config import ConfigWatcher
    from gsky_tpu.server.ows import OWSServer

    root = tmp_path_factory.mktemp("hostcpu")
    path = str(root / "MOSA_20200110.tif")
    data = np.random.default_rng(1).uniform(200, 3000, (512, 512))
    write_geotiff(path, data.astype(np.int16),
                  GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0),
                  parse_crs("EPSG:32755"), nodata=-999)
    store = MASStore()
    rec = extract(path, approx_stats=True)
    for ds in rec["geo_metadata"]:
        ds["namespace"] = "MOS"
    store.ingest(rec)
    conf = root / "conf"
    conf.mkdir()
    (conf / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [{"name": "mosaic", "data_source": str(root),
                    "rgb_products": ["MOS"], "time_generator": "mas"}]}))
    client = MASClient(store)
    watcher = ConfigWatcher(str(conf), mas_factory=lambda addr: client,
                            install_signal=False)
    return OWSServer(watcher, mas_factory=lambda addr: client,
                     metrics=MetricsLogger(), gateway=None)


def _getmap(server, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer
    m = MetricsLogger()
    monkeypatch.setattr(server, "metrics", m)

    async def go():
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.get(
                "/ows?service=WMS&request=GetMap&version=1.3.0"
                "&layers=mosaic&crs=EPSG:3857"
                "&bbox=16478548,-4211230,16489679,-4198025&width=256"
                "&height=256&format=image/png&time=2020-01-10T00:00:00.000Z")
            return resp.status
        finally:
            await client.close()
    assert asyncio.new_event_loop().run_until_complete(go()) == 200
    return m.summary()["tile_stages"]


STAGES = ("plan_s", "index_s", "decode_s", "dispatch_s", "readback_s",
          "encode_s")


def test_a_traced_tile_folds_its_stages_cpu_and_its_wall(tile_server,
                                                         monkeypatch):
    ts = _getmap(tile_server, monkeypatch)
    assert ts["tiles"] == 1
    for k in ("plan_cpu_s", "index_cpu_s", "decode_cpu_s",
              "dispatch_cpu_s", "readback_cpu_s", "encode_cpu_s"):
        assert ts[k] >= 0.0, k
    # a stage's CPU is at most its wall: one thread ran it
    for k in ("index", "decode", "dispatch", "readback", "encode"):
        assert ts[f"{k}_cpu_s"] <= ts[f"{k}_s"] + 1e-3, k
    # the stages run one after another inside the request
    assert ts["wall_s"] >= sum(ts[k] for k in STAGES)
    assert ts["gates"]["dispatch"]["wait_s"] >= 0.0


def test_an_untraced_tile_folds_the_wall_keys_and_the_encode_cpu(
        tile_server, monkeypatch):
    monkeypatch.setenv("GSKY_TRACE", "0")
    ts = _getmap(tile_server, monkeypatch)
    assert ts["tiles"] == 1
    assert all(k in ts for k in STAGES)
    assert ts["encode_cpu_s"] >= 0.0
    for k in ("plan_cpu_s", "index_cpu_s", "decode_cpu_s",
              "dispatch_cpu_s", "readback_cpu_s", "wall_s"):
        assert k not in ts, k


# -- the encode job's CPU -------------------------------------------------------

@pytest.mark.parametrize("job, busy", [(lambda: time.sleep(0.1), False),
                                       (lambda: _spin(0.05), True)])
def test_the_encode_job_reports_its_threads_cpu(job, busy):
    from gsky_tpu.io.png import encode_async, reset_encode_pool
    from gsky_tpu.obs.metrics import ENCODE_SECONDS
    reset_encode_pool()
    cpu_hist = ENCODE_SECONDS.labels(phase="cpu")
    wait_hist = ENCODE_SECONDS.labels(phase="wait")
    cpu0, wait0 = cpu_hist.sum, wait_hist.sum

    async def go():
        spans = {}
        with obs.start_trace("req") as tr:
            await encode_async(job, spans=spans)
        return spans, tr
    try:
        spans, tr = asyncio.run(go())
    finally:
        reset_encode_pool()
    enc = _span(tr, "encode")[0]
    cpu, wait = enc["attrs"]["cpu_s"], enc["attrs"]["wait_s"]
    assert spans["encode_cpu_s"] == pytest.approx(cpu, abs=1e-6)
    assert cpu_hist.sum - cpu0 == pytest.approx(cpu, abs=1e-5)
    assert wait_hist.sum - wait0 == pytest.approx(wait, abs=1e-5)
    # the span ran on the event loop's thread: no CPU of its own
    assert "cpu_s" not in enc
    assert cpu + wait == pytest.approx(spans["encode_s"], abs=0.01)
    if busy:
        assert cpu >= 0.05
    else:
        assert cpu < 0.02 and wait >= 0.09


# -- the dispatch gate's wait ---------------------------------------------------

def test_the_gate_counts_only_the_time_a_request_waits_for_a_slot(
        monkeypatch):
    from gsky_tpu.pipeline import tile_stages
    monkeypatch.setenv("GSKY_TILE_DISPATCH_SLOTS", "1")
    tile_stages.reset_gates()
    try:
        gate = tile_stages._gate("dispatch")
        with gate.enter():
            time.sleep(0.05)        # busy, nobody waiting
        assert gate.stats()["wait_s"] < 0.02
        assert gate.stats()["busy_s"] >= 0.05
        held, waited = threading.Event(), []

        def second():
            held.wait(10)
            t0 = time.perf_counter()
            with gate.enter():
                waited.append(time.perf_counter() - t0)
        t = threading.Thread(target=second)
        t.start()
        with gate.enter():
            held.set()
            time.sleep(0.1)
        t.join(10)
        assert not t.is_alive() and waited
        st = gate.stats()
        assert 0.08 <= st["wait_s"] <= waited[0] + 0.02
        assert st["entries"] == 3 and st["waiting"] == 0
    finally:
        tile_stages.reset_gates()


def test_the_gates_wait_is_a_span_inside_the_stage():
    from gsky_tpu.pipeline import tile_stages
    tile_stages.reset_gates()
    try:
        with obs.start_trace("req") as tr:
            with obs.span("tile.decode"):
                with tile_stages._gate("decode").enter():
                    pass
        gate = _span(tr, "tile.decode_gate")
        decode = _span(tr, "tile.decode")
        assert len(gate) == 1 and gate[0]["parent_id"] == decode[0]["span_id"]
    finally:
        tile_stages.reset_gates()


# -- the collector's pauses -----------------------------------------------------

class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation."""
    log = []
    broken = False

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        if self.broken:
            raise RuntimeError("no profiler")
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def watch(monkeypatch):
    monkeypatch.setattr(_Annotation, "log", [])
    w = process.GCWatch(_Annotation)
    yield w
    w.uninstall()


def test_a_collection_moves_the_counters(watch):
    watch.install()
    gc.collect()
    gc.collect(0)
    st = watch.stats()
    assert st["collections"][2] >= 1 and st["collections"][0] >= 1
    assert st["pause_s"][2] > 0.0
    assert 0.0 < st["longest_s"] <= sum(st["pause_s"])


def test_a_full_collection_is_one_annotation_entered_and_left(watch):
    watch.install()
    gc.collect(0)
    gc.collect(1)
    assert _Annotation.log == []
    gc.collect()
    n = watch.collections[2]
    assert n >= 1
    assert _Annotation.log == [("enter", "gc.collect"),
                               ("exit", "gc.collect")] * n


def test_the_hook_installed_twice_counts_once(watch):
    watch.install()
    watch.install()
    assert sum(1 for cb in gc.callbacks if cb is watch) == 1
    before = watch.collections[2]
    gc.collect()
    assert watch.collections[2] - before == 1
    watch.uninstall()
    assert not watch.installed
    gc.collect()
    assert watch.collections[2] - before == 1


def test_a_failing_annotation_costs_the_event_not_the_count(watch,
                                                            monkeypatch):
    monkeypatch.setattr(_Annotation, "broken", True)
    watch.install()
    gc.collect()
    assert watch.collections[2] >= 1 and watch.pause_s[2] > 0.0
    assert _Annotation.log == []


def test_a_stop_without_a_start_is_ignored(watch):
    watch("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    assert watch.collections == [0, 0, 0]


def test_debug_and_metrics_read_the_installed_watch(monkeypatch):
    monkeypatch.setattr(process, "_WATCH", None)
    assert "gc" not in process.process_stats()
    assert "gsky_gc_collections_total" not in obs.render_metrics()
    w = process.install()
    try:
        assert process.install() is w
        gc.collect()
        st = MetricsLogger().summary()["process"]
        assert st["cpu_s"] > 0.0
        assert st["gc"]["collections"][2] >= 1
        assert len(st["gc"]["pause_s"]) == 3 and st["gc"]["longest_s"] > 0
        fams = obs.parse_exposition(obs.render_metrics())
        assert "gsky_gc_collections_total" in fams
        assert "gsky_gc_pause_seconds_total" in fams
    finally:
        w.uninstall()


def test_the_server_installs_the_watch_once(monkeypatch):
    """`gsky-ows` installs it before it reads its arguments."""
    from gsky_tpu.server import main as main_mod
    monkeypatch.setattr(process, "_WATCH", None)
    try:
        with pytest.raises(SystemExit):
            main_mod.main(["-no_such_flag"])
        with pytest.raises(SystemExit):
            main_mod.main(["-no_such_flag"])
        assert process._WATCH is not None
        assert sum(1 for cb in gc.callbacks if cb is process._WATCH) == 1
    finally:
        if process._WATCH is not None:
            process._WATCH.uninstall()
