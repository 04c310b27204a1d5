"""The readers of what a host stage ran and what it waited, on a made-up
`Ctx` (two `/debug` documents): each divides what moved by the tiles,
requests or time it moved over, and each returns None on a parent's
`/debug`, which has none of the keys it reads.  The idle-under-gc reader
on a made-up `reduce.Trace` with a known overlap of device-idle time and
`gc.collect` events."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import reduce, spec       # noqa: E402
from benchmarks.ctx import Ctx       # noqa: E402

GC = "device.idle_under_gc_share"


def ctx(debug0, debug1, trace=None, answered=0, failed=0):
    results = [SimpleNamespace(ok=True)] * answered \
        + [SimpleNamespace(ok=False)] * failed
    return Ctx(cell=SimpleNamespace(name="made-up.cell"), results=results,
               t0=0.0, window_s=20.0, setup_s=1.0, warmup=[], warmed=None,
               debug0=debug0, debug1=debug1, compiles_in_window=(0, 0),
               device_kind="cpu", hbm_peak_bytes=None, trace=trace)


def tiles(n, **seconds):
    wall = ("plan_s", "index_s", "decode_s", "dispatch_s", "readback_s",
            "encode_s")
    dispatch = {"limit": 2, "busy_s": 2.0}
    if "gate_wait_s" in seconds:
        dispatch["wait_s"] = seconds.pop("gate_wait_s")
    gates = {"decode": {"limit": 4, "busy_s": 1.0}, "dispatch": dispatch}
    return {"tile_stages": dict({k: 0.0 for k in wall}, tiles=n,
                                gates=gates, **seconds)}


def process(uptime_s, cpu_s, pause_s):
    return {"uptime_s": uptime_s,
            "process": {"cpu_s": cpu_s,
                        "gc": {"collections": [100, 10, 1],
                               "pause_s": pause_s, "longest_s": 0.05}}}


# the change's /debug at the window's start and end: 40 tiles, two WPS
# Executes, 2 s of process CPU, 0.2 s of pauses over 20 s
BEFORE = dict(tiles(100, plan_s=0.1, index_s=0.2, decode_s=0.3,
                    dispatch_s=1.0, readback_s=0.5, encode_s=1.0,
                    dispatch_cpu_s=0.2, readback_cpu_s=0.05,
                    encode_cpu_s=0.3, wall_s=5.0, gate_wait_s=0.1),
              drill_stages={"requests": 3, "device_s": 0.3,
                            "device_cpu_s": 0.06},
              **process(100.0, 50.0, [0.1, 0.1, 0.3]))
AFTER = dict(tiles(140, plan_s=0.14, index_s=0.28, decode_s=0.42,
                   dispatch_s=1.4, readback_s=0.7, encode_s=1.4,
                   dispatch_cpu_s=0.28, readback_cpu_s=0.09,
                   encode_cpu_s=0.42, wall_s=7.0, gate_wait_s=0.18),
             drill_stages={"requests": 5, "device_s": 0.4,
                           "device_cpu_s": 0.1},
             **process(120.0, 52.0, [0.15, 0.15, 0.4]))
# what the parent serves: the same blocks without any key read here
PARENT = tiles(100, plan_s=0.1, dispatch_s=1.0)
PARENT["drill_stages"] = {"requests": 3, "device_s": 0.3}
PARENT["uptime_s"] = 100.0
PARENT_AFTER = tiles(140, plan_s=0.14, dispatch_s=1.4)
PARENT_AFTER["drill_stages"] = {"requests": 5, "device_s": 0.4}
PARENT_AFTER["uptime_s"] = 120.0


@pytest.mark.parametrize("metric, value", [
    # 2 s of CPU over 40 requests answered (the 3 failed ones are not)
    ("process.cpu_ms_per_request", 50.0),
    # 0.2 s of pauses over the 20 s between the two reads
    ("process.gc_pause_share", 1.0),
    ("executor.dispatch_gate_wait_ms_per_tile", 2.0),
    # (0.08 + 0.04) s over 40 tiles
    ("executor.dispatch_readback_cpu_ms_per_tile", 3.0),
    ("frontend.encode_cpu_ms_per_tile", 3.0),
    # wall 2.0 s less plan 0.04, index 0.08, decode 0.12, dispatch 0.4,
    # readback 0.2, encode 0.4, over 40
    ("frontend.tile_unattributed_ms_per_tile", 19.0),
    ("executor.drill_device_cpu_ms_per_request", 20.0),
])
def test_a_reader_divides_what_moved(metric, value):
    read = spec.reader("layer_metrics", metric).read
    assert read(ctx(BEFORE, AFTER, answered=40, failed=3)) == \
        pytest.approx(value)
    # the parent's /debug holds none of the keys: nothing to read
    assert read(ctx(PARENT, PARENT_AFTER, answered=40)) is None
    assert read(ctx({}, {}, answered=40)) is None


def test_nothing_moved_reads_none_or_zero():
    same = ctx(AFTER, AFTER, answered=0)
    for metric in ("executor.dispatch_gate_wait_ms_per_tile",
                   "executor.dispatch_readback_cpu_ms_per_tile",
                   "frontend.encode_cpu_ms_per_tile",
                   "frontend.tile_unattributed_ms_per_tile",
                   "executor.drill_device_cpu_ms_per_request",
                   "process.cpu_ms_per_request"):
        assert spec.reader("layer_metrics", metric).read(same) is None, metric
    # no pause in a window is a reading of 0, not a missing one
    assert spec.reader("layer_metrics", "process.gc_pause_share").read(
        ctx(AFTER, AFTER)) == 0.0


def test_gc_pause_share_falls_back_to_the_window_without_uptime():
    d0, d1 = dict(BEFORE), dict(AFTER)
    del d0["uptime_s"], d1["uptime_s"]
    read = spec.reader("layer_metrics", "process.gc_pause_share").read
    assert read(ctx(d0, d1)) == pytest.approx(1.0)


def test_a_program_without_the_watch_reads_none():
    """The server did not install the collector's watch: `process`
    holds its CPU and no `gc`."""
    d0 = {"uptime_s": 1.0, "process": {"cpu_s": 1.0}}
    d1 = {"uptime_s": 21.0, "process": {"cpu_s": 3.0}}
    assert spec.reader("layer_metrics", "process.gc_pause_share").read(
        ctx(d0, d1)) is None
    assert spec.reader("layer_metrics", "process.cpu_ms_per_request").read(
        ctx(d0, d1, answered=20)) == pytest.approx(100.0)


def line(events):
    return reduce.Line([e[0] for e in events],
                       np.array([e[1] for e in events], float),
                       np.array([e[2] for e in events], float))


def made_up(host):
    """The device runs 0-10 ms, 30-40 ms and 90-100 ms: idle 20 ms then
    50 ms between its first and its last operation."""
    t = reduce.Trace()
    t.planes["/device:TPU:0"] = {
        reduce.OPS_LINE: line([("fusion.1", 0, 10e6), ("fusion.1", 30e6, 10e6),
                               ("fusion.1", 90e6, 10e6)])}
    t.planes["/host:CPU"] = host
    return t


WATCHED = {"process": {"cpu_s": 1.0, "gc": {"collections": [0, 0, 1],
                                            "pause_s": [0, 0, 0.01],
                                            "longest_s": 0.01}}}


def test_idle_under_a_collection_on_any_thread():
    m = spec.reader("layer_metrics", GC)
    t = made_up({
        # a collection 5..15 ms (5 of it idle) and one 50..60 ms on
        # another line, overlapping a stage that is no collection
        "python": line([("gc.collect", 5e6, 10e6),
                        ("tile.dispatch", 0, 100e6)]),
        "worker": line([("gc.collect", 50e6, 10e6),
                        ("gc.collect", 55e6, 10e6)])})     # 50..65 merged
    found = m.collections(m._IDLE.events_of(t))
    assert found.tolist() == [[5e6, 15e6], [50e6, 65e6]]
    # 70 ms idle, 5 + 15 of it under a collection
    assert m.share(t, found) == pytest.approx(100 * 20 / 70)
    assert m.read(ctx({}, WATCHED, trace=t)) == pytest.approx(100 * 20 / 70)


def test_no_collection_in_the_slice_reads_zero_where_the_program_counts_them():
    m = spec.reader("layer_metrics", GC)
    t = made_up({"python": line([("tile.dispatch", 10e6, 20e6)])})
    assert m.read(ctx({}, WATCHED, trace=t)) == 0.0
    # the parent: no watch, so nothing says whether a collection ran
    assert m.read(ctx({}, {}, trace=t)) is None
    assert m.read(ctx({}, {"process": {"cpu_s": 1.0}}, trace=t)) is None


def test_idle_under_gc_needs_a_device_trace():
    m = spec.reader("layer_metrics", GC)
    assert m.read(ctx({}, WATCHED)) is None              # --trace 0
    cpu = reduce.Trace()                                 # a rehearsal
    cpu.planes["/host:CPU"] = {"python": line([("gc.collect", 0, 5e6)])}
    assert m.read(ctx({}, WATCHED, trace=cpu)) is None


def test_the_slice_is_read_from_the_file_where_run_py_kept_it(tmp_path,
                                                              monkeypatch):
    m = spec.reader("layer_metrics", GC)
    t = made_up({"python": line([("tile.plan", 40e6, 5e6)])})
    monkeypatch.setenv("GSKY_TRACE_FILE", str(tmp_path / "x.spans.jsonl"))
    (tmp_path / "made-up.cell.xplane.pb").write_bytes(b"")
    # the file holds a thread that the loaded trace lost
    monkeypatch.setattr(m._IDLE, "events_in", lambda path: [
        ("gc.collect", 50e6, 35e6)])
    assert m.read(ctx({}, WATCHED, trace=t)) == pytest.approx(100 * 35 / 70)


def test_the_entries_are_appended_with_their_cells():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    tile_cells = ["landsat8-mosaic.pan-cold", "sentinel2-rgb.pan-cold",
                  "sentinel2-algebra.ndvi-cold"]
    want = {"process.cpu_ms_per_request": None,
            "process.gc_pause_share": None,
            "device.idle_under_gc_share": None,
            "executor.dispatch_gate_wait_ms_per_tile": tile_cells,
            "executor.dispatch_readback_cpu_ms_per_tile": tile_cells,
            "frontend.encode_cpu_ms_per_tile": tile_cells,
            "frontend.tile_unattributed_ms_per_tile": tile_cells,
            "executor.drill_device_cpu_ms_per_request":
                ["modis-fc-drill.polygons-warm"]}
    for name, cells in want.items():
        entry = by_name[name]
        assert entry.get("workloads") == cells, name
        assert os.path.isfile(os.path.join(spec.HERE, "layer_metrics",
                                           name + ".py"))
