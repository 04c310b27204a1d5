"""The readers of the drill's stage metrics on a made-up `Ctx` (two
`/debug` documents), and the idle reader on a made-up `reduce.Trace`
with a known overlap of device-idle time and stage annotations."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import reduce, spec       # noqa: E402
from benchmarks.ctx import Ctx       # noqa: E402

IDLE = "device.idle_outside_stages_share"


def ctx(debug0, debug1, trace=None):
    return Ctx(cell=SimpleNamespace(name="made-up.cell"), results=[], t0=0.0,
               window_s=20.0, setup_s=1.0, warmup=[], warmed=None,
               debug0=debug0, debug1=debug1, compiles_in_window=(0, 0),
               device_kind="cpu", hbm_peak_bytes=None, trace=trace)


def stages(requests, **seconds):
    keys = ("parse_s", "admission_s", "index_s", "prepare_s", "device_s",
            "host_read_s", "merge_s", "format_s", "wall_s")
    return {"drill_stages": dict({k: 0.0 for k in keys}, requests=requests,
                                 **seconds)}


BEFORE = stages(10, parse_s=0.01, admission_s=0.5, index_s=1.0,
                prepare_s=0.2, device_s=0.3, merge_s=0.1, format_s=0.05,
                wall_s=3.0)
AFTER = stages(14, parse_s=0.018, admission_s=0.9, index_s=1.4,
               prepare_s=0.28, device_s=0.5, host_read_s=0.04, merge_s=0.12,
               format_s=0.09, wall_s=4.6)


@pytest.mark.parametrize("metric, ms", [
    ("frontend.wps_parse_ms_per_request", 2.0),
    ("frontend.wps_format_ms_per_request", 10.0),
    ("index.ms_per_drill", 100.0),
    ("drill.prepare_ms_per_request", 20.0),
    ("executor.drill_device_ms_per_request", 50.0),
    ("drill.merge_ms_per_request", 5.0),
    # wall 1.6 s less parse 0.008, admission 0.4, index 0.4, prepare 0.08,
    # device 0.2, host reads 0.04, merge 0.02 and format 0.04, over 4
    ("drill.unattributed_ms_per_request", 103.0),
])
def test_a_stage_reader_divides_what_moved_by_the_requests(metric, ms):
    read = spec.reader("layer_metrics", metric).read
    assert read(ctx(BEFORE, AFTER)) == pytest.approx(ms)
    # the window answered no Execute: nothing to read
    assert read(ctx(AFTER, AFTER)) is None
    # a program without the fold (the parent): no `drill_stages` at all
    assert read(ctx({}, {})) is None
    # the first Execute of the process fell inside the window
    assert read(ctx({}, AFTER)) is not None


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
                               ("fusion.1", 90e6, 10e6)]),
        reduce.MODULES_LINE: line([("jit_window_gather(7)", 0, 10e6),
                                   ("jit_window_gather(7)", 30e6, 10e6),
                                   ("jit_masked_mean", 90e6, 10e6)])}
    t.planes["/host:CPU"] = host
    return t


def test_idle_time_is_shared_out_among_the_stages_open_then():
    m = spec.reader("layer_metrics", IDLE)
    t = made_up({
        # two threads; a request's root and the runtime's own events are
        # no stages
        "python": line([("drill.prepare", 5e6, 10e6),       # idle 10..15
                        ("drill.device", 25e6, 20e6),       # idle 25..30, 40..45
                        ("ows.request", 0, 100e6),
                        ("wps.format", 95e6, 5e6)]),        # device busy
        "worker": line([("drill.prepare", 12e6, 6e6),       # idle 12..18
                        ("encode", 60e6, 10e6),             # idle 60..70
                        ("$pjrt::Execute", 40e6, 50e6)])})
    found = m.stages(m.events_of(t))
    assert sorted(found) == ["drill.device", "drill.prepare", "encode",
                             "wps.format"]
    assert found["drill.prepare"].tolist() == [[5e6, 18e6]]
    got = m.by_stage(t)
    assert got == pytest.approx({"drill.prepare": 0.008, "drill.device": 0.010,
                                 "encode": 0.010, "wps.format": 0.0})
    # 70 ms idle, 28 ms of it under some stage
    assert m.outside_share(t, found) == pytest.approx(100 * 42 / 70)
    assert m.read(ctx({}, {}, trace=t)) == pytest.approx(60.0)
    # both executions of window_gather began inside drill.device? the
    # first began before it opened
    assert m.begin_inside(t, "window_gather", found["drill.device"]) == (1, 2)
    assert m.begin_inside(t, "render_scenes_ctrl",
                          found["drill.device"]) == (0, 0)


def test_overlapping_stages_count_an_instant_once_in_the_share():
    m = spec.reader("layer_metrics", IDLE)
    t = made_up({"a": line([("tile.index", 10e6, 10e6)]),
                 "b": line([("tile.dispatch", 15e6, 10e6)])})
    got = m.by_stage(t)
    # each stage has its 10 ms; together they cover 15 of the 70
    assert got == pytest.approx({"tile.index": 0.010, "tile.dispatch": 0.010})
    assert m.outside_share(t, m.stages(m.events_of(t))) == \
        pytest.approx(100 * 55 / 70)


def test_nothing_to_read_without_annotations_or_without_a_device():
    m = spec.reader("layer_metrics", IDLE)
    # the parent's profile: host events, none of them a stage
    bare = made_up({"python": line([("$pjrt::Execute", 0, 50e6)])})
    assert m.read(ctx({}, {}, trace=bare)) is None
    assert m.by_stage(bare) == {}
    # a rehearsal: annotations and no device plane
    cpu = reduce.Trace()
    cpu.planes["/host:CPU"] = {"python": line([("drill.device", 0, 5e6)])}
    assert m.read(ctx({}, {}, trace=cpu)) is None
    assert m.by_stage(cpu) == {}
    assert m.read(ctx({}, {})) is None       # --trace 0


def test_the_slice_is_read_from_the_file_where_run_py_kept_it(tmp_path,
                                                              monkeypatch):
    """`reduce.load` keeps one line of each name and every Python thread
    is called `python`; the reader goes back to the file for the rest."""
    m = spec.reader("layer_metrics", IDLE)
    t = made_up({"python": line([("drill.device", 40e6, 5e6)])})
    c = ctx({}, {}, trace=t)
    assert m.kept_slice(c) is None
    monkeypatch.setenv("GSKY_TRACE_FILE", str(tmp_path / "x.spans.jsonl"))
    assert m.kept_slice(c) is None           # no slice was kept there
    kept = tmp_path / "made-up.cell.xplane.pb"
    kept.write_bytes(b"")
    assert m.kept_slice(c) == str(kept)
    # the file holds a second thread that the loaded trace lost
    monkeypatch.setattr(m, "events_in", lambda path: [
        ("drill.device", 40e6, 5e6), ("drill.prepare", 50e6, 20e6)])
    assert m.read(c) == pytest.approx(100 * 45 / 70)
