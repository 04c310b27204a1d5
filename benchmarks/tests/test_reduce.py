"""reduce.py: interval arithmetic on made-up events, and the whole
reduction on a small trace recorded on the chip
(`benchmarks/testdata/record.py`: two named programs run a known number
of times with the device left idle for a known time in between)."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import reduce, roofline       # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def line(events):
    return reduce.Line([e[0] for e in events],
                       np.array([e[1] for e in events], float),
                       np.array([e[2] for e in events], float))


def test_union_merges_overlaps_and_keeps_gaps():
    iv = reduce.union(np.array([50.0, 0.0, 5.0, 20.0, 22.0]),
                      np.array([10.0, 10.0, 3.0, 5.0, 10.0]))
    assert iv.tolist() == [[0.0, 10.0], [20.0, 32.0], [50.0, 60.0]]
    assert reduce.union(np.array([]), np.array([])).shape == (0, 2)


def test_gaps_are_what_the_busy_intervals_leave():
    iv = np.array([[0.0, 10.0], [20.0, 32.0], [50.0, 60.0]])
    assert reduce.gaps(iv, 0.0, 60.0).tolist() == [[10.0, 20.0], [32.0, 50.0]]
    assert reduce.gaps(iv, 5.0, 70.0).tolist() == \
        [[10.0, 20.0], [32.0, 50.0], [60.0, 70.0]]


def synthetic():
    t = reduce.Trace()
    t.planes["/device:TPU:0"] = {
        reduce.OPS_LINE: line([("fusion.1", 0, 4e6), ("gather.2", 4e6, 6e6),
                               ("fusion.1", 30e6, 4e6)]),
        reduce.MODULES_LINE: line([
            ("jit_render_scenes_ctrl(123)", 0, 10e6),
            ("jit_render_scenes_ctrl(456)", 30e6, 4e6),
            ("jit_render_scenes_ctrl_many(9)", 40e6, 1e6)])}
    t.planes["/host:CPU"] = {"python": line([("x", 0, 1e9)])}
    return t


def test_busy_ops_and_modules():
    t = synthetic()
    assert t.devices() == ["/device:TPU:0"]
    busy_s, iv = reduce.busy(t)
    assert busy_s == pytest.approx(0.014)
    assert iv.tolist() == [[0.0, 10e6], [30e6, 34e6]]
    assert reduce.top_ops(t, 2) == [
        ["jit_render_scenes_ctrl/fusion.1", pytest.approx(0.008)],
        ["jit_render_scenes_ctrl/gather.2", pytest.approx(0.006)]]
    assert reduce.short_op(
        "%fusion.7 = f32[65536]{0:T(1024)S(1)} fusion(f32[17,17]{1,0} %c, "
        "s32[65536]{0} %b), kind=kCustom, calls=%fused_computation.7") == \
        "fusion.7 fusion:kCustom f32[65536]"
    assert reduce.short_op(
        "%p.1 = (f32[1024,2048]{1,0}, s32[1024,2048]{1,0}) custom-call("
        "f32[8]{0} %x), custom_call_target=\"tpu_custom_call\"") == \
        "p.1 custom-call f32[1024,2048]"
    secs, n = reduce.module_time(t, "render_scenes_ctrl")
    assert n == 2 and secs == pytest.approx(0.014)      # not ..._many
    assert reduce.module_time(t, "window_gather") == (0, 0)
    assert reduce.busy(reduce.Trace()) is None


def test_gaps_are_named_by_the_innermost_open_span():
    idle = np.array([[10e6, 30e6], [34e6, 35e6]])
    spans = [("request", 100.0, 100.1), ("tile.index", 100.015, 100.025),
             ("encode", 100.05, 100.06)]
    assert reduce.label_gaps(idle, spans, wall0=100.0) == \
        [["tile.index", pytest.approx(0.02)], ["request", pytest.approx(0.001)]]
    assert reduce.label_gaps(idle[:1], [], wall0=100.0)[0][0] == \
        "no request in flight"


def test_read_spans(tmp_path):
    p = tmp_path / "spans.jsonl"
    p.write_text(json.dumps({"trace_id": "a", "spans": [
        {"name": "tile.plan", "t0": 5.0, "dur_s": 0.5},
        {"name": "open", "t0": 6.0, "dur_s": None}]}) + "\nnot json\n")
    assert reduce.read_spans(str(p)) == [("tile.plan", 5.0, 5.5)]
    assert reduce.read_spans(str(tmp_path / "missing")) == []


def test_recorded_trace():
    want = json.load(open(os.path.join(DATA, "tiny.expected.json")))
    t = reduce.load(os.path.join(DATA, "tiny.xplane.pb"))
    assert len(t.devices()) == 1
    for name, runs in want["runs"].items():
        secs, n = reduce.module_time(t, name)
        assert n == runs and secs > 0
    busy_s, iv = reduce.busy(t)
    assert 0 < busy_s < 0.05
    # the pause between the two programs is the longest idle gap
    idle = reduce.gaps(iv, iv[0, 0], iv[-1, 1])
    longest = (idle[:, 1] - idle[:, 0]).max() / 1e9
    assert want["pause_s"] <= longest < want["pause_s"] + 0.05
    assert len(reduce.top_ops(t)) >= 2
    assert sum(s for _, s in reduce.top_ops(t, 100)) == pytest.approx(
        busy_s, rel=0.05)


def test_peaks_and_least_time():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    # one 256 x 256 tile from 4 scenes, nearest: 1 MB of taps + 64 KB out
    ops, nbytes = roofline.render_scenes_ctrl(4)
    assert nbytes == 65536 * 16 + 2 * 17 * 17 * 4 + 4 * 44 + 65536
    secs, bound = roofline.least_seconds(ops, nbytes, peak)
    assert bound == "memory" and secs == pytest.approx(nbytes / 819e9)
    # a 100 x 200 window through 1,000 steps: read, written, a byte each
    ops, nbytes = roofline.window_gather(1000, (100, 200))
    assert nbytes == 20_000_000 * 9 + 20_000
    assert roofline.least_seconds(ops, nbytes, peak)[1] == "memory"
    ops, nbytes = roofline.masked_stats(1000, (100, 200))
    assert nbytes == 20_000_000 * 5 + 8000
