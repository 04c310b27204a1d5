"""The reader of `drill.windows_per_file` on made-up `/debug` documents,
as `test_drill_readers.py` holds the stage readers (that file is the
accepted benchmark's, so this case of its test lives here)."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import spec       # noqa: E402
from benchmarks.ctx import Ctx       # noqa: E402

METRIC = "drill.windows_per_file"


def ctx(debug0, debug1):
    return Ctx(cell=SimpleNamespace(name="made-up.cell"), results=[], t0=0.0,
               window_s=20.0, setup_s=1.0, warmup=[], warmed=None,
               debug0=debug0, debug1=debug1, compiles_in_window=(0, 0),
               device_kind="cpu", hbm_peak_bytes=None)


def stages(requests, files, windows=None):
    doc = {"requests": requests, "files": files, "prepare_s": 0.1 * requests}
    if windows is not None:
        doc["windows"] = windows
    return {"drill_stages": doc}


@pytest.mark.parametrize("before, after, want", [
    # 4 requests of three files on one grid: 4 windows for 12 files
    (stages(10, 30, 10), stages(14, 42, 14), 100 / 3),
    # a window for every file (the files share no grid)
    (stages(10, 30, 30), stages(14, 42, 42), 100.0),
    # the first Execute of the process fell inside the window
    ({}, stages(4, 12, 5), 100 * 5 / 12),
    # no file drilled in the window: nothing to read
    (stages(14, 42, 14), stages(14, 42, 14), None),
    # a program whose fold has `files` and no `windows` (the parent): None,
    # not the 0 % an absent key would read as
    (stages(10, 30), stages(14, 42), None),
    # no fold at all
    ({}, {}, None),
])
def test_windows_per_file_divides_windows_by_files(before, after, want):
    read = spec.reader("layer_metrics", METRIC).read
    got = read(ctx(before, after))
    assert got is None if want is None else got == pytest.approx(want)


def test_benchmark_json_lists_the_metric_for_the_drill_cell():
    entry = [m for m in spec.load_json(os.path.join(
        spec.ROOT, "BENCHMARK.json"))["per_layer"]
        if m["name"] == METRIC]
    assert entry == [{
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "drill",
        "moves": "latency_p50_ms",
        "workloads": ["modis-fc-drill.polygons-warm"]}]
