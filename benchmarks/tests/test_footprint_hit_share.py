"""`index.footprint_prepared_hit_share`: hits over hits + misses of
`/debug` cache.mas_footprints across the window, in %; nothing (and no
error) from a program that has no such counter, as the parent of the PR
that brought it has not; and its entry in BENCHMARK.json names the
three cells."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import spec                     # noqa: E402
from benchmarks.ctx import Ctx                  # noqa: E402

NAME = "index.footprint_prepared_hit_share"


def ctx(debug0, debug1):
    return Ctx(cell=SimpleNamespace(), results=[], t0=0.0, window_s=20.0,
               setup_s=1.0, warmup=[], warmed=None, debug0=debug0,
               debug1=debug1, compiles_in_window=(0, 0),
               device_kind="cpu", hbm_peak_bytes=None)


def rows(hits, misses):
    return {"cache": {"mas_rows": {"hits": 7, "misses": 3},
                      "mas_footprints": {"hits": hits, "misses": misses}}}


@pytest.mark.parametrize("debug0, debug1, want", [
    (rows(0, 12), rows(6000, 12), 100.0),       # prepared before the window
    (rows(10, 10), rows(40, 20), 75.0),         # the window's own deltas
    (rows(5, 5), rows(5, 5), None),             # no row refined in it
    ({"cache": {"mas_rows": {"hits": 1, "misses": 1}}},
     {"cache": {"mas_rows": {"hits": 2, "misses": 5}}}, None),   # the parent
    ({}, {}, None),
])
def test_reads_the_windows_share(debug0, debug1, want):
    got = spec.reader("layer_metrics", NAME).read(ctx(debug0, debug1))
    assert got == want


def test_its_entry():
    """Found by its name: entries are appended, so the last is whatever
    the newest PR brought."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "index",
        "moves": "latency_p50_ms",
        "workloads": [w["name"] for w in bench["workloads"]]}
    for cell in entry["workloads"]:
        assert NAME in [m["name"] for m in spec.load_cell(cell).per_layer]
