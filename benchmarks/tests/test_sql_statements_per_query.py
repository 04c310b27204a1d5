"""`index.sql_statements_per_query`: statements over queries of
`/debug` cache.mas_sql across the window; nothing (and no error) from a
program that has no such counter, as the parent of the PR that brought
it has not; and its entry in BENCHMARK.json, found by its name, names
every cell."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import spec                     # noqa: E402
from benchmarks.ctx import Ctx                  # noqa: E402

NAME = "index.sql_statements_per_query"


def ctx(debug0, debug1):
    return Ctx(cell=SimpleNamespace(), results=[], t0=0.0, window_s=20.0,
               setup_s=1.0, warmup=[], warmed=None, debug0=debug0,
               debug1=debug1, compiles_in_window=(0, 0),
               device_kind="cpu", hbm_peak_bytes=None)


def sql(queries, statements):
    return {"cache": {"mas_query": {"hits": 0, "misses": queries},
                      "mas_sql": {"queries": queries,
                                  "statements": statements}}}


@pytest.mark.parametrize("debug0, debug1, want", [
    (sql(3000, 1), sql(5000, 1), 0.0),          # built in the warm-up
    (sql(3000, 6000), sql(5000, 10000), 2.0),   # a statement store
    (sql(10, 1), sql(20, 2), 0.1),              # an ingest in the window
    (sql(5, 1), sql(5, 1), None),               # no query in it
    ({"cache": {"mas_query": {"hits": 1, "misses": 1}}},
     {"cache": {"mas_query": {"hits": 2, "misses": 5}}}, None),  # the parent
    ({}, {}, None),
])
def test_reads_the_windows_statements_per_query(debug0, debug1, want):
    got = spec.reader("layer_metrics", NAME).read(ctx(debug0, debug1))
    assert got == want


def test_its_entry():
    """Found by its name: entries are appended, so the last is whatever
    the newest PR brought."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "statements", "better": "lower",
        "source": "program_counter", "layer": "index",
        "moves": "latency_p50_ms",
        "workloads": [w["name"] for w in bench["workloads"]]}
    for cell in entry["workloads"]:
        assert NAME in [m["name"] for m in spec.load_cell(cell).per_layer]
