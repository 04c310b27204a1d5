"""A store in memory answers `intersects` without an SQL statement and
without `MASStore._lock`: its generation is a number, its candidate
rows come from arrays kept per generation (`index/store.py`:
`_GenerationRows`, `MASStore._candidates`).  The statement stays in the
store (`MASStore._select`: what a file database runs), so every
expectation here is made by running the statement itself on the same
store."""

import contextlib
import random
import sqlite3
import sys
import threading
import time

import numpy as np
import pytest

import test_index_footprints as FP
from benchmarks import spec
from gsky_tpu.geo.transform import BBox
from gsky_tpu.index import MASClient
from gsky_tpu.index.store import MASStore, fmt_time

T0 = 1577836800.0                       # 2020-01-01T00:00:00Z
DAY = 86400.0


def _at(t):
    """RFC3339 with the fraction kept: the edges below are fractions of
    a second."""
    whole = int(t // 1)
    return fmt_time(whole)[:-5] + ".%03dZ" % round((t - whole) * 1000)


def _ds(path, polygon, ns="b1", stamps=(T0, T0 + 10 * DAY), srs="EPSG:4326"):
    ds = {"ds_name": path, "namespace": ns, "array_type": "Int16",
          "srs": srs, "geotransform": [0, 1, 0, 0, 0, -1],
          "timestamps": [fmt_time(t) for t in stamps]}
    if polygon:
        ds["polygon"] = polygon
    return ds


def _rec(path, *datasets):
    return {"filename": path, "file_type": "GTiff",
            "geo_metadata": list(datasets)}


@contextlib.contextmanager
def by_statement(store):
    """`intersects` with its candidates from the SQL statement, whatever
    the kind of store, and with no answer kept from before."""
    array_step = store._candidates
    store._candidates = lambda generation, *args: store._select(*args)
    store._query_cache.clear()
    try:
        yield
    finally:
        store._candidates = array_step
        store._query_cache.clear()


class Both:
    """Holds every candidate step of a store to the statement, run on
    the same store for the same query: the same rows, in its order."""

    def __init__(self, store):
        self.calls = 0
        array_step = store._candidates

        def both(generation, *args):
            got = array_step(generation, *args)
            assert got == store._select(*args), args
            self.calls += 1
            return got
        store._candidates = both


# -- (a) the benchmark's traffic --------------------------------------------------

CELLS = dict(FP.CELLS)
CELLS["sentinel2-algebra.ndvi-cold"] = ("sentinel2-algebra", "ndvi-pan-cold")


@pytest.fixture(scope="module")
def archives(tmp_path_factory):
    out = {}
    for name, (config, mix) in CELLS.items():
        cell = spec.Cell(
            name, 1,
            spec.sized(spec.load_json(
                f"{spec.HERE}/configs/{config}.json"), True),
            spec.sized(spec.load_json(
                f"{spec.HERE}/traffic/{mix}.json"), True), [], [])
        archive = spec.load_kind("archives", cell.config["archive"]["kind"])
        root = tmp_path_factory.mktemp(config)
        out[name] = (cell, archive,
                     archive.build(cell.config["archive"], 3, str(root)),
                     str(root))
    return out


@pytest.mark.parametrize("name", list(CELLS))
def test_a_cells_first_2000_queries_pick_the_statements_rows(archives, name):
    cell, archive, records, root = archives[name]
    store = MASStore()
    store.ingest_many(records)
    both = Both(store)
    gen, reqs = FP._requests(cell, archive, 2147485020, 2000)
    assert len(reqs) == 2000
    mas = MASClient(store)
    for req in reqs:
        FP._ask(cell, root, mas, gen, req)
    assert both.calls >= 2000 == store.query_misses
    # one statement built the generation, and no query ran another
    assert store.sql_statements == 1
    assert store.footprint_misses <= len(store._held.rows)


# -- the predicates, one by one ---------------------------------------------------

SQ = FP._box(10, 10, 20, 20)
ODD = "/d/odd%_\\x"                     # LIKE's wildcards and its escape


def _archive():
    """Rows that differ in one thing each.  Ids follow the order of
    ingest; a.tif holds two namespaces, so (namespace, id) order and id
    order differ."""
    store = MASStore()
    store.ingest_many([
        _rec("/d/a.tif", _ds("/d/a.tif", SQ, "b2"), _ds("/d/a.tif", SQ, "b1")),
        _rec("/d/b.tif", _ds("/d/b.tif", SQ, "b1",
                             (T0 + 20 * DAY, T0 + 30 * DAY))),
        _rec("/d/nobox.tif", _ds("/d/nobox.tif", "", "b1")),
        _rec("/d/nostamps.tif", _ds("/d/nostamps.tif", SQ, "b2", ())),
        _rec(ODD + "/f.tif", _ds(ODD + "/f.tif", SQ)),
        _rec("/d/oddABCx/g.tif", _ds("/d/oddABCx/g.tif", SQ)),
        _rec("/d/odd%_x/h.tif", _ds("/d/odd%_x/h.tif", SQ)),
        _rec("/D/Upper.TIF", _ds("/D/Upper.TIF", SQ, "B1")),
        _rec("/d/É/i.tif", _ds("/d/É/i.tif", SQ)),
        _rec("/d/east.tif", _ds("/d/east.tif",
                                FP._box(179.1, -35.9, 179.9, -35.1))),
        _rec("/d/west.tif", _ds("/d/west.tif",
                                FP._box(-179.9, -35.9, -179.1, -35.1))),
        _rec("/d/utm.tif", _ds("/d/utm.tif", FP.ZONE60[1], "b1",
                               (T0, T0), FP.ZONE60[0])),
        _rec("/e/other.tif", _ds("/e/other.tif", SQ)),
    ])
    return store


IN_SQ = FP._box(12, 12, 13, 13)
ACROSS = ("POLYGON((179.8 -35.8,-179.8 -35.8,-179.8 -35.2,179.8 -35.2,"
          "179.8 -35.8))")
B_FIRST, B_LAST = T0 + 20 * DAY, T0 + 30 * DAY

QUERIES = {
    "box-only": dict(wkt=IN_SQ),
    "box-apart": dict(wkt=FP._box(40, 40, 41, 41)),
    "box-touching-a-corner": dict(wkt=FP._box(20, 20, 21, 21)),
    "box-3857": dict(srs="EPSG:3857",
                     wkt=FP._box(1600000, 1600000, 1700000, 1700000)),
    "point": dict(wkt="POINT(15 15)"),
    "instant-inside": dict(wkt=IN_SQ, time=_at(T0 + DAY)),
    "instant-on-the-first-stamp": dict(wkt=IN_SQ, time=_at(B_FIRST)),
    "instant-on-the-last-stamp": dict(wkt=IN_SQ, time=_at(B_LAST)),
    "instant-just-before": dict(wkt=IN_SQ, time=_at(B_FIRST - 0.001)),
    "instant-just-after": dict(wkt=IN_SQ, time=_at(B_LAST + 0.001)),
    "range-over": dict(wkt=IN_SQ, time=_at(T0 - DAY), until=_at(T0 + DAY)),
    # OVERLAPS with a second of slack on both edges: strict at the second
    "range-starts-inside-the-slack": dict(
        wkt=IN_SQ, time=_at(B_LAST + 0.999), until=_at(B_LAST + 5 * DAY)),
    "range-starts-on-the-slacks-edge": dict(
        wkt=IN_SQ, time=_at(B_LAST + 1), until=_at(B_LAST + 5 * DAY)),
    "range-ends-inside-the-slack": dict(
        wkt=IN_SQ, time=_at(B_FIRST - 5 * DAY), until=_at(B_FIRST - 0.999)),
    "range-ends-on-the-slacks-edge": dict(
        wkt=IN_SQ, time=_at(B_FIRST - 5 * DAY), until=_at(B_FIRST - 1)),
    "range-no-geometry": dict(time=_at(T0), until=_at(T0 + 25 * DAY)),
    "instant-no-geometry": dict(time=_at(T0 + 5 * DAY)),
    "one-namespace": dict(wkt=IN_SQ, namespaces=["b1"]),
    "two-namespaces-in-the-indexs-order": dict(
        wkt=IN_SQ, namespaces=["b2", "b1"]),
    "a-namespace-twice": dict(wkt=IN_SQ, namespaces=["b2", "b2", "b1"]),
    "a-namespace-nobody-has": dict(wkt=IN_SQ, namespaces=["b9"]),
    "one-known-one-not": dict(wkt=IN_SQ, namespaces=["b9", "b2"]),
    "namespaces-match-case": dict(wkt=IN_SQ, namespaces=["B1"]),
    "no-namespaces-at-all": dict(wkt=IN_SQ, namespaces=[]),
    "namespaces-no-geometry": dict(namespaces=["b2", "b1"]),
    "namespaces-and-a-range": dict(
        wkt=IN_SQ, namespaces=["b2", "b1"], time=_at(T0), until=_at(T0 + DAY)),
    "limit-1": dict(wkt=IN_SQ, limit=1),
    "limit-3": dict(wkt=IN_SQ, limit=3),
    "limit-past-the-end": dict(wkt=IN_SQ, limit=50),
    "limit-under-namespaces": dict(wkt=IN_SQ, namespaces=["b2", "b1"],
                                   limit=2),
    "limit-no-geometry": dict(limit=2),
    "limit-no-geometry-namespaces": dict(limit=2, namespaces=["b2", "b1"]),
    "no-geometry": dict(),
    "a-row-with-no-box-only-without-geometry": dict(gpath="/d/nobox"),
    "a-row-with-no-box-meets-no-box": dict(
        gpath="/d/nobox", wkt=FP._box(-180, -90, 180, 90)),
    "a-row-with-no-stamps-fails-the-instant": dict(
        gpath="/d/nostamps", wkt=IN_SQ, time=_at(T0)),
    "a-row-with-no-stamps-fails-the-range": dict(
        gpath="/d/nostamps", time=_at(T0 - DAY), until=_at(T0 + DAY)),
    "a-row-with-no-stamps-without-a-time": dict(gpath="/d/nostamps",
                                                wkt=IN_SQ),
    "a-path-with-wildcards-and-the-escape": dict(gpath=ODD, wkt=IN_SQ),
    "a-path-with-wildcards-no-geometry": dict(gpath=ODD),
    "a-path-ending-in-the-escape": dict(gpath="/d/odd%_\\"),
    "percent-alone": dict(gpath="/d/odd%", wkt=IN_SQ),
    "underscore-alone": dict(gpath="/d/odd%_", wkt=IN_SQ),
    "like-folds-ascii": dict(gpath="/d/UPPER", wkt=IN_SQ),
    "like-folds-ascii-the-other-way": dict(gpath="/D/A.TIF", wkt=IN_SQ),
    "like-folds-ascii-only": dict(gpath="/d/é", wkt=IN_SQ),
    "like-its-own-letter": dict(gpath="/d/É", wkt=IN_SQ),
    "a-prefix-of-everything": dict(gpath="", wkt=IN_SQ),
    "a-prefix-of-nothing": dict(gpath="/nowhere", wkt=IN_SQ),
    "another-collection": dict(gpath="/e", wkt=IN_SQ),
    "across-the-dateline": dict(wkt=ACROSS),
    "across-the-dateline-under-namespaces": dict(wkt=ACROSS,
                                                 namespaces=["b1"]),
    "east-of-the-dateline": dict(wkt=FP._box(179.2, -35.8, 179.6, -35.2)),
    "a-utm-row-past-the-dateline": dict(
        wkt=FP._box(179.0, 59.6, 179.2, 59.8)),
}


@pytest.fixture(scope="module")
def archive():
    return _archive()


@pytest.mark.parametrize("metadata", ["", "gdal"], ids=["files", "gdal"])
@pytest.mark.parametrize("case", list(QUERIES))
def test_a_query_answers_as_the_statement_does(archive, case, metadata):
    kw = dict(QUERIES[case], metadata=metadata)
    gpath = kw.pop("gpath", "/d")
    Both(archive)
    try:
        archive._query_cache.clear()
        got = archive.intersects(gpath, **kw)
    finally:
        del archive._candidates             # the class's again
    with by_statement(archive):
        want = archive.intersects(gpath, **kw)
    assert got == want
    if "gdal" in got:
        assert [(r["file_path"], r["namespace"]) for r in got["gdal"]] \
            == [(r["file_path"], r["namespace"]) for r in want["gdal"]]


def test_the_cases_select_what_their_names_say(archive):
    """The cases above would agree on nothing selected, too."""
    def files(case, **more):
        kw = dict(QUERIES[case], **more)
        return archive.intersects(kw.pop("gpath", "/d"), **kw)["files"]

    def rows(case):
        kw = dict(QUERIES[case], metadata="gdal")
        return [(r["file_path"].rsplit("/", 1)[1], r["namespace"])
                for r in archive.intersects(kw.pop("gpath", "/d"),
                                            **kw)["gdal"]]
    assert "/d/b.tif" in files("instant-on-the-first-stamp")
    assert "/d/b.tif" in files("instant-on-the-last-stamp")
    assert "/d/b.tif" not in files("instant-just-before")
    assert "/d/b.tif" not in files("instant-just-after")
    assert "/d/b.tif" in files("range-starts-inside-the-slack")
    assert "/d/b.tif" not in files("range-starts-on-the-slacks-edge")
    assert "/d/b.tif" in files("range-ends-inside-the-slack")
    assert "/d/b.tif" not in files("range-ends-on-the-slacks-edge")
    assert rows("two-namespaces-in-the-indexs-order")[:3] == [
        ("a.tif", "b1"), ("b.tif", "b1"), ("f.tif", "b1")]
    assert rows("two-namespaces-in-the-indexs-order")[-2:] == [
        ("a.tif", "b2"), ("nostamps.tif", "b2")]
    assert rows("box-only")[:2] == [("a.tif", "b2"), ("a.tif", "b1")]
    assert rows("limit-under-namespaces") == [("a.tif", "b1"),
                                              ("b.tif", "b1")]
    assert rows("namespaces-match-case") == [("Upper.TIF", "B1")]
    assert files("a-row-with-no-box-only-without-geometry") \
        == ["/d/nobox.tif"]
    assert files("a-row-with-no-box-meets-no-box") == []
    assert files("a-row-with-no-stamps-without-a-time") \
        == ["/d/nostamps.tif"]
    assert files("a-row-with-no-stamps-fails-the-instant") == []
    assert files("a-row-with-no-stamps-fails-the-range") == []
    assert files("a-path-with-wildcards-and-the-escape") == [ODD + "/f.tif"]
    assert files("percent-alone") == [ODD + "/f.tif", "/d/odd%_x/h.tif"]
    assert files("like-folds-ascii") == ["/D/Upper.TIF"]
    assert "/D/Upper.TIF" not in files("box-only", gpath="/d/a")
    assert files("like-folds-ascii-only") == []
    assert files("like-its-own-letter") == ["/d/É/i.tif"]
    assert files("across-the-dateline") == ["/d/east.tif", "/d/west.tif"]
    assert files("east-of-the-dateline") == ["/d/east.tif"]
    assert files("a-utm-row-past-the-dateline") == ["/d/utm.tif"]
    assert files("another-collection") == ["/e/other.tif"]


def test_the_box_is_the_trees_own():
    """The R*Tree keeps float32 boxes rounded outwards, so the statement
    lets through a query that falls just short of a row's float64 box.
    A row without a polygon that parses is never refined, so the answer
    shows it."""
    store = MASStore()
    store.ingest(_rec("/d/a.tif", _ds("/d/a.tif", FP._box(0.1, 0.1, 0.7, 0.7))))
    with store._lock:
        store._conn().execute(
            "UPDATE datasets SET polygon = 'POLYGON((not wkt))'")
        store._conn().commit()
    near = np.nextafter(np.float32(0.7), np.float32(1))     # > 0.7
    assert float(near) > 0.7
    kw = dict(wkt=FP._box(float(near), 0.2, 0.9, 0.3))
    with by_statement(store):
        want = store.intersects("/d", **kw)
    assert want["files"] == ["/d/a.tif"]
    assert store.intersects("/d", **kw) == want
    kw = dict(wkt=FP._box(0.7001, 0.2, 0.9, 0.3))
    with by_statement(store):
        assert store.intersects("/d", **kw)["files"] == []
    assert store.intersects("/d", **kw)["files"] == []


# -- (b) the generation -----------------------------------------------------------

def _meta(store):
    (v,), = store._fetchall("SELECT v FROM gsky_meta WHERE k = 'generation'")
    return v


def _ingest(store):
    assert store.ingest(_rec("/d/new.tif", _ds("/d/new.tif", SQ))) == 1


def _ingest_many(store):
    assert store.ingest_many(
        _rec(f"/d/m{i}.tif", _ds(f"/d/m{i}.tif", SQ)) for i in range(3)) == 3


def _ingest_nothing(store):
    assert store.ingest_many([]) == 0


def _failed_ingest(store):
    bad = _rec("/d/bad.tif", _ds("/d/bad.tif", SQ),
               dict(_ds("/d/bad.tif", SQ), timestamps=["NOT-A-TIME"]))
    with pytest.raises(ValueError):
        store.ingest(bad)


def _failed_batch(store):
    with pytest.raises(ValueError):
        store.ingest_many([_rec("/d/ok.tif", _ds("/d/ok.tif", SQ)),
                           {"file_type": "no filename"}])


def _delete(store):
    """A file crawled again with no dataset left in it."""
    assert store.ingest(_rec("/d/a.tif")) == 0


MOVES = {"ingest": (_ingest, 1), "ingest_many": (_ingest_many, 1),
         "ingest-of-nothing": (_ingest_nothing, 1),
         "failed-ingest": (_failed_ingest, 0),
         "failed-batch": (_failed_batch, 0), "delete": (_delete, 1)}


@pytest.mark.parametrize("move", list(MOVES))
def test_a_memory_stores_generation_is_gsky_metas(move):
    store = MASStore()
    assert store.generation == _meta(store) == 0
    store.ingest(_rec("/d/a.tif", _ds("/d/a.tif", SQ)))
    before = store.intersects("/d", wkt=IN_SQ)["files"]
    assert before == ["/d/a.tif"]
    g0 = store.generation
    assert g0 == _meta(store) == 1
    act, moved = MOVES[move]
    act(store)
    assert store.generation == _meta(store) == g0 + moved
    # a failure left nothing behind, and a success is seen at once
    with by_statement(store):
        want = store.intersects("/d", wkt=IN_SQ)["files"]
    assert store.intersects("/d", wkt=IN_SQ)["files"] == want
    assert (want == before) == (moved == 0 or move == "ingest-of-nothing")
    # and the next ingest counts on from it
    _ingest(store)
    assert store.generation == _meta(store) == g0 + moved + 1


def test_reading_a_memory_stores_generation_takes_no_lock_and_no_statement():
    store = MASStore()
    _ingest(store)
    seen = []
    store._memory_conn.set_trace_callback(seen.append)
    with store._lock:           # would deadlock a read that takes it
        assert store.generation == 1
    assert seen == []


def test_a_file_store_still_sees_another_connections_bump(tmp_path):
    db = str(tmp_path / "mas.sqlite")
    reader, writer = MASStore(db), MASStore(db)
    assert reader.generation == 0
    _ingest(writer)
    assert reader.generation == writer.generation == 1
    assert reader.intersects("/d", wkt=IN_SQ)["files"] == ["/d/new.tif"]
    # raw, as the crawler's CLI or another process would
    conn = sqlite3.connect(db)
    conn.execute("UPDATE gsky_meta SET v = v + 5 WHERE k = 'generation'")
    conn.commit()
    conn.close()
    assert reader.generation == writer.generation == 6


# -- the statements a query runs, by the kind of store ------------------------------

FORMS = {
    "box": dict(wkt=IN_SQ),
    "box-instant-namespaces": dict(wkt=IN_SQ, time=_at(T0),
                                   namespaces=["b1", "b2"]),
    "no-geometry-range": dict(time=_at(T0), until=_at(T0 + DAY)),
}


@pytest.mark.parametrize("form", list(FORMS))
def test_a_file_store_runs_the_statements_it_ran(tmp_path, form):
    """The generation's and the select, spelled as the parent spelled
    them (the text below is copied from its `intersects`)."""
    store = MASStore(str(tmp_path / "mas.sqlite"))
    store.ingest(_rec("/d/a.tif", _ds("/d/a.tif", SQ, "b1"),
                      _ds("/d/a.tif", SQ, "b2")))
    kw = FORMS[form]
    if "wkt" in kw:
        sql = ("SELECT datasets.* FROM datasets"
               " JOIN datasets_rtree AS rt ON datasets.id = rt.id"
               " WHERE datasets.path LIKE ? ESCAPE '\\'"
               " AND rt.xmax >= ? AND rt.xmin <= ?"
               " AND rt.ymax >= ? AND rt.ymin <= ?")
    else:
        sql = "SELECT * FROM datasets WHERE path LIKE ? ESCAPE '\\'"
    if "time" in kw and "until" not in kw:
        sql += " AND min_stamp <= ? AND max_stamp >= ?"
    elif "time" in kw:
        sql += " AND ? < max_stamp + 1 AND min_stamp - 1 < ?"
    if kw.get("namespaces"):
        sql += " AND namespace IN (?,?)"
    seen = []
    store._conn().set_trace_callback(seen.append)
    unexpanded = []
    fetchall = store._fetchall
    store._fetchall = lambda s, a=(): unexpanded.append(s) or fetchall(s, a)
    got = store.intersects("/d", metadata="gdal", **kw)
    store._conn().set_trace_callback(None)
    assert len(got["gdal"]) == 2
    assert unexpanded == [sql]
    # less the R*Tree's own, which sqlite reports as comments
    seen = [s for s in seen if not s.startswith("--")]
    assert len(seen) == 2 and seen[0] == \
        "SELECT v FROM gsky_meta WHERE k = 'generation'"
    assert seen[1].startswith(sql.split("?")[0])
    assert (store.query_misses, store.sql_statements) == (1, 2)
    assert store._held is None                  # nothing built for it


@pytest.mark.parametrize("form", list(FORMS))
def test_a_memory_store_runs_one_statement_a_generation(form):
    store = MASStore()
    store.ingest(_rec("/d/a.tif", _ds("/d/a.tif", SQ, "b1"),
                      _ds("/d/a.tif", SQ, "b2")))
    seen = []
    store._memory_conn.set_trace_callback(seen.append)
    kw = FORMS[form]
    for k in range(5):
        got = store.intersects("/d" + "/" * k, metadata="gdal", **kw)
        assert len(got["gdal"]) == (2 if k < 2 else 0)
    # less the R*Tree's own, which sqlite reports as comments
    seen[:] = [s for s in seen if not s.startswith("--")]
    assert len(seen) == 1 and seen[0].startswith("SELECT datasets.*, rt.xmin")
    assert (store.query_misses, store.sql_statements) == (5, 1)
    _ingest(store)
    del seen[:]
    for k in range(5):
        store.intersects("/d" + "/" * k, metadata="gdal", **kw)
    assert [s for s in seen if s.startswith("SELECT")] \
        == ["SELECT datasets.*, rt.xmin, rt.xmax, rt.ymin, rt.ymax FROM "
            "datasets LEFT JOIN datasets_rtree AS rt ON rt.id = datasets.id "
            "ORDER BY datasets.id"]
    assert (store.query_misses, store.sql_statements) == (10, 2)


def test_debug_cache_has_mas_sql(tmp_path):
    from gsky_tpu.server.metrics import cache_stats
    before = cache_stats()["mas_sql"]
    assert set(before) == {"queries", "statements"}
    memory, on_disk = MASStore(), MASStore(str(tmp_path / "mas.sqlite"))
    for store in (memory, on_disk):
        _ingest(store)
        for k in range(4):
            store.intersects("/d", wkt=FP._box(12 + 0.1 * k, 12, 13, 13))
        # answered by the answer cache: no query, no statement
        store.intersects("/d", wkt=FP._box(12 + 0.1 * 0, 12, 13, 13))
    after = cache_stats()["mas_sql"]
    assert after["queries"] - before["queries"] == 8
    assert after["statements"] - before["statements"] == 1 + 2 * 4
    assert (memory.sql_statements, on_disk.sql_statements) == (1, 8)


# -- (c) six threads query while a seventh ingests -----------------------------------

class CountedLock:
    """`MASStore._lock` with its acquisitions written down by thread."""

    def __init__(self, lock):
        self._lock = lock
        self.by_thread = {}

    def __enter__(self):
        me = threading.get_ident()
        self.by_thread[me] = self.by_thread.get(me, 0) + 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_six_threads_beside_an_ingest_answer_by_the_generation_they_read():
    """A writer moves /d/a.tif between EAST and WEST, one generation a
    move, and writes down the statement's answer to both questions for
    every generation it makes.  Six readers ask both, each question new
    to the answer cache.  Every answer is the statement's for the
    generation whose rows the query was given, which is never older than
    the one it read; and the readers take `_lock` to build a generation
    and for nothing else."""
    store = MASStore()
    store.ingest(_rec("/d/still.tif", _ds("/d/still.tif", FP.EAST)))
    store.ingest(_rec("/d/a.tif", _ds("/d/a.tif", FP.EAST)))
    sides = {"east": BBox(24, 14, 24.5, 15), "west": BBox(4, 14, 4.5, 15)}

    def statement(side):
        rows = store._select("/d", sides[side], None, None, None)
        return sorted(r[store._i_path] for r in rows)
    by_generation = {store.generation: {s: statement(s) for s in sides}}
    counted = store._lock = CountedLock(store._lock)
    local = threading.local()
    generation_rows = store._generation_rows

    def watched(generation):
        held = generation_rows(generation)
        local.read, local.given = generation, held.generation
        return held
    store._generation_rows = watched

    readers, per_reader, errors, answers = 6, 60, [], []
    first, deadline = store.generation, time.monotonic() + 60
    reading = [True] * readers
    start = threading.Barrier(readers + 1)

    def write():
        try:
            start.wait()
            k = 0
            while any(reading) or k < 20:
                store.ingest(_rec("/d/a.tif", _ds(
                    "/d/a.tif", FP.WEST if k % 2 == 0 else FP.EAST)))
                by_generation[store.generation] = {
                    s: statement(s) for s in sides}
                k += 1
                # a lock is not fair: a writer that never rests takes it
                # again before a reader that waits to build has woken
                time.sleep(0.002)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    def read(t):
        try:
            start.wait()
            draw, k = random.Random(t), 0
            # as long as it takes the writer to move the row a few times
            while k < per_reader or (store.generation < first + 12
                                     and time.monotonic() < deadline):
                side = draw.choice(["east", "west"])
                b = sides[side]
                x = b.xmin + 1e-7 * k + 0.05 * t
                got = store.intersects(
                    "/d", wkt=FP._box(x, b.ymin, x + 0.4, b.ymax))
                answers.append((side, local.read, local.given, got["files"]))
                k += 1
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)
        finally:
            reading[t] = False

    ts = [threading.Thread(target=write)] + [
        threading.Thread(target=read, args=(t,)) for t in range(readers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts)
    assert not errors, errors[:1]
    assert len(answers) >= readers * per_reader
    for side, read_, given, files in answers:
        assert given >= read_
        assert files == by_generation[given][side], (side, read_, given)
    assert len({given for _, _, given, _ in answers}) >= 3
    # the readers saw the row in both places
    kinds = {tuple(f) for _, _, _, f in answers}
    assert kinds <= {("/d/a.tif", "/d/still.tif"), ("/d/still.tif",),
                     ("/d/a.tif",), ()} and len(kinds) >= 3
    # the lock: the writer's ingests and statements, and the readers'
    # to build a generation (one builds; those that met the generation
    # with it queue behind it and find it built)
    writer = ts[0].ident
    by_readers = sum(n for who, n in counted.by_thread.items()
                     if who != writer)
    built = store.sql_statements
    assert 3 <= built <= len(by_generation)
    assert built <= by_readers <= readers * built
    # and once the generation is built, none at all
    store.intersects("/d", wkt=FP.IN_EAST)
    taken = sum(counted.by_thread.values())
    statements = store.sql_statements
    for k in range(50):
        store.intersects("/d", wkt=FP._box(24 + 0.01 * k, 14, 25, 15),
                         metadata="gdal", namespaces=["b1"])
        store.intersects("/d" + "/" * (k % 3), time=_at(T0 + k))
    assert sum(counted.by_thread.values()) == taken
    assert store.sql_statements == statements


# -- (d) 100,000 rows -----------------------------------------------------------------

N = 100_000


class Counted(list):
    """A list that writes down how it is read."""

    def __init__(self, items):
        super().__init__(items)
        self.items_read = self.walks = 0

    def __getitem__(self, i):
        self.items_read += 1
        return super().__getitem__(i)

    def __iter__(self):
        self.walks += 1
        return super().__iter__()


@pytest.fixture(scope="module")
def catalog():
    """Landsat-ish boxes over Australia, 16 namespaces, a year of
    stamps, two collections: written straight into the tables (an
    ingest of 100,000 records takes 25 s), then one empty ingest for the
    generation."""
    rng = np.random.default_rng(1)
    x0, y0 = rng.uniform(112, 152, N), rng.uniform(-42, -12, N)
    w, h = rng.uniform(0.2, 0.4, (2, N))
    t = 1.5e9 + rng.uniform(0, 3e7, N)
    store = MASStore()
    with store._lock:
        store._conn().executemany(
            "INSERT INTO datasets(path, namespace, xmin, ymin, xmax, ymax,"
            " min_stamp, max_stamp) VALUES (?,?,?,?,?,?,?,?)",
            [(f"/{'ab'[i % 2]}/scenes/l8_{i:07d}.tif", f"band{i % 16}",
              x0[i], y0[i], x0[i] + w[i], y0[i] + h[i], t[i], t[i] + 60)
             for i in range(N)])
        store._conn().commit()
    store.ingest_many([])
    return store


def _questions(n, seed=7):
    rng = np.random.default_rng(seed)
    for k in range(n):
        cx, cy = rng.uniform(113, 151), rng.uniform(-41, -13)
        yield (BBox(cx, cy, cx + 0.3, cy + 0.3),
               *((1.51e9, 1.52e9) if k % 2 else (1.515e9, None)),
               [f"band{j}" for j in rng.permutation(16)[:3]]
               if k % 3 else None)


def test_a_big_catalog_selects_the_statements_rows(catalog):
    """Under a namespace filter in the statement's order, which is the
    namespace index's; without one the statement walks an R*Tree of
    more than one node in the tree's order, and the arrays answer the
    same rows by id."""
    picked = 0
    for qb, t_a, t_b, namespaces in _questions(60):
        got = catalog._candidates(catalog.generation, "/a", qb, t_a, t_b,
                                  namespaces)
        want = catalog._select("/a", qb, t_a, t_b, namespaces)
        if namespaces:
            assert got == want
        else:
            assert got == sorted(want)          # a row begins with its id
        picked += len(got)
    assert picked > 50


def test_a_big_catalogs_query_walks_no_row_it_does_not_return(catalog):
    """No Python loop over the rows: a query reads from the row list the
    rows it returns and no other, the paths are walked once a prefix and
    not once a query, and the step stays under the R*Tree's own figure
    (1-2 ms at this size, against 21.5 ms for a scan: `_SCHEMA`'s note)
    with room for a loaded machine."""
    held = catalog._generation_rows(catalog.generation)
    assert len(held.rows) == N
    rows, paths = held.rows, held._paths
    held.rows, held._paths = Counted(rows), Counted(paths)
    try:
        held._prefixes.clear()
        questions = list(_questions(40))
        returned, took = 0, []
        for qb, t_a, t_b, namespaces in questions:
            t0 = time.perf_counter()
            got = catalog._candidates(catalog.generation, "/a", qb, t_a, t_b,
                                      namespaces)
            took.append(time.perf_counter() - t0)
            returned += len(got)
        assert 0 < returned == held.rows.items_read < N // 100
        assert (held.rows.walks, held._paths.walks) == (0, 1)
        assert sorted(took)[len(took) // 2] < 0.010
    finally:
        held.rows, held._paths = rows, paths
    assert catalog.sql_statements == 1
