"""A dataset row is decoded once per store generation and shared,
read-only, by every query that returns it (`MASStore._records`,
`store.GdalRecord`): same answers as a fresh parse, dead with the data,
bounded, safe under threads, and never written by its consumers."""

import copy
import json
import re
import sys
import threading
import urllib.parse

import pytest

from gsky_tpu.index import MASClient
from gsky_tpu.index.client import Dataset
from gsky_tpu.index.sharded import MASShardedStore
from gsky_tpu.index.store import MASStore, fmt_time, parse_time

from fixtures import make_archive

POLY = "POLYGON((140 -36,142 -36,142 -34,140 -34,140 -36))"
T0 = 946684800.0


def _stamps(n, first=T0):
    return [fmt_time(first + 8 * 86400 * i) for i in range(n)]


def _record(path, ns="phot_veg", stamps=None, **more):
    ds = {"ds_name": f"NETCDF:{path}:{ns}", "namespace": ns,
          "array_type": "Float32", "srs": "EPSG:4326",
          "geotransform": [140, 0.004, 0, -34, 0, -0.004],
          "polygon": POLY, "nodata": -1.0,
          "timestamps": _stamps(40) if stamps is None else stamps}
    ds.update(more)
    return {"filename": path, "file_type": "nc", "geo_metadata": [ds]}


def _wkt(k):
    """The k-th of a family of distinct squares inside POLY: each a new
    question to the store's answer cache, each returning every row."""
    x = 140.1 + 0.005 * k
    return (f"POLYGON(({x} -35.5,{x + 0.3} -35.5,{x + 0.3} -35.2,"
            f"{x} -35.2,{x} -35.5))")


def _fresh(resp):
    """What a client makes of the answer when nothing is shared: the
    records as masapi's JSON carries them, every stamp parsed anew."""
    return [Dataset.from_json(j)
            for j in json.loads(json.dumps(resp))["gdal"]]


def _parent_gdal(store, gpath):
    """The `gdal` answer for every row under gpath, built straight from
    the SQL rows the way the store built it before rows were kept."""
    cols = store._columns
    out = []
    for row in store._fetchall(
            "SELECT * FROM datasets WHERE path LIKE ? ORDER BY id",
            (gpath + "%",)):
        r = dict(zip(cols, row))
        load = lambda k: json.loads(r[k]) if r[k] else None  # noqa: E731
        out.append({
            "file_path": r["path"], "ds_name": r["ds_name"],
            "namespace": r["namespace"], "array_type": r["array_type"],
            "srs": r["srs"],
            "geo_transform": json.loads(r["geo_transform"] or "null"),
            "timestamps": json.loads(r["timestamps"] or "[]"),
            "polygon": r["polygon"], "overviews": load("overviews"),
            "means": load("means"), "sample_counts": load("sample_counts"),
            "nodata": r["nodata"] if r["nodata"] is not None else 0.0,
            "axes": load("axes"), "geo_loc": load("geo_loc")})
    return out


AXES = [{"name": "time", "params": [1.0, 2.0], "strides": [1],
         "shape": [2], "grid": "enum"},
        {"name": "level", "params": [10.0, 20.0, 30.0], "strides": [2],
         "shape": [3], "grid": "enum"}]


def _single(tmp_path):
    s = MASStore()
    for ns in ("phot_veg", "nphot_veg", "bare_soil"):
        s.ingest(_record(f"/d/{ns}.nc", ns))
    return s, "/d", 3


def _sharded(tmp_path):
    root = tmp_path / "root"
    s = MASShardedStore(str(root), db_dir=str(tmp_path / "dbs"))
    for shard in ("a", "b"):
        for ns in ("phot_veg", "bare_soil"):
            s.ingest(_record(f"{root}/{shard}/{ns}.nc", ns))
    return s, str(root), 4


def _no_timestamps(tmp_path):
    s = MASStore()
    s.ingest(_record("/d/static.nc", stamps=[]))
    s.ingest(_record("/d/moving.nc", "bare_soil"))
    return s, "/d", 2


def _with_axes(tmp_path):
    s = MASStore()
    s.ingest(_record("/d/cube.nc", axes=AXES, means=[0.5] * 40,
                     sample_counts=[7] * 40,
                     overviews=[{"x_size": 64, "y_size": 64}]))
    return s, "/d", 1


@pytest.mark.parametrize("make", [_single, _sharded, _no_timestamps,
                                  _with_axes])
def test_datasets_equal_a_fresh_parse(make, tmp_path):
    store, gpath, n = make(tmp_path)
    client = MASClient(store)
    for k in range(3):          # decoded by the first, kept for the rest
        kw = dict(srs="EPSG:4326", wkt=_wkt(k))
        got = client.intersects(gpath, **kw)
        want = _fresh(store.intersects(gpath, metadata="gdal", **kw))
        assert len(got) == n
        assert got == want      # dataclass equality: field for field
        for d in got:
            assert d.timestamps == [parse_time(s)
                                    for s in d.timestamps_iso]
            assert all(type(t) is float for t in d.timestamps)
    # a query a second time, from the store's answer cache, too
    assert client.intersects(gpath, srs="EPSG:4326", wkt=_wkt(0)) \
        == _fresh(store.intersects(gpath, metadata="gdal",
                                   srs="EPSG:4326", wkt=_wkt(0)))


@pytest.mark.parametrize("make", [_single, _no_timestamps, _with_axes])
def test_masapi_json_is_what_it_was(make, tmp_path):
    store, gpath, _ = make(tmp_path)
    want = json.dumps({"gdal": _parent_gdal(store, gpath)})
    for k in range(2):
        assert json.dumps(store.intersects(
            gpath, metadata="gdal", srs="EPSG:4326", wkt=_wkt(k))) == want
    assert json.dumps(store.intersects(gpath, metadata="gdal")) == want
    assert json.dumps(store.intersects(gpath, metadata="gdal")) == want


def test_masapi_over_http_is_what_it_was(tmp_path):
    """The served bytes, and a client over HTTP (which parses for
    itself) against one in-process."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from gsky_tpu.index.api import build_app
    store, gpath, _ = _with_axes(tmp_path)
    store.ingest(_record("/d/more.nc", "bare_soil"))
    want = json.dumps({"gdal": _parent_gdal(store, gpath)})

    async def go():
        client = TestClient(TestServer(build_app(store)))
        await client.start_server()
        try:
            bodies = []
            for k in (0, 1, 1):
                resp = await client.get(gpath, params={
                    "intersects": "", "metadata": "gdal",
                    "srs": "EPSG:4326", "wkt": _wkt(k)})
                bodies.append(await resp.text())
            return bodies
        finally:
            await client.close()
    for body in asyncio.new_event_loop().run_until_complete(go()):
        assert body == want
        assert [Dataset.from_json(j) for j in json.loads(body)["gdal"]] \
            == MASClient(store).intersects(gpath, srs="EPSG:4326",
                                           wkt=_wkt(5))


@pytest.mark.parametrize("same_store", [True, False],
                         ids=["same-store", "second-store-same-file"])
def test_an_ingest_is_seen_by_the_next_query(tmp_path, same_store):
    db = str(tmp_path / "mas.sqlite")
    reader = MASStore(db)
    writer = reader if same_store else MASStore(db)
    writer.ingest(_record("/d/a.nc", stamps=_stamps(5)))
    writer.ingest(_record("/d/b.nc", "bare_soil", stamps=_stamps(5)))
    client = MASClient(reader)
    kw = dict(srs="EPSG:4326", wkt=_wkt(0))
    for _ in range(2):
        old = client.intersects("/d", **kw)
    assert [len(d.timestamps) for d in old] == [5, 5]
    later = _stamps(7, T0 + 86400)
    writer.ingest(_record("/d/a.nc", stamps=later))
    new = client.intersects("/d", **kw)
    by_path = {d.file_path: d for d in new}
    assert by_path["/d/a.nc"].timestamps_iso == later
    assert by_path["/d/a.nc"].timestamps == [parse_time(s) for s in later]
    assert by_path["/d/b.nc"].timestamps_iso == _stamps(5)
    # what the earlier query was given is not rewritten under it
    assert [len(d.timestamps) for d in old] == [5, 5]
    assert new == _fresh(reader.intersects("/d", metadata="gdal", **kw))
    # nothing of the old generation is kept
    gen, kept = reader._rows
    assert gen == reader.generation and len(kept) == 2


def test_a_database_written_by_the_parent_opens_and_answers(tmp_path):
    """No migration: decoded rows live in memory only, so a file
    database is opened and answered from with its schema left as it
    was (`_SCHEMA` is the parent's, column for column)."""
    import sqlite3
    db = str(tmp_path / "old.sqlite")
    MASStore(db).ingest(_record("/d/a.nc"))
    conn = sqlite3.connect(db)
    before = conn.execute("SELECT sql FROM sqlite_master ORDER BY name"
                          ).fetchall()
    conn.close()
    store = MASStore(db)
    got = MASClient(store).intersects("/d", srs="EPSG:4326", wkt=_wkt(0))
    assert [d.timestamps_iso for d in got] == [_stamps(40)]
    conn = sqlite3.connect(db)
    assert conn.execute("SELECT sql FROM sqlite_master ORDER BY name"
                        ).fetchall() == before
    conn.close()


def test_a_kept_row_answers_only_for_the_row_it_was_decoded_from():
    """sqlite hands a deleted row's id to the next insert; a kept record
    under the same id and generation must not answer for it."""
    store, gpath, _ = _with_axes(None)
    gen = store.generation
    row, = store._fetchall("SELECT * FROM datasets")
    first, = store._records([row], gen)
    i = store._columns.index("timestamps")
    forged = row[:i] + (json.dumps(_stamps(3)),) + row[i + 1:]
    other, = store._records([forged], gen)
    assert first["timestamps"] == _stamps(40) and len(first.unix) == 40
    assert other["timestamps"] == _stamps(3) and len(other.unix) == 3
    assert (store.row_hits, store.row_misses) == (0, 2)
    again, = store._records([forged], gen)
    assert again == other and again.unix is other.unix
    assert (store.row_hits, store.row_misses) == (1, 2)
    # a query that read an older generation keeps nothing and is not
    # answered from what is kept
    stale, = store._records([row], gen - 1)
    assert stale["timestamps"] == _stamps(40)
    assert (store.row_hits, store.row_misses) == (1, 3)
    assert store._rows[0] == gen


def test_eight_threads_get_equal_answers_and_the_counters_add_up():
    store, gpath, n = _single(None)
    client = MASClient(store)
    # nothing decoded yet: the threads race for each row's first decode
    want = [Dataset.from_json(j) for j in _parent_gdal(store, gpath)]
    hits0, misses0 = store.row_hits, store.row_misses
    assert (hits0, misses0) == (0, 0)
    total0 = (MASStore.total_row_hits, MASStore.total_row_misses)
    per_thread, threads, errors = 25, 8, []
    start = threading.Barrier(threads)

    def work(t):
        try:
            start.wait()
            for k in range(per_thread):
                got = client.intersects(gpath, srs="EPSG:4326",
                                        wkt=_wkt(t * per_thread + k))
                assert got == want
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)
    ts = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)         # many more switches mid-query
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts)
    assert not errors, errors[:1]
    hits = store.row_hits - hits0
    misses = store.row_misses - misses0
    assert hits + misses == threads * per_thread * n
    # threads that meet a row not kept yet may each decode it, no more
    assert n <= misses <= threads * n
    assert (MASStore.total_row_hits - total0[0],
            MASStore.total_row_misses - total0[1]) == (hits, misses)
    assert len(store._rows[1]) == n


def test_the_bound_on_kept_rows_holds(monkeypatch):
    monkeypatch.setattr(MASStore, "_ROW_CACHE_MAX", 4)
    store = MASStore()
    store.ingest_many(_record(f"/d/f{i:02d}.nc", stamps=_stamps(2, T0 + i))
                      for i in range(10))
    client = MASClient(store)
    for k in range(3):
        got = client.intersects("/d", srs="EPSG:4326", wkt=_wkt(k))
        assert len(got) == 10
        assert got == _fresh(store.intersects(
            "/d", metadata="gdal", srs="EPSG:4326", wkt=_wkt(k)))
        assert len(store._rows[1]) <= 4
    # two rows fit: kept and found again
    hits0 = store.row_hits
    for k in range(3):
        client.intersects("/d/f00", srs="EPSG:4326", wkt=_wkt(10 + k))
    assert store.row_hits - hits0 >= 2


def test_debug_cache_has_mas_rows():
    from gsky_tpu.server.metrics import cache_stats
    before = cache_stats()["mas_rows"]
    store, gpath, n = _single(None)
    for k in range(2):
        MASClient(store).intersects(gpath, srs="EPSG:4326", wkt=_wkt(k))
    after = cache_stats()["mas_rows"]
    assert after["misses"] - before["misses"] == n
    assert after["hits"] - before["hits"] == n
    assert set(after) == {"hits", "misses"}


# -- the served paths: same bytes, shared lists never written -----------------

BBOX3857 = "16478548,-4211230,16489679,-4198025"
DATE = "2020-01-10T00:00:00.000Z"


def _geojson(x0):
    return json.dumps({"type": "FeatureCollection", "features": [{
        "type": "Feature", "geometry": {
            "type": "Polygon",
            "coordinates": [[[x0, -36.0], [x0 + 0.5, -36.0],
                             [x0 + 0.5, -35.0], [x0, -35.0],
                             [x0, -36.0]]]}}]})


class _AsOverHTTP:
    """The store's answers as a remote masapi's arrive: through JSON, so
    with nothing shared and every stamp parsed by the client."""

    def __init__(self, store):
        self._store = store

    def intersects(self, *a, **kw):
        return json.loads(json.dumps(self._store.intersects(*a, **kw)))

    def __getattr__(self, name):
        return getattr(self._store, name)


def _server(root, store, arch_root):
    from gsky_tpu.server.config import ConfigWatcher
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    root.mkdir()
    (root / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [{"name": "landsat", "title": "scenes",
                    "data_source": arch_root,
                    "rgb_products": ["LC08_20200110_T1"],
                    "time_generator": "mas"}],
        "processes": [{"identifier": "geometryDrill", "title": "drill",
                       "max_area": 10000, "approx": False,
                       "data_sources": [{
                           "data_source": arch_root,
                           "rgb_products": ["phot_veg", "bare_soil"]}]}],
    }))
    client = MASClient(store)
    watcher = ConfigWatcher(str(root), mas_factory=lambda addr: client,
                            install_signal=False)
    return OWSServer(watcher, mas_factory=lambda addr: client,
                     metrics=MetricsLogger())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("rows")
    arch = make_archive(str(root / "data"))
    return {"arch": arch,
            "shared": _server(root / "conf", arch["store"], arch["root"]),
            "fresh": _server(root / "conf_fresh",
                             _AsOverHTTP(arch["store"]), arch["root"])}


def _get(server, path):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    async def go():
        client = TestClient(TestServer(server.app()))
        await client.start_server()
        try:
            resp = await client.get(path)
            return resp.status, await resp.read()
        finally:
            await client.close()
    return asyncio.new_event_loop().run_until_complete(go())


def _execute(server, x0):
    status, body = _get(
        server, "/ows?service=WPS&request=Execute&identifier=geometryDrill"
                f"&datainputs=geometry={urllib.parse.quote(_geojson(x0))}")
    assert status == 200, body[:400]
    # the wall clock to the second is no part of the answer
    return re.sub(rb'creationTime="[^"]*"', b'creationTime=""', body)


def test_executes_give_the_bytes_of_a_fresh_parse(served):
    from gsky_tpu.pipeline import drill_cache as DC
    store = served["arch"]["store"]
    hits0 = store.row_hits
    got = [_execute(served["shared"], x0) for x0 in (148.0, 148.0, 148.3)]
    assert store.row_hits > hits0       # the second and third found rows
    # host reads or the device leg, whichever each request met: both
    # sides once more with the stacks resident
    DC.default_drill_cache.wait_idle()
    got += [_execute(served["shared"], x0) for x0 in (148.0, 148.3)]
    want = [_execute(served["fresh"], x0)
            for x0 in (148.0, 148.0, 148.3, 148.0, 148.3)]
    assert got == want
    assert got[0] == got[1] == got[3] and got[2] == got[4] != got[0]
    assert b"2020-01-10" in got[0]


def _kept(store):
    records = {rid: e.record for rid, e in store._rows[1].items()}
    return {rid: (rec, rec["timestamps"], rec.unix, rec["geo_transform"],
                  rec["axes"], rec["means"])
            for rid, rec in records.items()}


def test_shared_lists_are_unchanged_after_a_drill_and_a_tile(served):
    store = served["arch"]["store"]
    client = MASClient(store)
    # every row of the archive decoded and kept
    handed = client.intersects(served["arch"]["root"])
    kept = _kept(store)
    assert len(kept) == len(handed) >= 3
    snapshot = copy.deepcopy({rid: (dict(v[0]), v[0].unix)
                              for rid, v in kept.items()})
    # a Dataset's lists ARE the store's
    ids = {id(v[2]) for v in kept.values()}
    assert {id(d.timestamps) for d in handed} == ids
    hits0 = store.row_hits
    _execute(served["shared"], 148.1)
    status, body = _get(
        served["shared"],
        "/ows?service=WMS&request=GetMap&version=1.3.0&layers=landsat"
        f"&crs=EPSG:3857&bbox={BBOX3857}&width=256&height=256"
        f"&format=image/png&time={DATE}")
    assert status == 200, body[:300]
    assert store.row_hits > hits0       # both went through the kept rows
    after = _kept(store)
    assert set(after) == set(kept)
    for rid, v in after.items():
        assert all(a is b for a, b in zip(v, kept[rid]))   # not replaced
        assert (dict(v[0]), v[0].unix) == snapshot[rid]    # not written
