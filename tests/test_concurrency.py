"""Concurrency stress tests — the `-race`-style coverage SURVEY §5.2
notes the reference never had.  Hammers the shared mutable state
(executor geo/stack caches, device scene cache, handle cache, MAS store)
from many threads and asserts results stay correct and deterministic."""

import threading
import time

import numpy as np
import pytest

from gsky_tpu.geo.crs import EPSG3857, EPSG4326
from gsky_tpu.geo.transform import BBox, transform_bbox
from gsky_tpu.index.client import MASClient
from gsky_tpu.pipeline.tile import TilePipeline
from gsky_tpu.pipeline.types import GeoTileRequest

from fixtures import make_archive

NS = "LC08_20200110_T1"


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    return make_archive(str(tmp_path_factory.mktemp("conc")), scenes=2,
                        size=256)


def _req(archive, shift=0.0):
    bb = transform_bbox(
        BBox(148.02 + shift, -35.32, 148.12 + shift, -35.22),
        EPSG4326, EPSG3857)
    return GeoTileRequest(collection=archive["root"], bands=[NS],
                          bbox=bb, crs=EPSG3857, width=128, height=128)


def test_parallel_renders_are_deterministic(archive):
    """32 concurrent renders over 4 distinct tiles from one shared
    pipeline must equal the single-threaded results."""
    pipe = TilePipeline(MASClient(archive["store"]))
    shifts = [0.0, 0.01, 0.02, 0.03]
    expected = {}
    for s in shifts:
        res = pipe.process(_req(archive, s))
        expected[s] = (np.asarray(res.data[NS]).copy(),
                       np.asarray(res.valid[NS]).copy())

    errors = []
    results = [None] * 32

    def worker(i):
        try:
            s = shifts[i % len(shifts)]
            res = pipe.process(_req(archive, s))
            results[i] = (s, np.asarray(res.data[NS]),
                          np.asarray(res.valid[NS]))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    for r in results:
        assert r is not None
        s, data, valid = r
        np.testing.assert_array_equal(valid, expected[s][1])
        np.testing.assert_array_equal(data, expected[s][0])


def test_scene_cache_single_decode_under_contention(archive):
    """Many threads requesting the same uncached scene must decode it
    exactly once (per-key latch), and all get the same device buffer."""
    from gsky_tpu.pipeline.scene_cache import SceneCache
    mas = MASClient(archive["store"])
    ds = next(d for d in mas.intersects(archive["root"], namespaces=NS)
              if d.file_path.endswith(".tif"))
    from gsky_tpu.pipeline.types import Granule
    g = Granule(path=ds.file_path, ds_name=ds.ds_name, namespace=NS,
                base_namespace=NS, band=1, time_index=None,
                timestamp=0.0, srs=ds.srs,
                geo_transform=ds.geo_transform, nodata=ds.nodata,
                array_type=ds.array_type)

    cache = SceneCache()
    loads = []
    orig = cache._load

    def counting_load(granule, level=1):
        loads.append(granule.path)
        return orig(granule, level)

    cache._load = counting_load
    out = [None] * 16

    def worker(i):
        out[i] = cache.get(g)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert all(s is not None for s in out)
    assert len(loads) == 1, f"scene decoded {len(loads)} times"
    assert len({id(s.dev) for s in out}) == 1


def test_mas_store_concurrent_queries(archive):
    """The sqlite-backed store must serve concurrent intersects without
    errors or cross-talk."""
    mas = MASClient(archive["store"])
    wkt = ("POLYGON((148 -36,149 -36,149 -35,148 -35,148 -36))")
    base = mas.intersects(archive["root"], srs="EPSG:4326", wkt=wkt)
    assert base
    errors = []

    def worker():
        try:
            got = mas.intersects(archive["root"], srs="EPSG:4326",
                                 wkt=wkt)
            assert len(got) == len(base)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors


def test_drill_stack_cache_single_load_under_contention(tmp_path):
    """16 threads racing the same drill stack must trigger exactly one
    load (the inflight latch), and all get the same device buffer."""
    import threading

    from gsky_tpu.geo.crs import EPSG4326
    from gsky_tpu.io.netcdf import write_netcdf3
    from gsky_tpu.pipeline.drill_cache import DrillStackCache

    p = str(tmp_path / "c.nc")
    rng = np.random.default_rng(0)
    write_netcdf3(p, {"v": rng.uniform(0, 1, (4, 32, 32)).astype(
        np.float32)}, 148.0 + np.arange(32) * 0.01,
        -35.0 - np.arange(32) * 0.01, EPSG4326,
        times=1.6e9 + np.arange(4) * 86400.0, nodata=-9.0)

    cache = DrillStackCache()
    loads = []
    orig = cache._load

    def counting(path, is_nc, var, band0, nodata):
        loads.append(path)
        time.sleep(0.05)       # widen the race window
        return orig(path, is_nc, var, band0, nodata)

    cache._load = counting
    out = [None] * 16

    def worker(i):
        out[i] = cache.get(p, True, "v", 1, None)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30)
    assert len(loads) == 1
    serials = {s.serial for s in out if s is not None}
    assert len(serials) == 1 and all(s is not None for s in out)


def test_sharded_store_concurrent_ingest_and_query(tmp_path):
    """Concurrent ingest into distinct shards + root fan-out queries
    must neither crash nor drop records."""
    import threading

    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index import MASShardedStore
    from gsky_tpu.index.crawler import extract
    from gsky_tpu.io import write_geotiff

    root = tmp_path / "data"
    utm = parse_crs("EPSG:32755")
    recs = []
    for k in range(8):
        d = root / f"coll{k}"
        d.mkdir(parents=True)
        gt = GeoTransform(590000.0 + k * 100, 30.0, 0.0, 6105000.0,
                          0.0, -30.0)
        fp = str(d / f"coll{k}_20200110.tif")
        write_geotiff(fp, np.ones((32, 32), np.int16), gt, utm)
        recs.append(extract(fp))
    store = MASShardedStore(str(root))
    errors = []

    def ingest(rec):
        try:
            store.ingest(rec)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def query():
        try:
            for _ in range(5):
                store.intersects(str(root), metadata="gdal")
                store.timestamps(str(root))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=ingest, args=(r,))
               for r in recs] + \
              [threading.Thread(target=query) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30)
    assert not errors, errors[:2]
    final = store.intersects(str(root), metadata="gdal")
    assert len(final["gdal"]) == 8
