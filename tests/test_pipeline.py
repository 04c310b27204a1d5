"""Pipeline tests: tile rendering end-to-end over the fixture archive,
granule expansion, drill statistics, extent suggestion, feature info."""

import datetime as dt
import math
import os

import numpy as np
import pytest

from gsky_tpu.geo.crs import EPSG3857, EPSG4326, parse_crs
from gsky_tpu.geo.transform import BBox, GeoTransform, transform_bbox
from gsky_tpu.index import MASClient
from gsky_tpu.index.client import Dataset, DatasetAxis
from gsky_tpu.io.geotiff import GeoTIFF
from gsky_tpu.pipeline import (DrillPipeline, GeoDrillRequest, GeoTileRequest,
                               TilePipeline, compute_reprojection_extent)
from gsky_tpu.pipeline.drill import drill_csv
from gsky_tpu.pipeline.feature_info import get_feature_info
from gsky_tpu.pipeline.granule import expand_granules
from gsky_tpu.pipeline.types import AxisSelector, MaskSpec

from fixtures import make_archive


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    return make_archive(str(tmp_path_factory.mktemp("parch")))


@pytest.fixture(scope="module")
def mas(archive):
    return MASClient(archive["store"])


def t(day: int) -> float:
    return dt.datetime(2020, 1, day, tzinfo=dt.timezone.utc).timestamp()


# over the fixture granules: UTM55 E 590000-613040, N 6085800-6105000
# ~ lon 147.99-148.24, lat -35.19..-35.37
TILE_BBOX = transform_bbox(BBox(148.02, -35.32, 148.12, -35.22),
                           EPSG4326, EPSG3857)


class TestGranuleExpansion:
    def _ds(self, stamps, axes=None):
        return Dataset(
            file_path="/x.nc", ds_name='NETCDF:"/x.nc":v', namespace="v",
            array_type="Float32", srs="EPSG:4326",
            geo_transform=[0, 1, 0, 0, 0, -1],
            timestamps=[float(s) for s in stamps],
            timestamps_iso=[str(s) for s in stamps],
            polygon="POLYGON((0 0,1 0,1 1,0 1,0 0))", nodata=-1.0,
            axes=axes or [])

    def test_time_range(self):
        ds = self._ds([100, 200, 300])
        gs = expand_granules([ds], 150.0, 350.0)
        assert [g.timestamp for g in gs] == [200.0, 300.0]
        assert [g.band for g in gs] == [2, 3]  # time index + 1
        assert all(g.time_index == g.band - 1 for g in gs)

    def test_exact_time(self):
        ds = self._ds([100, 200])
        gs = expand_granules([ds], 200.0, None)
        assert [g.timestamp for g in gs] == [200.0]

    def test_extra_axis_expansion(self):
        ax = DatasetAxis(name="depth", params=[5.0, 10.0, 20.0],
                         strides=[2], shape=[3], grid="default")
        ds = self._ds([100], axes=[ax])
        sel = AxisSelector(name="depth", start=5.0, end=15.0)
        gs = expand_granules([ds], 100.0, None, [sel])
        assert {g.namespace for g in gs} == {"v#depth=5", "v#depth=10"}
        assert sorted(g.band for g in gs) == [1, 3]  # strides applied

    def test_unselected_axis_takes_first(self):
        ax = DatasetAxis(name="depth", params=[5.0, 10.0], strides=[1],
                         shape=[2])
        ds = self._ds([100], axes=[ax])
        gs = expand_granules([ds], 100.0, None)
        assert len(gs) == 1
        assert gs[0].namespace == "v#depth=5"

    def test_dedup(self):
        ds = self._ds([100])
        gs = expand_granules([ds, ds], 100.0, None)
        assert len(gs) == 1


class TestTilePipeline:
    def test_landsat_tile_renders(self, mas, archive):
        # a 3857 tile over both UTM granules on the shared date window
        req = GeoTileRequest(
            collection=archive["root"], bands=["LC08_20200110_T1"],
            bbox=TILE_BBOX, crs=EPSG3857, width=256, height=256,
            start_time=t(9), end_time=t(13))
        pipe = TilePipeline(mas)
        res = pipe.process(req)
        assert res.namespaces == ["LC08_20200110_T1"]
        d = res.data["LC08_20200110_T1"]
        ok = res.valid["LC08_20200110_T1"]
        assert d.shape == (256, 256)
        assert ok.sum() > 1000  # tile covered by the granule
        assert 200 <= d[ok].mean() <= 3000

    def test_warp_matches_direct_read(self, mas, archive):
        """Pixel-parity spot check: nearest-warped value == the source
        pixel the reference's truncation picks."""
        path = archive["paths"][0]
        with GeoTIFF(path) as g:
            src = g.read(1)
            src_gt, src_crs = g.gt, g.crs
        req = GeoTileRequest(
            collection=archive["root"], bands=["LC08_20200110_T1"],
            bbox=TILE_BBOX, crs=EPSG3857, width=64, height=64,
            start_time=t(10), end_time=t(10))
        pipe = TilePipeline(mas)
        res = pipe.process(req)
        d = res.data["LC08_20200110_T1"]
        ok = res.valid["LC08_20200110_T1"]
        from gsky_tpu.ops.warp import coord_grid
        rows, cols = coord_grid(req.dst_gt(), EPSG3857, 64, 64, src_gt,
                                src_crs)
        for y, x in [(10, 10), (32, 40), (60, 5)]:
            if not ok[y, x]:
                continue
            ri = int(math.floor(rows[y, x] + 0.5 + 1e-10))
            ci = int(math.floor(cols[y, x] + 0.5 + 1e-10))
            if 0 <= ri < src.shape[0] and 0 <= ci < src.shape[1]:
                assert d[y, x] == float(src[ri, ci])

    def test_temporal_mosaic_prefers_newest(self, mas, archive):
        # both scenes overlap; in the overlap the 01-11 scene must win
        req = GeoTileRequest(
            collection=archive["root"],
            bands=["LC08_20200110_T1", "LC08_20200111_T1"],
            bbox=TILE_BBOX, crs=EPSG3857, width=128, height=128,
            start_time=t(9), end_time=t(13))
        pipe = TilePipeline(mas)
        res = pipe.process(req)
        assert set(res.namespaces) == {"LC08_20200110_T1",
                                       "LC08_20200111_T1"}

    def test_ndvi_style_expression(self, mas, archive):
        req = GeoTileRequest(
            collection=archive["root"],
            bands=["ratio = phot_veg / (phot_veg + bare_soil)"],
            bbox=TILE_BBOX, crs=EPSG3857, width=64, height=64,
            start_time=t(10), end_time=t(10))
        res = TilePipeline(mas).process(req)
        d = res.data["ratio"]
        ok = res.valid["ratio"]
        assert ok.any()
        # fc fixtures: bare_soil = phot_veg * 0.5 -> ratio = 1/1.5
        np.testing.assert_allclose(d[ok], 2.0 / 3.0, atol=1e-5)

    def test_empty_when_no_time_match(self, mas, archive):
        req = GeoTileRequest(
            collection=archive["root"], bands=["phot_veg"],
            bbox=TILE_BBOX, crs=EPSG3857, width=32, height=32,
            start_time=t(25), end_time=t(26))
        res = TilePipeline(mas).process(req)
        assert not res.valid["phot_veg"].any()

    def test_empty_when_disjoint(self, mas, archive):
        far = transform_bbox(BBox(10, 10, 11, 11), EPSG4326, EPSG3857)
        req = GeoTileRequest(
            collection=archive["root"], bands=["phot_veg"],
            bbox=far, crs=EPSG3857, width=32, height=32,
            start_time=t(10), end_time=t(10))
        res = TilePipeline(mas).process(req)
        assert not res.valid["phot_veg"].any()

    def test_bilinear_smooths(self, mas, archive):
        req = GeoTileRequest(
            collection=archive["root"], bands=["phot_veg"],
            bbox=TILE_BBOX, crs=EPSG3857, width=64, height=64,
            start_time=t(10), end_time=t(10), resample="bilinear")
        res = TilePipeline(mas).process(req)
        assert res.valid["phot_veg"].any()


class TestDrill:
    WKT = "POLYGON((148.0 -35.8,148.4 -35.8,148.4 -35.4,148.0 -35.4,148.0 -35.8))"

    def test_exact_drill_netcdf(self, mas, archive):
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt=self.WKT, start_time=t(9), end_time=t(13),
            approx=False)
        res = DrillPipeline(mas).process(req)
        assert len(res.dates) == 3
        vs = res.values["phot_veg"]
        assert all(0 <= v <= 100 for v in vs)
        assert all(c > 0 for c in res.counts["phot_veg"])

    def test_approx_uses_crawler_stats(self, mas, archive):
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt=self.WKT, start_time=t(9), end_time=t(13),
            approx=True)
        res = DrillPipeline(mas).process(req)
        assert len(res.dates) == 3
        # approx means are whole-file means (45-55 for uniform 0..100)
        assert all(30 <= v <= 70 for v in res.values["phot_veg"])

    def test_deciles(self, mas, archive):
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt=self.WKT, start_time=t(10), end_time=t(10),
            approx=False, deciles=3)
        res = DrillPipeline(mas).process(req)
        for d in range(1, 4):
            ns = f"phot_veg_d{d}"
            assert ns in res.values
        # quartile ordering
        assert res.values["phot_veg_d1"][0] <= res.values["phot_veg_d2"][0] \
            <= res.values["phot_veg_d3"][0]

    def test_device_stack_cache_parity(self, mas, archive, monkeypatch):
        """The device-resident stack path (drill_cache + window_gather)
        must match host-read reductions exactly."""
        from gsky_tpu.pipeline.drill_cache import default_drill_cache

        monkeypatch.delenv("GSKY_DRILL_CACHE", raising=False)
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt=self.WKT, start_time=t(9), end_time=t(13),
            approx=False, deciles=3)
        dp = DrillPipeline(mas)
        dp.process(req)                        # primes the async upload
        assert default_drill_cache.wait_idle(60)
        from gsky_tpu.pipeline.executor import default_executor
        legs0 = dict(default_executor.bucket_stats)
        res_dev = dp.process(req)              # cached-stack path
        # guard against a vacuous pass: the fixture's stack must be
        # device-resident (earlier tests may have already cached it)
        assert any(k[0].startswith(archive["root"])
                   for k in default_drill_cache._order)
        monkeypatch.setenv("GSKY_DRILL_CACHE", "0")
        res_host = dp.process(req)             # host-read path
        # the leg that answered is counted where /debug reads it, and a
        # device-path failure cannot hide behind the host reads

        def grew(leg):
            return default_executor.bucket_stats.get(leg, 0) \
                - legs0.get(leg, 0)
        assert grew("drill_device") >= 1 and grew("drill_host") >= 1
        assert grew("drill_device_error") == 0
        assert res_dev.dates == res_host.dates
        for ns in res_host.values:
            np.testing.assert_allclose(
                res_dev.values[ns], res_host.values[ns], rtol=1e-6,
                err_msg=ns)
            assert res_dev.counts[ns] == res_host.counts[ns], ns

    def test_device_stack_cache_edge_polygon(self, mas, archive,
                                             monkeypatch):
        """Window clamped at the raster edge: the shifted mask must keep
        pixel identity (parity with host reads)."""
        # fixture NetCDF grid spans lon 147.99-148.24, lat -35.37..-35.19;
        # this polygon pokes past the north-west corner
        wkt = ("POLYGON((147.9 -35.25,148.05 -35.25,148.05 -35.1,"
               "147.9 -35.1,147.9 -35.25))")
        from gsky_tpu.pipeline.drill_cache import default_drill_cache

        monkeypatch.delenv("GSKY_DRILL_CACHE", raising=False)
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt=wkt, start_time=t(9), end_time=t(13),
            approx=False)
        dp = DrillPipeline(mas)
        dp.process(req)                        # primes the async upload
        assert default_drill_cache.wait_idle(60)
        res_dev = dp.process(req)
        assert default_drill_cache._order  # device path engaged
        monkeypatch.setenv("GSKY_DRILL_CACHE", "0")
        res_host = dp.process(req)
        assert res_dev.dates == res_host.dates
        assert res_dev.dates, "edge polygon should still hit data"
        for ns in res_host.values:
            np.testing.assert_allclose(
                res_dev.values[ns], res_host.values[ns], rtol=1e-6)
            assert res_dev.counts[ns] == res_host.counts[ns]

    def test_drill_stack_cache_async_miss_then_hit(self, archive):
        """get_async: first call misses (returns None, schedules a
        background upload); after wait_idle the stack is resident."""
        from gsky_tpu.pipeline.drill_cache import DrillStackCache

        nc = None
        for fn in os.listdir(archive["root"]):
            if fn.endswith(".nc"):
                nc = os.path.join(archive["root"], fn)
                break
        assert nc
        cache = DrillStackCache()
        assert cache.get_async(nc, True, "phot_veg", 1, None) is None
        assert cache.wait_idle(30)
        hit = cache.get_async(nc, True, "phot_veg", 1, None)
        assert hit is not None and hit.shape[0] >= 1
        assert cache.hits == 1 and cache.misses == 1
        cache.clear()
        assert cache.get_async(nc, True, "phot_veg", 1, None) is None

    def test_drill_stack_cache_reuse_and_eviction(self, archive):
        from gsky_tpu.pipeline.drill_cache import DrillStackCache

        nc = None
        for fn in os.listdir(archive["root"]):
            if fn.endswith(".nc"):
                nc = os.path.join(archive["root"], fn)
                break
        assert nc
        cache = DrillStackCache()
        s1 = cache.get(nc, True, "phot_veg", 1, None)
        assert s1 is not None and s1.shape[0] >= 1
        assert cache.get(nc, True, "phot_veg", 1, None).serial == s1.serial
        # over-budget stack -> uncacheable, negative entry sticks
        tiny = DrillStackCache(max_item_bytes=16)
        assert tiny.get(nc, True, "phot_veg", 1, None) is None
        assert tiny.get(nc, True, "phot_veg", 1, None) is None
        # byte-budget eviction keeps the newest
        small = DrillStackCache(max_bytes=s1.nbytes + 1)
        a = small.get(nc, True, "phot_veg", 1, None)
        b = small.get(nc, True, "bare_soil", 1, None)
        assert a is not None and b is not None
        c = small.get(nc, True, "phot_veg", 1, None)
        assert c is not None and c.serial != a.serial  # was evicted

    # -- one request's co-gridded files, drilled as one group ----------

    GROUP_WKT = ("POLYGON((148.3 -35.9,149.0 -35.7,149.1 -35.2,148.6 -35.0,"
                 "148.2 -35.3,148.3 -35.9))")
    BANDS = ["phot_veg", "nphot_veg", "bare_soil"]

    @pytest.fixture(scope="class")
    def stacks(self, tmp_path_factory):
        """Three one-variable NetCDF stacks on one 96 x 96 grid and a
        fourth on a grid of its own, five steps each, crawled into a
        store of their own."""
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.index.store import MASStore
        from gsky_tpu.io.netcdf import write_netcdf3

        root = str(tmp_path_factory.mktemp("stacks"))
        rng = np.random.default_rng(7)
        times = np.array([t(d) for d in (10, 11, 12, 13, 14)])
        store = MASStore()
        grids = {"phot_veg": 96, "nphot_veg": 96, "bare_soil": 96,
                 "other": 64}
        for name, n in grids.items():
            data = rng.uniform(0, 100, (5, n, n)).astype(np.float32)
            data[:, : n // 8, : n // 8] = -1.0
            path = os.path.join(root, f"{name}.nc")
            write_netcdf3(path, {name: data},
                          np.linspace(148.0, 149.5, n),
                          np.linspace(-34.8, -36.2, n), EPSG4326,
                          times=times, nodata=-1.0)
            rec = extract(path)
            assert not rec.get("error"), rec
            store.ingest(rec)
        return {"root": root, "mas": MASClient(store)}

    def _group_req(self, stacks, bands=None, **kw):
        kw.setdefault("start_time", t(10))
        kw.setdefault("end_time", t(14))
        return GeoDrillRequest(collection=stacks["root"],
                               bands=bands or self.BANDS,
                               geometry_wkt=self.GROUP_WKT, approx=False,
                               **kw)

    @staticmethod
    def _file_by_file(dp, req):
        """The answer as it was made before files were grouped: one
        `_drill_file` per dataset, in the index's order."""
        from collections import defaultdict

        from gsky_tpu.geo import geometry as geom
        from gsky_tpu.pipeline import drill as DR
        acc = defaultdict(list)
        g4326 = geom.from_wkt(req.geometry_wkt)
        for ds in dp.index(req):
            sel = DR._selected_times(ds, req)
            stats = DR._drill_file(ds, sel, g4326, req)
            if stats is None:
                continue
            values, counts, deciles = stats
            for k, ti in enumerate(sel):
                date = ds.timestamps[ti]
                acc[(ds.namespace, date)].append(
                    (float(values[k]), int(counts[k])))
                for d in range(req.deciles):
                    acc[(f"{ds.namespace}_d{d + 1}", date)].append(
                        (float(deciles[k, d]), 1))
        return DR._merge(acc, req)

    @staticmethod
    def _same_bits(got, want):
        assert got.dates == want.dates and got.dates
        assert got.raw_namespaces == want.raw_namespaces
        assert sorted(got.values) == sorted(want.values)
        for ns in want.values:
            a = np.asarray(got.values[ns], np.float64)
            b = np.asarray(want.values[ns], np.float64)
            assert a.tobytes() == b.tobytes(), ns
            assert got.counts[ns] == want.counts[ns], ns

    @staticmethod
    def _legs():
        from gsky_tpu.pipeline.executor import default_executor
        return dict(default_executor.bucket_stats)

    @staticmethod
    def _grew(before, leg):
        from gsky_tpu.pipeline.executor import default_executor
        return default_executor.bucket_stats.get(leg, 0) - before.get(leg, 0)

    def test_group_one_grid_one_window_no_file_opened(self, stacks,
                                                      monkeypatch):
        """(a) three resident stacks on one grid: one rasterised mask,
        no header opened, `windows` 1 and `files` 3, and the answer is
        bit for bit the file-by-file one."""
        from gsky_tpu import obs
        from gsky_tpu.geo import geometry as geom
        from gsky_tpu.pipeline import drill as DR
        from gsky_tpu.server.metrics import MetricsLogger

        monkeypatch.setenv("GSKY_DRILL_CACHE", "sync")
        dp = DrillPipeline(stacks["mas"])
        req = self._group_req(stacks)
        want = self._file_by_file(dp, req)      # uploads the stacks too
        burns, opened = [], []
        rasterize = geom.rasterize
        monkeypatch.setattr(
            geom, "rasterize",
            lambda *a, **k: burns.append(1) or rasterize(*a, **k))
        monkeypatch.setattr(
            DR, "NetCDF", lambda path: opened.append(path) or 1 / 0)
        legs0 = self._legs()
        with obs.start_trace("test") as trace:
            got = dp.process(req)
        assert len(burns) == 1 and opened == []
        assert self._grew(legs0, "drill_device") == 3
        assert self._grew(legs0, "drill_host") == 0
        self._same_bits(got, want)
        assert len(got.dates) == 5 and sorted(got.values) == sorted(self.BANDS)
        # the counters /debug reads, folded as the server folds them
        assert trace.count("drill.prepare") == 1
        assert trace.total("files") == 3
        m = MetricsLogger()
        m.record_drill(trace.seconds_by_name(), trace.age_s(),
                       files=trace.total("files"),
                       windows=trace.count("drill.prepare"))
        stages = m.summary()["drill_stages"]
        assert stages["windows"] == 1 and stages["files"] == 3
        assert stages["last"]["windows"] == 1
        assert stages["device_s"] > 0 and stages["host_read_s"] == 0

    def test_group_two_grids_two_windows(self, stacks, monkeypatch):
        """(b) a second grid in the same request gets a window of its
        own; both answers as file by file."""
        from gsky_tpu import obs

        monkeypatch.setenv("GSKY_DRILL_CACHE", "sync")
        dp = DrillPipeline(stacks["mas"])
        req = self._group_req(stacks, bands=self.BANDS + ["other"])
        want = self._file_by_file(dp, req)
        with obs.start_trace("test") as trace:
            got = dp.process(req)
        assert trace.count("drill.prepare") == 2
        assert trace.total("files") == 4
        self._same_bits(got, want)
        assert "other" in got.values

    def test_group_one_stack_not_resident(self, stacks, monkeypatch):
        """(c) one of three stacks is not on the device: that file is
        answered by host reads, the others by the device."""
        from gsky_tpu import obs
        from gsky_tpu.pipeline import drill_cache as DC

        monkeypatch.setenv("GSKY_DRILL_CACHE", "sync")
        dp = DrillPipeline(stacks["mas"])
        req = self._group_req(stacks)
        want = self._file_by_file(dp, req)
        get = DC.default_drill_cache.get
        monkeypatch.setattr(
            DC.default_drill_cache, "get",
            lambda path, *a: None if path.endswith("nphot_veg.nc")
            else get(path, *a))
        legs0 = self._legs()
        with obs.start_trace("test") as trace:
            got = dp.process(req)
        assert self._grew(legs0, "drill_device") == 2
        assert self._grew(legs0, "drill_host") == 1
        assert self._grew(legs0, "drill_device_error") == 0
        assert trace.count("drill.host_read") == 1
        assert trace.total("files") == 3
        # host reads and the device agree to rounding, not to the bit:
        # the file-by-file answer takes the same legs file for file
        ref = self._file_by_file(dp, req)
        self._same_bits(got, ref)
        for ns in want.values:
            np.testing.assert_allclose(got.values[ns], want.values[ns],
                                       rtol=1e-6, err_msg=ns)
            assert got.counts[ns] == want.counts[ns]

    def test_group_device_error_at_collect_stays_with_its_file(
            self, stacks, monkeypatch):
        """(d) one file's result cannot be read back: it is counted,
        that file is answered from host reads, the others keep their
        device values."""
        from gsky_tpu.pipeline import drill as DR

        monkeypatch.setenv("GSKY_DRILL_CACHE", "sync")
        dp = DrillPipeline(stacks["mas"])
        req = self._group_req(stacks)
        want = self._file_by_file(dp, req)

        class Unreadable:
            def __array__(self, *a, **k):
                raise RuntimeError("injected: device result lost")

        enqueue, calls = DR._stats_enqueue, []

        def second_is_lost(dataf, validf, r):
            kind, a, c, dec = enqueue(dataf, validf, r)
            calls.append(1)
            return (kind, Unreadable(), c, dec) if len(calls) == 2 \
                else (kind, a, c, dec)

        monkeypatch.setattr(DR, "_stats_enqueue", second_is_lost)
        legs0 = self._legs()
        got = dp.process(req)
        assert self._grew(legs0, "drill_device_error") == 1
        assert self._grew(legs0, "drill_host") == 1
        assert self._grew(legs0, "drill_device") == 2
        assert got.dates == want.dates
        lost = [d.namespace for d in dp.index(req)][1]
        for ns in want.values:
            if ns != lost:      # untouched, to the bit
                assert np.asarray(got.values[ns]).tobytes() \
                    == np.asarray(want.values[ns]).tobytes(), ns
            np.testing.assert_allclose(got.values[ns], want.values[ns],
                                       rtol=1e-6, err_msg=ns)
            assert got.counts[ns] == want.counts[ns]

    def test_group_reads_back_early_past_its_bytes_in_flight(
            self, stacks, monkeypatch):
        """Windows too large to keep three enqueued are read back as
        they go; the answer does not change."""
        from gsky_tpu import obs
        from gsky_tpu.pipeline import drill as DR

        monkeypatch.setenv("GSKY_DRILL_CACHE", "sync")
        dp = DrillPipeline(stacks["mas"])
        req = self._group_req(stacks)
        with obs.start_trace("test") as trace:
            want = dp.process(req)
        readbacks = [sp["attrs"]["queued"] for sp in trace.span_dicts()
                     if "queued" in sp.get("attrs", {})]
        assert readbacks == [3]
        monkeypatch.setattr(DR, "_GATHER_BYTES_IN_FLIGHT", 2 * 8 * 64 * 64 * 4)
        with obs.start_trace("test") as trace:
            got = dp.process(req)
        readbacks = [sp["attrs"]["queued"] for sp in trace.span_dicts()
                     if "queued" in sp.get("attrs", {})]
        assert readbacks == [2, 1]
        assert trace.count("drill.prepare") == 1
        self._same_bits(got, want)

    @pytest.mark.parametrize("kw, env", [
        (dict(deciles=3), {}), (dict(band_strides=2), {}),
        (dict(band_strides=3, deciles=2), {}), (dict(pixel_count=True), {}),
        # the Pallas leg leaves sums on the device, divided after the
        # readback; the wave leg hands back host arrays
        (dict(), {"GSKY_PALLAS": "interpret", "GSKY_WAVES": "0"}),
        (dict(deciles=2), {"GSKY_PALLAS": "interpret"})])
    def test_group_deciles_and_strides_as_file_by_file(self, stacks,
                                                       monkeypatch, kw, env):
        """(e) deciles, strided reads with interpolation and pixel
        counts go through the group as they go file by file, whichever
        leg reduces."""
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        monkeypatch.setenv("GSKY_DRILL_CACHE", "sync")
        dp = DrillPipeline(stacks["mas"])
        req = self._group_req(stacks, **kw)
        want = self._file_by_file(dp, req)
        legs0 = self._legs()
        got = dp.process(req)
        assert self._grew(legs0, "drill_device") == 3
        self._same_bits(got, want)
        if kw.get("deciles"):
            assert "bare_soil_d1" in got.values

    def test_drill_expression(self, mas, archive):
        req = GeoDrillRequest(
            collection=archive["root"],
            bands=["total = phot_veg + bare_soil"],
            geometry_wkt=self.WKT, start_time=t(9), end_time=t(13),
            approx=False)
        res = DrillPipeline(mas).process(req)
        assert "total" in res.values
        v = res.values["total"][0]
        assert not math.isnan(v)

    def test_csv(self, mas, archive):
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt=self.WKT, start_time=t(9), end_time=t(13),
            approx=True)
        res = DrillPipeline(mas).process(req)
        csv = drill_csv(res, ["phot_veg"])
        lines = csv.split("\n")
        assert len(lines) == 3
        assert lines[0].startswith("2020-01-10,")

    def test_point_drill(self, mas, archive):
        req = GeoDrillRequest(
            collection=archive["root"], bands=["phot_veg"],
            geometry_wkt="POINT(148.2 -35.6)", start_time=t(10),
            end_time=t(10), approx=False)
        res = DrillPipeline(mas).process(req)
        assert res.dates
        assert res.counts["phot_veg"][0] == 1


class TestExtent:
    def test_suggests_native_resolution(self, mas, archive):
        req = GeoTileRequest(
            collection=archive["root"], bands=["LC08_20200110_T1"],
            bbox=TILE_BBOX, crs=EPSG3857, width=0, height=0,
            start_time=t(9), end_time=t(13))
        w, h = compute_reprojection_extent(mas, req)
        # 30m pixels over a ~28km tile -> several hundred pixels
        assert 300 <= w <= 2000
        assert 300 <= h <= 2000


class TestFeatureInfo:
    def test_click_value(self, mas, archive):
        req = GeoTileRequest(
            collection=archive["root"], bands=["phot_veg"],
            bbox=TILE_BBOX, crs=EPSG3857, width=64, height=64,
            start_time=t(10), end_time=t(10))
        fi = get_feature_info(TilePipeline(mas), req, 32, 32)
        assert fi.values["phot_veg"] is not None
        assert 0 <= fi.values["phot_veg"] <= 100
        assert any(p.endswith(".nc") for p in fi.files)
        assert "2020-01-10T00:00:00.000Z" in fi.dates

    def test_out_of_range(self, mas, archive):
        req = GeoTileRequest(
            collection=archive["root"], bands=["phot_veg"],
            bbox=TILE_BBOX, crs=EPSG3857, width=64, height=64)
        with pytest.raises(ValueError):
            get_feature_info(TilePipeline(mas), req, 100, 5)


class TestReviewRegressions:
    def test_drill_fast_path_untimed_dataset(self, mas, archive):
        """Untimed dataset with crawler stats must not crash the approx
        fast path."""
        from gsky_tpu.index.client import Dataset
        from gsky_tpu.pipeline.drill import DrillPipeline

        class FakeMAS:
            def intersects(self, gpath, **kw):
                return [Dataset(
                    file_path="/undated.tif", ds_name="/undated.tif",
                    namespace="v", array_type="Int16", srs="EPSG:4326",
                    geo_transform=[0, 1, 0, 0, 0, -1], timestamps=[],
                    timestamps_iso=[],
                    polygon="POLYGON((0 0,1 0,1 1,0 1,0 0))", nodata=-1.0,
                    axes=[], means=[42.0], sample_counts=[10])]

        req = GeoDrillRequest(collection="/", bands=["v"],
                              geometry_wkt="POLYGON((0 0,1 0,1 1,0 1,0 0))",
                              approx=True)
        res = DrillPipeline(FakeMAS()).process(req)
        assert res.values["v"] == [42.0]

    def test_concurrent_store_reads(self, archive):
        """:memory: store serialises concurrent access."""
        import threading
        errs = []

        def q():
            try:
                for _ in range(20):
                    archive["store"].timestamps("/")
            except Exception as e:
                errs.append(e)
        ts = [threading.Thread(target=q) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errs


class TestFusedBandsRender:
    def test_matches_modular_path(self, archive):
        """render_bands_byte (one fused dispatch) must equal the modular
        process() + per-band scale_to_byte path for plain RGB styles."""
        import jax.numpy as jnp
        from gsky_tpu.ops.scale import scale_to_byte

        mas = MASClient(archive["store"])
        pipe = TilePipeline(mas)
        req = GeoTileRequest(
            collection=archive["root"],
            bands=["phot_veg", "bare_soil"],
            bbox=TILE_BBOX, crs=EPSG3857, width=128, height=128,
            start_time=1578000000.0 - 90 * 86400,
            end_time=1578700000.0)
        out = pipe.render_bands_byte(req, auto=True)
        assert out is not None
        out = np.asarray(out)
        assert out.shape == (2, 128, 128)

        res = pipe.process(req)
        for i, ns in enumerate(["phot_veg", "bare_soil"]):
            want = np.asarray(scale_to_byte(
                jnp.asarray(res.data[ns]), jnp.asarray(res.valid[ns]),
                auto=True))
            mism = np.mean(out[i] != want)
            # approx-transform nearest flips allowed on boundary pixels
            assert mism < 0.02, f"{ns}: {mism:.1%} differ"

    def test_rejects_expressions(self, archive):
        pipe = TilePipeline(MASClient(archive["store"]))
        req = GeoTileRequest(
            collection=archive["root"],
            bands=["total = phot_veg + bare_soil"],
            bbox=TILE_BBOX, crs=EPSG3857, width=64, height=64)
        assert pipe.render_bands_byte(req) is None


class TestPackedRgbRender:
    @pytest.fixture(scope="class")
    def rgb_archive(self, tmp_path_factory):
        """One 3-band RGB GeoTIFF, crawler-indexed (the Sentinel-2
        true-colour shape)."""
        from gsky_tpu.index import MASStore
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io import write_geotiff

        root = str(tmp_path_factory.mktemp("rgb"))
        utm = parse_crs("EPSG:32755")
        rng = np.random.default_rng(11)
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        rgb = rng.uniform(200, 3000, (3, 512, 512)).astype(np.int16)
        rgb[:, :64, :64] = -999
        p = os.path.join(root, "S2_20200110_T1.tif")
        write_geotiff(p, rgb, gt, utm, nodata=-999)
        store = MASStore()
        rec = extract(p)
        assert not rec.get("error"), rec
        store.ingest(rec)
        return {"store": store, "root": root, "utm": utm}

    def _req(self, rgb_archive, resample, order=(1, 2, 3)):
        utm = rgb_archive["utm"]
        core = BBox(592000.0, 6098000.0, 598000.0, 6102000.0)
        merc = transform_bbox(transform_bbox(core, utm, EPSG4326),
                              EPSG4326, EPSG3857)
        return GeoTileRequest(
            collection=rgb_archive["root"],
            bands=[f"S2_20200110_T1_b{k}" for k in order],
            bbox=merc, crs=EPSG3857, width=128, height=128,
            start_time=t(9), end_time=t(11), resample=resample)

    @pytest.mark.parametrize("resample", ["near", "bilinear", "cubic"])
    def test_matches_per_band_path(self, rgb_archive, resample):
        """The channel-packed RGBA kernel must byte-match the per-band
        fused path plus the host interleave/alpha rules of encode_png."""
        pipe = TilePipeline(MASClient(rgb_archive["store"]))
        req = self._req(rgb_archive, resample)
        rgba = pipe.render_rgba_byte(req, auto=True)
        assert rgba is not None
        rgba = np.asarray(rgba)
        assert rgba.shape == (128, 128, 4)

        planes = np.asarray(pipe.render_bands_byte(req, auto=True))
        for i in range(3):
            if resample == "near":
                np.testing.assert_array_equal(rgba[..., i], planes[i])
            else:
                # interpolated taps: the two XLA programs reassociate
                # f32 sums differently; allow rare one-level flips
                mism = rgba[..., i].astype(int) - planes[i].astype(int)
                frac = np.mean(mism != 0)
                assert frac < 0.005, f"band {i}: {frac:.2%} differ"
                if frac:
                    assert np.abs(mism[mism != 0]).max() <= 1
        # alpha rule self-consistency: 0 exactly where all three
        # channels carry the nodata byte
        nodata = np.all(rgba[..., :3] == 255, axis=-1)
        np.testing.assert_array_equal(rgba[..., 3],
                                      np.where(nodata, 0, 255))

    def test_band_order_respected(self, rgb_archive):
        """Expression order (B, G, R) must permute channels."""
        pipe = TilePipeline(MASClient(rgb_archive["store"]))
        fwd = np.asarray(pipe.render_rgba_byte(
            self._req(rgb_archive, "near"), auto=True))
        rev = np.asarray(pipe.render_rgba_byte(
            self._req(rgb_archive, "near", order=(3, 2, 1)), auto=True))
        np.testing.assert_array_equal(fwd[..., 0], rev[..., 2])
        np.testing.assert_array_equal(fwd[..., 2], rev[..., 0])

    def test_multi_granule_falls_back(self, tmp_path):
        """Granule sets beyond the single-scene shape must decline the
        packed path — and the ladder must land them on the per-band
        planes kernel in the same index pass."""
        from gsky_tpu.index import MASStore
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io.netcdf import write_netcdf3

        root = str(tmp_path)
        rng = np.random.default_rng(12)
        H = W = 96
        xs = 148.0 + (np.arange(W) + 0.5) * 0.002
        ys = -35.0 - (np.arange(H) + 0.5) * 0.002
        times = np.asarray([t(10), t(12)])
        p = os.path.join(root, "rgb_stack.nc")
        write_netcdf3(
            p, {v: rng.uniform(0, 1, (2, H, W)).astype(np.float32)
                for v in ("red", "green", "blue")},
            xs, ys, EPSG4326, times, nodata=-9.0)
        store = MASStore()
        store.ingest(extract(p))
        pipe = TilePipeline(MASClient(store))
        merc = transform_bbox(BBox(148.02, -35.15, 148.15, -35.02),
                              EPSG4326, EPSG3857)
        req = GeoTileRequest(
            collection=root, bands=["red", "green", "blue"],
            bbox=merc, crs=EPSG3857, width=64, height=64,
            start_time=t(9), end_time=t(13))
        # six granules (two timestamps x three vars) in the window
        assert pipe.render_rgba_byte(req) is None
        made = pipe.render_rgb_auto(req, auto=True)
        assert made is not None and made[0] == "planes"
        assert np.asarray(made[1]).shape == (3, 64, 64)

    def test_ladder_picks_rgba(self, rgb_archive):
        made = TilePipeline(MASClient(rgb_archive["store"])) \
            .render_rgb_auto(self._req(rgb_archive, "near"), auto=True)
        assert made is not None and made[0] == "rgba"
        assert np.asarray(made[1]).shape == (128, 128, 4)


class TestTimeSplitter:
    def test_year_step_windows(self):
        """TimeSplitter parity (`processor/date_splitter.go:19-31`)."""
        import datetime as dt
        from gsky_tpu.pipeline.drill import split_by_years
        from gsky_tpu.pipeline.types import GeoDrillRequest
        t0 = dt.datetime(2015, 3, 1, tzinfo=dt.timezone.utc).timestamp()
        t1 = dt.datetime(2020, 1, 1, tzinfo=dt.timezone.utc).timestamp()
        req = GeoDrillRequest(collection="/c", bands=["b"],
                              geometry_wkt="POINT(0 0)",
                              start_time=t0, end_time=t1)
        parts = list(split_by_years(req, 2))
        assert len(parts) == 3
        assert parts[0].start_time == t0
        for a, b in zip(parts, parts[1:]):
            assert b.start_time == a.end_time
        # last window extends past end_time, as the reference's loop does
        assert parts[-1].end_time >= t1
        # other fields preserved
        assert all(p.collection == "/c" and p.bands == ["b"]
                   for p in parts)

    def test_no_step_passthrough(self):
        from gsky_tpu.pipeline.drill import split_by_years
        from gsky_tpu.pipeline.types import GeoDrillRequest
        req = GeoDrillRequest(collection="/c", bands=["b"],
                              geometry_wkt="POINT(0 0)",
                              start_time=0.0, end_time=1.0)
        assert list(split_by_years(req, 0)) == [req]

    def test_merge_results_concatenates_windows(self):
        from gsky_tpu.pipeline.drill import merge_results
        from gsky_tpu.pipeline.types import DrillResult
        a = DrillResult([1.0, 2.0], {"ndvi": [0.1, 0.2]},
                        {"ndvi": [5, 6]}, ["ndvi"])
        b = DrillResult([3.0], {"ndvi": [0.3]}, {"ndvi": [7]}, ["ndvi"])
        m = merge_results([b, a])
        assert m.dates == [1.0, 2.0, 3.0]
        assert m.values["ndvi"] == [0.1, 0.2, 0.3]
        assert m.counts["ndvi"] == [5, 6, 7]

    def test_process_split_runs_one_drill_per_window(self, monkeypatch):
        """serve_wps drives `process_split`, so a configured year_step
        must fan the drill out into windowed sub-requests."""
        import datetime as dt
        from gsky_tpu.pipeline.drill import DrillPipeline
        from gsky_tpu.pipeline.types import DrillResult, GeoDrillRequest
        t0 = dt.datetime(2015, 1, 1, tzinfo=dt.timezone.utc).timestamp()
        t1 = dt.datetime(2019, 1, 1, tzinfo=dt.timezone.utc).timestamp()
        req = GeoDrillRequest(collection="/c", bands=["b"],
                              geometry_wkt="POINT(0 0)",
                              start_time=t0, end_time=t1)
        seen = []

        def fake_process(self, r):
            seen.append((r.start_time, r.end_time))
            return DrillResult([r.start_time], {"b": [1.0]}, {"b": [1]},
                               ["b"])

        monkeypatch.setattr(DrillPipeline, "process", fake_process)
        res = DrillPipeline(mas=None).process_split(req, year_step=2)
        assert len(seen) == 2
        assert seen[0][1] == seen[1][0]
        assert len(res.dates) == 2


class TestCtrlGridValidation:
    """GDAL-approx-transformer parity: the control grid refines (step
    halves) when bilinear interpolation error exceeds 0.125 px
    (`worker/gdalprocess/warp.go:219`)."""

    def test_linear_transform_keeps_step(self):
        from gsky_tpu.geo.crs import parse_crs
        from gsky_tpu.geo.transform import GeoTransform
        from gsky_tpu.pipeline.executor import WarpExecutor
        ex = WarpExecutor()
        gt = GeoTransform.from_gdal((0.0, 100.0, 0.0, 0.0, 0.0, -100.0))
        crs = parse_crs("EPSG:3857")
        _, _, step = ex._ctrl_geo_coords(gt, crs, 256, 256, crs, 16)
        assert step == 16

    def test_nonlinear_transform_refines_step(self):
        import numpy as np
        from gsky_tpu.geo.transform import GeoTransform
        from gsky_tpu.pipeline.executor import WarpExecutor

        class BendyCRS:
            """Strongly nonlinear toy projection (quadratic in x)."""

            def transform_to(self, other, x, y, xp=np):
                return xp.asarray(x) ** 2 / 300.0, xp.asarray(y)

            def __hash__(self):
                return 42

            def __eq__(self, o):
                return isinstance(o, BendyCRS)

        ex = WarpExecutor()
        gt = GeoTransform.from_gdal((0.0, 1.0, 0.0, 0.0, 0.0, -1.0))
        _, _, step = ex._ctrl_geo_coords(gt, BendyCRS(), 256, 256,
                                         object(), 16)
        assert step < 16

    def test_scene_serials_are_unique(self):
        from gsky_tpu.geo.crs import parse_crs
        from gsky_tpu.geo.transform import GeoTransform
        from gsky_tpu.pipeline.scene_cache import DeviceScene
        import jax.numpy as jnp
        mk = lambda: DeviceScene(
            dev=jnp.zeros((4, 4)), height=4, width=4, nodata=0.0,
            gt=GeoTransform.from_gdal((0, 1, 0, 0, 0, -1)),
            crs=parse_crs("EPSG:4326"))
        a, b = mk(), mk()
        assert a.serial != b.serial


class TestMultiCRSMosaic:
    def test_fused_groups_match_window_path(self, tmp_path):
        """Granule sets spanning source CRSs (UTM zones) render through
        per-CRS scored dispatches + priority combine; result must match
        the decode-window fallback path."""
        from gsky_tpu.geo.crs import parse_crs
        from gsky_tpu.geo.transform import GeoTransform
        from gsky_tpu.index import MASStore
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io import write_geotiff

        rng = np.random.default_rng(3)
        store = MASStore()
        # zone 55 scene and zone 56 scene, overlapping near 150E
        # ~149.6E in zone 55 and ~149.7E in zone 56 at ~35.2S: the
        # scenes overlap near the zone boundary
        specs = [("EPSG:32755", 740000.0, "2020-01-10"),
                 ("EPSG:32756", 215000.0, "2020-01-11")]
        for srs, x0, date in specs:
            gt = GeoTransform(x0, 60.0, 0.0, 6105000.0, 0.0, -60.0)
            data = rng.uniform(200, 3000, (512, 512)).astype(np.int16)
            p = str(tmp_path / f"S_{date.replace('-', '')}.tif")
            write_geotiff(p, data, gt, parse_crs(srs), nodata=-999)
            store.ingest(extract(p))
        mas = MASClient(store)
        pipe = TilePipeline(mas)
        import datetime as dt
        t0 = dt.datetime(2020, 1, 9, tzinfo=dt.timezone.utc).timestamp()
        t1 = dt.datetime(2020, 1, 12, tzinfo=dt.timezone.utc).timestamp()
        from gsky_tpu.geo.transform import transform_bbox
        merc = transform_bbox(BBox(149.75, -35.45, 150.05, -35.25),
                              EPSG4326, EPSG3857)
        bands = [f"S_{d.replace('-', '')}" for _, _, d in specs]
        req = GeoTileRequest(collection=str(tmp_path), bands=bands,
                             bbox=merc, crs=EPSG3857,
                             width=256, height=256,
                             start_time=t0, end_time=t1)
        granules = pipe.index(req)
        assert len({g.srs for g in granules}) == 2

        fused = pipe.process(req)
        # force the decode-window fallback
        orig = pipe.executor.warp_mosaic_scenes
        pipe.executor.warp_mosaic_scenes = lambda *a, **k: None
        try:
            window = pipe.process(req)
        finally:
            pipe.executor.warp_mosaic_scenes = orig
        for ns in fused.namespaces:
            fv = np.asarray(fused.valid[ns])
            wv = np.asarray(window.valid[ns])
            assert fv.any()
            np.testing.assert_array_equal(fv, wv)
            fd = np.asarray(fused.data[ns])
            wd = np.asarray(window.data[ns])
            assert np.mean(fd != wd) < 0.02  # approx-transform flips




def dataclasses_replace_mask(req):
    """Clone a request with a mask spec that matches nothing, purely to
    push render() onto the modular (non-fused) route."""
    import dataclasses

    from gsky_tpu.pipeline.types import MaskSpec
    # value "0": bitwise AND with 0 excludes nothing, so the render
    # result must match the fused path exactly
    return dataclasses.replace(req, mask=MaskSpec(id="bt", value="0",
                                                  bit_tests=[]))


class TestGeolocWarp:
    """Curvilinear (geolocation-array) products end-to-end: crawler
    detection -> MAS geo_loc record -> ctrl-point inversion -> fused
    render (`worker/gdalprocess/warp.go:52-67`)."""

    GH, GW = 180, 240
    L0, B0 = 147.0, -34.0

    def _lonlat(self, ii, jj):
        # sheared curvilinear grid with an exact analytic inverse
        lon = self.L0 + 0.004 * jj + 0.0012 * ii
        lat = self.B0 - 0.003 * ii
        return lon, lat

    def _inv(self, lon, lat):
        i = (self.B0 - lat) / 0.003
        j = (lon - self.L0 - 0.0012 * i) / 0.004
        return i, j

    def _make(self, tmp_path):
        from gsky_tpu.io.netcdf import write_netcdf3

        ii, jj = np.mgrid[0:self.GH, 0:self.GW].astype(np.float64)
        lon, lat = self._lonlat(ii, jj)
        data = (1000 + ii * 3 + jj * 7).astype(np.float32)
        data[:6, :6] = -9999.0
        root = str(tmp_path / "glarch")
        os.makedirs(root, exist_ok=True)
        p = os.path.join(root, "swath_20200110.nc")
        # axis vars are index-valued; the 2-D lon/lat arrays carry the
        # real georeferencing (CF curvilinear layout)
        write_netcdf3(p, {"bt": data,
                          "lon": lon.astype(np.float64),
                          "lat": lat.astype(np.float64)},
                      np.arange(self.GW, dtype=np.float64),
                      np.arange(self.GH, dtype=np.float64),
                      EPSG4326, nodata=-9999.0)
        return root, p, data

    def test_crawler_detects_geoloc(self, tmp_path):
        from gsky_tpu.index.crawler import extract

        root, p, _ = self._make(tmp_path)
        rec = extract(p)
        assert not rec.get("error")
        md = [d for d in rec["geo_metadata"] if d["namespace"] == "bt"]
        assert len(md) == 1
        gl = md[0].get("geo_loc")
        assert gl and gl["x_var"] == "lon" and gl["y_var"] == "lat"
        # polygon spans the geoloc bbox, not the index axes
        assert "147" in md[0]["polygon"]
        # lon/lat must not crawl as raster namespaces themselves
        assert not any(d["namespace"] in ("lon", "lat")
                       for d in rec["geo_metadata"])

    def test_render_matches_analytic_inverse(self, tmp_path):
        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.pipeline import TilePipeline, GeoTileRequest

        root, p, data = self._make(tmp_path)
        store = MASStore()
        rec = extract(p)
        store.ingest(rec)
        # tile well inside the swath, EPSG:4326 dst
        bbox = BBox(147.35, -34.40, 147.75, -34.10)
        req = GeoTileRequest(collection=root, bands=["bt"], bbox=bbox,
                             crs=EPSG4326, width=128, height=128,
                             resample="near")
        pipe = TilePipeline(MASClient(store))
        grans = pipe.index(req)
        assert grans and grans[0].geo_loc
        res = pipe.process(req)
        got = np.asarray(res.data["bt"])
        vgot = np.asarray(res.valid["bt"])
        # exact expectation from the analytic inverse (nearest sample)
        gt = req.dst_gt()
        cc, rr = np.meshgrid(np.arange(128) + 0.5, np.arange(128) + 0.5)
        lon, lat = gt.pixel_to_geo(cc, rr)
        ei, ej = self._inv(lon, lat)
        # sample centres sit at integer grid indices: nearest = rint
        ein = np.rint(ei).astype(int)
        ejn = np.rint(ej).astype(int)
        inside = (ein >= 0) & (ein < self.GH) & (ejn >= 0) \
            & (ejn < self.GW)
        exp = np.where(inside, data[np.clip(ein, 0, self.GH - 1),
                                    np.clip(ejn, 0, self.GW - 1)], 0.0)
        expv = inside & (exp != -9999.0)
        assert vgot.sum() > 0.8 * 128 * 128
        # the ctrl-grid bilinear reconstruction may flip pixels exactly
        # on sample boundaries; demand near-total agreement
        frac_v = np.mean(vgot != expv)
        frac_d = np.mean(got[vgot & expv] != exp[vgot & expv])
        assert frac_v < 0.02, f"validity differs on {frac_v:.1%}"
        assert frac_d < 0.02, f"values differ on {frac_d:.1%}"

    def test_geoloc_grid_invert_accuracy(self):
        from gsky_tpu.geo.geoloc import GeolocGrid

        ii, jj = np.mgrid[0:self.GH, 0:self.GW].astype(np.float64)
        lon, lat = self._lonlat(ii, jj)
        grid = GeolocGrid(lon, lat)
        rng = np.random.default_rng(4)
        qi = rng.uniform(0, self.GH - 1, 400)
        qj = rng.uniform(0, self.GW - 1, 400)
        qlon, qlat = self._lonlat(qi, qj)
        col, row = grid.invert(qlon, qlat)
        np.testing.assert_allclose(row - 0.5, qi, atol=0.05)
        np.testing.assert_allclose(col - 0.5, qj, atol=0.05)


    def test_modular_path_renders_geoloc(self, tmp_path):
        """The mask-band/modular route must also serve curvilinear
        granules (scene-cache geoloc warp, not the affine decode)."""
        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.pipeline import TilePipeline, GeoTileRequest

        root, p, data = self._make(tmp_path)
        store = MASStore()
        store.ingest(extract(p))
        bbox = BBox(147.35, -34.40, 147.75, -34.10)
        req = GeoTileRequest(collection=root, bands=["bt"], bbox=bbox,
                             crs=EPSG4326, width=96, height=96,
                             resample="near")
        pipe = TilePipeline(MASClient(store))
        fused = pipe.process(req)
        # force the modular route (what a mask-band request takes)
        granules = pipe.index(req)
        modular = pipe.render(
            dataclasses_replace_mask(req), granules)
        np.testing.assert_array_equal(
            np.asarray(fused.valid["bt"]), np.asarray(modular.valid["bt"]))
        np.testing.assert_array_equal(
            np.asarray(fused.data["bt"]), np.asarray(modular.data["bt"]))

    def test_invert_across_antimeridian(self):
        from gsky_tpu.geo.geoloc import GeolocGrid

        ii, jj = np.mgrid[0:100, 0:150].astype(np.float64)
        lon = 179.0 + 0.02 * jj          # crosses +180 -> wraps
        lon = np.where(lon > 180.0, lon - 360.0, lon)
        lat = -10.0 - 0.02 * ii
        grid = GeolocGrid(lon, lat)
        qi = np.array([10.0, 50.0, 90.0])
        qj = np.array([20.0, 75.0, 140.0])
        qlon = 179.0 + 0.02 * qj
        qlon = np.where(qlon > 180.0, qlon - 360.0, qlon)
        qlat = -10.0 - 0.02 * qi
        col, row = grid.invert(qlon, qlat)
        np.testing.assert_allclose(row - 0.5, qi, atol=0.05)
        np.testing.assert_allclose(col - 0.5, qj, atol=0.05)

    def test_crawl_pure_swath_without_axis_vars(self, tmp_path):
        """A genuine swath file has 2-D lon/lat and NO 1-D coordinate
        variables; extraction must not abort on the missing affine."""
        h5py = pytest.importorskip("h5py")
        from gsky_tpu.index.crawler import extract

        p = str(tmp_path / "pure_swath_20200110.nc")
        ii, jj = np.mgrid[0:80, 0:120].astype(np.float64)
        with h5py.File(p, "w") as f:
            f.create_dataset("lon", data=150.0 + 0.01 * jj + 0.002 * ii)
            f.create_dataset("lat", data=-20.0 - 0.01 * ii)
            d = f.create_dataset(
                "rad", data=(ii + jj).astype(np.float32))
            d.attrs["_FillValue"] = np.float32(-9999.0)
        rec = extract(p)
        assert not rec.get("error"), rec
        md = [d for d in rec["geo_metadata"] if d["namespace"] == "rad"]
        assert md and md[0].get("geo_loc")
        assert md[0]["geo_loc"]["x_var"] == "lon"


class TestDrillPolygonTiling:
    """Large-polygon drill tiling (`drill_indexer.go:115-137` +
    getTiledGeometries): tiled sub-geometries must merge to the same
    statistics as one whole-polygon drill."""

    def test_clip_bbox(self):
        from gsky_tpu.geo import geometry as geom
        from gsky_tpu.geo.transform import BBox

        g = geom.from_wkt(
            "POLYGON((0 0,10 0,10 10,0 10,0 0))")
        c = g.clip_bbox(BBox(5, 5, 15, 15))
        assert not c.is_empty
        b = c.bbox()
        assert (b.xmin, b.ymin, b.xmax, b.ymax) == (5, 5, 10, 10)
        assert abs(c.area() - 25.0) < 1e-9
        assert g.clip_bbox(BBox(20, 20, 30, 30)).is_empty

    def test_tiled_geometries_cover(self):
        from gsky_tpu.pipeline.drill import tiled_geometries
        from gsky_tpu.geo import geometry as geom

        wkt = ("POLYGON((148.0 -35.8,148.4 -35.8,148.4 -35.4,"
               "148.0 -35.4,148.0 -35.8))")
        tiles = tiled_geometries(wkt, 0.15, 0.15)
        assert len(tiles) == 9   # 3x3 grid over a 0.4-degree square
        total = sum(geom.from_wkt(t).area() for t in tiles)
        assert abs(total - geom.from_wkt(wkt).area()) < 1e-9
        # disabled / point / degenerate pass through whole
        assert tiled_geometries(wkt, 0.0, 0.0) == [wkt]
        assert tiled_geometries("POINT(1 2)", 0.1, 0.1) == ["POINT(1 2)"]

    def test_no_sliver_tiles_on_even_division(self):
        from gsky_tpu.pipeline.drill import tiled_geometries

        wkt = "POLYGON((0 0,0.3 0,0.3 0.3,0 0.3,0 0))"
        # 0.3/0.05 accumulates to 0.29999... with float stepping, which
        # used to emit a sliver row+column re-burning the edge pixels
        assert len(tiled_geometries(wkt, 0.05, 0.05)) == 36

    def test_tiled_drill_matches_whole(self, mas, archive):
        wkt = TestDrill.WKT
        base = dict(collection=archive["root"], bands=["phot_veg"],
                    geometry_wkt=wkt, start_time=t(9), end_time=t(13),
                    approx=False)
        dp = DrillPipeline(mas)
        whole = dp.process(GeoDrillRequest(**base))
        tiled = dp.process(GeoDrillRequest(
            **base, index_tile_x_size=0.15, index_tile_y_size=0.15))
        assert tiled.dates == whole.dates
        for ns in whole.values:
            # ALL_TOUCHED burns count tile-boundary pixels in both
            # adjacent tiles (the reference's tiled geometries feed the
            # same ALL_TOUCHED rasterize, so it shares this property) —
            # statistics agree to boundary-pixel weight, not bitwise
            np.testing.assert_allclose(tiled.values[ns],
                                       whole.values[ns], rtol=0.02)
            # the fixture polygon is tiny (~100 px across), so the
            # boundary band is a large fraction; at the continent scale
            # the feature targets it is negligible
            for tc, wc in zip(tiled.counts[ns], whole.counts[ns]):
                assert wc <= tc <= wc * 1.25, (tc, wc)


class TestGeolocDrill:
    """Polygon drill over a curvilinear swath: membership comes from a
    containment test on the geolocation arrays, not an affine burn."""

    def test_drill_matches_analytic(self, tmp_path, monkeypatch):
        from gsky_tpu.geo import geometry as geom
        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io.netcdf import write_netcdf3

        GH, GW, T = 90, 120, 4
        ii, jj = np.mgrid[0:GH, 0:GW].astype(np.float64)
        lon = 147.0 + 0.004 * jj + 0.0012 * ii
        lat = -34.0 - 0.003 * ii
        rng = np.random.default_rng(2)
        data = rng.uniform(10, 20, (T, GH, GW)).astype(np.float32)
        root = str(tmp_path / "gldrill")
        os.makedirs(root)
        p = os.path.join(root, "swath.nc")
        t0 = dt.datetime(2020, 1, 1,
                         tzinfo=dt.timezone.utc).timestamp()
        times = t0 + np.arange(T) * 86400.0
        write_netcdf3(p, {"bt": data, "lon": lon, "lat": lat},
                      np.arange(GW, dtype=np.float64),
                      np.arange(GH, dtype=np.float64), EPSG4326,
                      times=times, nodata=-9999.0)
        store = MASStore()
        store.ingest(extract(p))
        wkt = ("POLYGON((147.2 -34.2,147.45 -34.2,147.45 -34.05,"
               "147.2 -34.05,147.2 -34.2))")
        req = GeoDrillRequest(collection=root, bands=["bt"],
                              geometry_wkt=wkt, start_time=t0,
                              end_time=t0 + T * 86400.0, approx=False)
        res = DrillPipeline(MASClient(store)).process(req)
        assert len(res.dates) == T
        g = geom.from_wkt(wkt)
        inpoly = geom.contains_mask(g, lon, lat)
        assert inpoly.sum() > 100
        for k in range(T):
            want = float(data[k][inpoly].mean())
            assert abs(res.values["bt"][k] - want) < 1e-4, k
            assert res.counts["bt"][k] == int(inpoly.sum())

    def test_contains_mask_matches_pointwise(self):
        from gsky_tpu.geo import geometry as geom

        g = geom.from_wkt(
            "POLYGON((0 0,4 0,4 4,0 4,0 0),(1 1,2 1,2 2,1 2,1 1))")
        xs, ys = np.meshgrid(np.linspace(-1, 5, 40),
                             np.linspace(-1, 5, 40))
        got = geom.contains_mask(g, xs, ys)
        want = np.array([[g.contains_point(x, y)
                          for x, y in zip(rx, ry)]
                         for rx, ry in zip(xs, ys)])
        np.testing.assert_array_equal(got, want)

    def test_point_drill_on_swath(self, tmp_path):
        """A point drill over a curvilinear collection marks the nearest
        sample instead of silently reporting no data."""
        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io.netcdf import write_netcdf3

        GH, GW = 60, 80
        ii, jj = np.mgrid[0:GH, 0:GW].astype(np.float64)
        lon = 147.0 + 0.004 * jj + 0.0012 * ii
        lat = -34.0 - 0.003 * ii
        data = (ii * 100 + jj).astype(np.float32)
        root = str(tmp_path / "glpt")
        os.makedirs(root)
        p = os.path.join(root, "swath_20200110.nc")
        write_netcdf3(p, {"bt": data, "lon": lon, "lat": lat},
                      np.arange(GW, dtype=np.float64),
                      np.arange(GH, dtype=np.float64), EPSG4326,
                      nodata=-9999.0)
        store = MASStore()
        store.ingest(extract(p))
        # the point at grid (i=20, j=30)
        px = 147.0 + 0.004 * 30 + 0.0012 * 20
        py = -34.0 - 0.003 * 20
        req = GeoDrillRequest(collection=root, bands=["bt"],
                              geometry_wkt=f"POINT({px} {py})",
                              approx=False)
        res = DrillPipeline(MASClient(store)).process(req)
        assert len(res.dates) == 1
        assert res.values["bt"][0] == pytest.approx(20 * 100 + 30)
        assert res.counts["bt"][0] == 1

    def test_subsampled_geoloc_grid_steps(self, tmp_path):
        """pixel/line steps > 1 (subsampled geolocation arrays) map grid
        indices to raster blocks; stats cover the expanded pixels."""
        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io.netcdf import write_netcdf3

        GH, GW = 40, 50                  # geoloc grid
        H, W = GH * 2, GW * 2            # raster, step 2
        ii, jj = np.mgrid[0:GH, 0:GW].astype(np.float64)
        lon = 147.0 + 0.01 * jj
        lat = -34.0 - 0.01 * ii
        rng = np.random.default_rng(7)
        data = rng.uniform(5, 9, (H, W)).astype(np.float32)
        root = str(tmp_path / "glstep")
        os.makedirs(root)
        p = os.path.join(root, "swath_20200110.nc")
        # NC4 via h5py: the geoloc arrays have their OWN (half-res)
        # dims, which the NC3 writer's single (y, x) layout can't hold
        h5py = pytest.importorskip("h5py")
        with h5py.File(p, "w") as f:
            d = f.create_dataset("bt", data=data)
            d.attrs["_FillValue"] = np.float32(-9999.0)
            f.create_dataset("lon2", data=lon)
            f.create_dataset("lat2", data=lat)
            f.create_dataset("x", data=np.arange(W, dtype=np.float64))
            f.create_dataset("y", data=np.arange(H, dtype=np.float64))
        store = MASStore()
        rec = extract(p)
        for ds in rec["geo_metadata"]:
            if ds["namespace"] == "bt":
                ds["geo_loc"] = {"x_var": "lon2", "y_var": "lat2",
                                 "line_offset": 0.0, "pixel_offset": 0.0,
                                 "line_step": 2.0, "pixel_step": 2.0,
                                 "srs": "EPSG:4326"}
                ds["proj_wkt"] = "EPSG:4326"
                ds["polygon"] = (
                    f"POLYGON (({lon.min()} {lat.min()},"
                    f"{lon.max()} {lat.min()},{lon.max()} {lat.max()},"
                    f"{lon.min()} {lat.max()},{lon.min()} {lat.min()}))")
        store.ingest(rec)
        # polygon covering geoloc samples i in [10, 20), j in [15, 25)
        wkt = (f"POLYGON(({147.0 + 0.01 * 14.6} {-34.0 - 0.01 * 19.4},"
               f"{147.0 + 0.01 * 24.4} {-34.0 - 0.01 * 19.4},"
               f"{147.0 + 0.01 * 24.4} {-34.0 - 0.01 * 9.6},"
               f"{147.0 + 0.01 * 14.6} {-34.0 - 0.01 * 9.6},"
               f"{147.0 + 0.01 * 14.6} {-34.0 - 0.01 * 19.4}))")
        req = GeoDrillRequest(collection=root, bands=["bt"],
                              geometry_wkt=wkt, approx=False)
        res = DrillPipeline(MASClient(store)).process(req)
        assert len(res.dates) == 1
        # samples i 10..19, j 15..24 -> raster block rows 20..39, cols 30..49
        want = float(data[20:40, 30:50].mean())
        # 10x10 geoloc samples, each expanding to a 2x2 raster block
        assert res.counts["bt"][0] == 400
        assert res.values["bt"][0] == pytest.approx(want, abs=1e-4)

    def test_ruleset_geoloc_drives_render(self, tmp_path):
        """eReefs-style products: the 2-D coord vars are named lon_v/
        lat_v, which auto-detection does NOT recognise — only the
        built-in 'ereef' RULESET wires them up, and the render must
        work off that record end to end."""
        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io.netcdf import write_netcdf3
        from gsky_tpu.pipeline import TilePipeline, GeoTileRequest

        GH, GW = 80, 100
        ii, jj = np.mgrid[0:GH, 0:GW].astype(np.float64)
        lon = 147.0 + 0.004 * jj + 0.001 * ii
        lat = -34.0 - 0.003 * ii
        data = (ii + jj).astype(np.float32)
        root = str(tmp_path / "ereef")
        os.makedirs(root)
        p = os.path.join(root, "ocean_roms_his_20200110.nc")
        write_netcdf3(p, {"temp": data, "lon_v": lon, "lat_v": lat},
                      np.arange(GW, dtype=np.float64),
                      np.arange(GH, dtype=np.float64), EPSG4326,
                      nodata=-9999.0)
        rec = extract(p)           # built-in rules applied
        md = [d for d in rec["geo_metadata"] if d["namespace"] == "temp"]
        assert md and md[0].get("geo_loc"), "ereef rule did not fire"
        assert md[0]["geo_loc"]["x_var"] == "lon_v"
        store = MASStore()
        store.ingest(rec)
        req = GeoTileRequest(
            collection=root, bands=["temp"],
            bbox=BBox(147.1, -34.2, 147.35, -34.05), crs=EPSG4326,
            width=64, height=64, resample="near")
        res = TilePipeline(MASClient(store)).process(req)
        v = np.asarray(res.valid["temp"])
        assert v.sum() > 500
        d = np.asarray(res.data["temp"])
        # spot-check one pixel against the analytic inverse
        gt = req.dst_gt()
        x, y = gt.pixel_to_geo(32.5, 32.5)
        ei = (-34.0 - y) / 0.003
        ej = (x - 147.0 - 0.001 * ei) / 0.004
        if v[32, 32]:
            assert d[32, 32] == pytest.approx(
                float(np.rint(ei) + np.rint(ej)), abs=1.0)


class TestCoarseZoomInteraction:
    """P2(b) index subdivision and overview-level reads fire on the
    same coarse requests; together they must still render correctly."""

    def test_subdivided_index_with_overview_reads(self, tmp_path):
        import datetime as dtm

        from gsky_tpu.index import MASStore, MASClient
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io import write_geotiff
        from gsky_tpu.pipeline import TilePipeline, GeoTileRequest
        utm = parse_crs("EPSG:32755")
        SZ = 1024
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        yy, xx = np.mgrid[0:SZ, 0:SZ]
        data = (200 + (xx + yy)).astype(np.int16)
        root = str(tmp_path / "coarse")
        os.makedirs(root)
        p = os.path.join(root, "LC08_20200110_T1.tif")
        write_geotiff(p, data, gt, utm, nodata=-999, overviews=(2, 4))
        store = MASStore()
        store.ingest(extract(p))
        ll = transform_bbox(gt.bbox(SZ, SZ), utm, EPSG4326)
        merc = transform_bbox(ll, EPSG4326, EPSG3857)
        t0 = dtm.datetime(2020, 1, 9,
                          tzinfo=dtm.timezone.utc).timestamp()
        base = dict(collection=root, bands=["LC08_20200110_T1"],
                    bbox=merc, crs=EPSG3857, width=128, height=128,
                    start_time=t0, end_time=t0 + 3 * 86400,
                    resample="near")
        pipe = TilePipeline(MASClient(store))
        plain = pipe.process(GeoTileRequest(**base))
        # coarse + subdivision + tiny res limit: 4 index tiles fire AND
        # the 1024-px scene renders onto 128 px -> overview level 4
        pipe2 = TilePipeline(MASClient(store))
        sub = pipe2.process(GeoTileRequest(
            **base, spatial_extent=(ll.xmin, ll.ymin, ll.xmax, ll.ymax),
            index_tile_x_size=0.5, index_tile_y_size=0.5,
            index_res_limit=1e-9))
        ns = "LC08_20200110_T1"
        pv, sv = np.asarray(plain.valid[ns]), np.asarray(sub.valid[ns])
        np.testing.assert_array_equal(pv, sv)
        pd, sd = np.asarray(plain.data[ns]), np.asarray(sub.data[ns])
        np.testing.assert_array_equal(pd, sd)
        assert sv.sum() > 5000
