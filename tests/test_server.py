"""OWS server tests: full WMS/WCS/WPS request handling over the fixture
archive through the aiohttp test client."""

import asyncio
import datetime as dt
import io
import json
import os

import numpy as np
import pytest
from PIL import Image

from gsky_tpu.index import MASClient
from gsky_tpu.io.png import decode_png
from gsky_tpu.server.config import ConfigWatcher, load_config_tree
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

from fixtures import make_archive

DATE = "2020-01-10T00:00:00.000Z"
# fixture granules ~ lon 147.99-148.24, lat -35.19..-35.37 (see
# tests/test_pipeline.py); bbox in 3857
BBOX3857 = "16478548,-4211230,16489679,-4198025"


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("srv")
    arch = make_archive(str(root / "data"))
    conf_dir = root / "conf"
    conf_dir.mkdir()
    config = {
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [
            {
                "name": "landsat", "title": "Landsat-ish scenes",
                "data_source": arch["root"],
                "rgb_products": ["LC08_20200110_T1"],
                "time_generator": "mas",
                "palette": {"interpolate": True, "colours": [
                    {"R": 0, "G": 0, "B": 128, "A": 255},
                    {"R": 255, "G": 255, "B": 0, "A": 255}]},
            },
            {
                "name": "frac_cover", "title": "Fractional cover",
                "data_source": arch["root"],
                "rgb_products": ["phot_veg", "bare_soil",
                                 "total = phot_veg + bare_soil"],
                "time_generator": "mas",
            },
            {
                "name": "hidden_wms", "title": "wcs only",
                "data_source": arch["root"],
                "rgb_products": ["phot_veg"],
                "disable_services": ["wms"],
                "dates": [DATE],
            },
        ],
        "processes": [{
            "identifier": "geometryDrill",
            "title": "Geometry drill",
            "max_area": 10000,
            "data_sources": [{
                "data_source": arch["root"],
                "rgb_products": ["phot_veg"],
            }],
            "approx": False,
        }],
    }
    (conf_dir / "config.json").write_text(json.dumps(config))

    mas_client = MASClient(arch["store"])
    watcher = ConfigWatcher(str(conf_dir),
                            mas_factory=lambda addr: mas_client,
                            install_signal=False)
    server = OWSServer(watcher, mas_factory=lambda addr: mas_client,
                       metrics=MetricsLogger())
    return {"server": server, "arch": arch, "conf": str(conf_dir)}


def _get(env, path):
    from aiohttp.test_utils import TestClient, TestServer

    async def go():
        client = TestClient(TestServer(env["server"].app()))
        await client.start_server()
        try:
            resp = await client.get(path)
            return resp.status, resp.content_type, await resp.read()
        finally:
            await client.close()
    return asyncio.new_event_loop().run_until_complete(go())


def _post(env, path, data):
    from aiohttp.test_utils import TestClient, TestServer

    async def go():
        client = TestClient(TestServer(env["server"].app()))
        await client.start_server()
        try:
            resp = await client.post(path, data=data)
            return resp.status, resp.content_type, await resp.read()
        finally:
            await client.close()
    return asyncio.new_event_loop().run_until_complete(go())


class TestWMS:
    def test_capabilities(self, env):
        status, ctype, body = _get(env, "/ows?service=WMS&request=GetCapabilities")
        assert status == 200
        text = body.decode()
        assert "<WMS_Capabilities" in text
        assert "<Name>landsat</Name>" in text
        assert "<Name>frac_cover</Name>" in text
        assert "hidden_wms" not in text  # wms disabled
        assert DATE in text  # mas time generator found the dates

    def test_getmap_renders_png(self, env):
        status, ctype, body = _get(
            env, f"/ows?service=WMS&request=GetMap&version=1.3.0"
                 f"&layers=landsat&crs=EPSG:3857&bbox={BBOX3857}"
                 f"&width=256&height=256&format=image/png&time={DATE}")
        assert status == 200, body[:300]
        assert ctype == "image/png"
        rgba = decode_png(body)
        assert rgba.shape == (256, 256, 4)
        # palette applied: valid pixels should be coloured
        assert (rgba[..., 3] > 0).sum() > 1000

    def test_getmap_no_time_uses_latest(self, env):
        status, _, body = _get(
            env, f"/ows?service=WMS&request=GetMap&version=1.3.0"
                 f"&layers=frac_cover&crs=EPSG:3857&bbox={BBOX3857}"
                 f"&width=64&height=64&format=image/png")
        assert status == 200, body[:300]

    def test_getmap_service_inferred(self, env):
        status, ctype, _ = _get(
            env, f"/ows?request=GetMap&version=1.3.0&layers=landsat"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=32&height=32"
                 f"&format=image/png&time={DATE}")
        assert status == 200
        assert ctype == "image/png"

    def test_getmap_missing_layer(self, env):
        status, ctype, body = _get(
            env, f"/ows?service=WMS&request=GetMap&layers=nope"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=32&height=32")
        assert status == 400
        assert b"LayerNotDefined" in body

    def test_getmap_oversize(self, env):
        status, _, body = _get(
            env, f"/ows?service=WMS&request=GetMap&layers=landsat"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=9999&height=32"
                 f"&format=image/png&time={DATE}")
        assert status == 400
        assert b"exceeds" in body

    def test_getmap_wms_disabled(self, env):
        status, _, body = _get(
            env, f"/ows?service=WMS&request=GetMap&layers=hidden_wms"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=32&height=32")
        assert status == 400
        assert b"disabled" in body

    def test_getmap_1_1_1_axis_order(self, env):
        # 1.1.1 + EPSG:4326: lon,lat order
        status, _, body = _get(
            env, "/ows?service=WMS&request=GetMap&version=1.1.1"
                 "&layers=landsat&srs=EPSG:4326"
                 "&bbox=148.02,-35.32,148.12,-35.22"
                 f"&width=64&height=64&format=image/png&time={DATE}")
        assert status == 200, body[:300]
        # 1.3.0 + EPSG:4326: lat,lon order (same request, swapped)
        status2, _, body2 = _get(
            env, "/ows?service=WMS&request=GetMap&version=1.3.0"
                 "&layers=landsat&crs=EPSG:4326"
                 "&bbox=-35.32,148.02,-35.22,148.12"
                 f"&width=64&height=64&format=image/png&time={DATE}")
        assert status2 == 200, body2[:300]
        assert body == body2  # identical tiles

    def test_feature_info(self, env):
        status, ctype, body = _get(
            env, f"/ows?service=WMS&request=GetFeatureInfo&version=1.3.0"
                 f"&layers=frac_cover&crs=EPSG:3857&bbox={BBOX3857}"
                 f"&width=64&height=64&i=32&j=32&time={DATE}")
        assert status == 200, body[:300]
        doc = json.loads(body)
        assert doc["type"] == "FeatureCollection"
        props = doc["features"][0]["properties"]
        assert "phot_veg" in props

    def test_legend_from_palette(self, env):
        status, ctype, body = _get(
            env, "/ows?service=WMS&request=GetLegendGraphic&layer=landsat")
        assert status == 200
        img = Image.open(io.BytesIO(body))
        assert img.size == (160, 320)

    def test_describe_layer(self, env):
        status, _, body = _get(
            env, "/ows?service=WMS&request=DescribeLayer&layers=landsat")
        assert status == 200
        assert b"LayerDescription" in body

    def test_bogus_request(self, env):
        status, _, body = _get(env, "/ows?service=WMS&request=Frobnicate")
        assert status == 400
        assert b"not supported" in body

    def test_unknown_namespace(self, env):
        status, _, body = _get(
            env, "/ows/nope?service=WMS&request=GetCapabilities")
        assert status == 404


class TestWCS:
    def test_capabilities(self, env):
        status, _, body = _get(env, "/ows?service=WCS&request=GetCapabilities")
        assert status == 200
        assert b"WCS_Capabilities" in body
        assert b"<name>landsat</name>" in body

    def test_describe_coverage(self, env):
        status, _, body = _get(
            env, "/ows?service=WCS&request=DescribeCoverage"
                 "&coverage=frac_cover")
        assert status == 200
        assert b"CoverageOffering" in body
        assert DATE.encode() in body

    def test_getcoverage_geotiff(self, env, tmp_path):
        status, ctype, body = _get(
            env, f"/ows?service=WCS&request=GetCoverage&coverage=frac_cover"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=128&height=96"
                 f"&format=GeoTIFF&time={DATE}")
        assert status == 200, body[:300]
        p = tmp_path / "cov.tif"
        p.write_bytes(body)
        from gsky_tpu.io.geotiff import GeoTIFF
        with GeoTIFF(str(p)) as g:
            assert g.width == 128 and g.height == 96
            assert g.count == 3  # phot_veg, bare_soil, total
            assert g.nodata == -9999.0
            data = g.read(1)
            assert (data != -9999.0).any()

    def test_getcoverage_netcdf(self, env, tmp_path):
        status, ctype, body = _get(
            env, f"/ows?service=WCS&request=GetCoverage&coverage=frac_cover"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=64&height=64"
                 f"&format=NetCDF&time={DATE}")
        assert status == 200, body[:300]
        p = tmp_path / "cov.nc"
        p.write_bytes(body)
        from gsky_tpu.io.netcdf import NetCDF
        with NetCDF(str(p)) as nc:
            assert "phot_veg" in nc.variables
            assert nc.variables["phot_veg"].shape == (64, 64)

    def test_getcoverage_bad_format(self, env):
        status, _, body = _get(
            env, f"/ows?service=WCS&request=GetCoverage&coverage=frac_cover"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=32&height=32"
                 f"&format=Zarr")
        assert status == 400
        assert b"InvalidFormat" in body

    def test_getcoverage_cluster_sharding(self, env, tmp_path):
        """OWS-cluster scale-out (`ows.go:835-872,930-995`): a master
        with ows_cluster_nodes splits the tile grid into row bands,
        fetches remote bands from a peer OWS via HTTP GetCoverage
        re-entry, and the merged coverage matches a local render."""
        from aiohttp.test_utils import TestClient, TestServer
        from gsky_tpu.server.config import ConfigWatcher, load_config_tree
        from gsky_tpu.server.metrics import MetricsLogger
        from gsky_tpu.server.ows import OWSServer

        arch = env["arch"]
        mas_client = MASClient(arch["store"])
        url = (f"/ows?service=WCS&request=GetCoverage&coverage=frac_cover"
               f"&crs=EPSG:3857&bbox={BBOX3857}&width=128&height=96"
               f"&format=GeoTIFF&time={DATE}")

        def make_server(conf_dir, cluster_nodes):
            config = {
                "service_config": {"ows_hostname": "",
                                   "mas_address": "inproc",
                                   "ows_cluster_nodes": cluster_nodes},
                "layers": [{
                    "name": "frac_cover", "title": "fc",
                    "data_source": arch["root"],
                    "rgb_products": ["phot_veg", "bare_soil"],
                    "dates": [DATE],
                    # force a multi-tile render so sharding kicks in
                    "wcs_max_tile_width": 32, "wcs_max_tile_height": 16,
                }],
            }
            conf_dir.mkdir()
            (conf_dir / "config.json").write_text(json.dumps(config))
            watcher = ConfigWatcher(str(conf_dir),
                                    mas_factory=lambda a: mas_client,
                                    install_signal=False)
            return OWSServer(watcher, mas_factory=lambda a: mas_client,
                             metrics=MetricsLogger())

        async def go():
            peer = make_server(tmp_path / "peer_conf", [])
            peer_client = TestClient(TestServer(peer.app()))
            await peer_client.start_server()
            peer_url = f"http://127.0.0.1:{peer_client.port}"
            try:
                master = make_server(tmp_path / "master_conf",
                                     ["local", peer_url])
                mc = TestClient(TestServer(master.app()))
                await mc.start_server()
                try:
                    sharded = await (await mc.get(url)).read()
                    # reference render: same server, sharding disabled
                    # via the wshard re-entry guard
                    plain = await (await mc.get(url + "&wshard=1")).read()
                finally:
                    await mc.close()
            finally:
                await peer_client.close()
            return sharded, plain

        sharded, plain = asyncio.new_event_loop().run_until_complete(go())
        ps = tmp_path / "sharded.tif"
        pp = tmp_path / "plain.tif"
        ps.write_bytes(sharded)
        pp.write_bytes(plain)
        from gsky_tpu.io.geotiff import GeoTIFF
        with GeoTIFF(str(ps)) as a, GeoTIFF(str(pp)) as b:
            assert a.width == b.width and a.height == b.height
            assert a.count == b.count == 2
            for bi in range(1, a.count + 1):
                da = a.read(bi)
                db = b.read(bi)
                assert (da != -9999.0).any()
                # approx-transform nearest flips may differ on a handful
                # of boundary pixels
                assert np.mean(da != db) < 0.02


class TestWPS:
    GEOM = json.dumps({"type": "FeatureCollection", "features": [{
        "type": "Feature", "geometry": {
            "type": "Polygon",
            "coordinates": [[[148.0, -36.0], [148.5, -36.0], [148.5, -35.0],
                             [148.0, -35.0], [148.0, -36.0]]]}}]})

    def test_capabilities(self, env):
        status, _, body = _get(env, "/ows?service=WPS&request=GetCapabilities")
        assert status == 200
        assert b"geometryDrill" in body

    def test_describe_process(self, env):
        status, _, body = _get(
            env, "/ows?service=WPS&request=DescribeProcess"
                 "&identifier=geometryDrill")
        assert status == 200
        assert b"ProcessDescription" in body

    def test_execute_kvp(self, env):
        import urllib.parse
        geom_q = urllib.parse.quote(self.GEOM)
        status, _, body = _get(
            env, f"/ows?service=WPS&request=Execute&identifier=geometryDrill"
                 f"&datainputs=geometry={geom_q}")
        assert status == 200, body[:400]
        text = body.decode()
        assert "ProcessSucceeded" in text
        assert "2020-01-10" in text

    def test_execute_xml_post(self, env):
        xml = f"""<?xml version="1.0" encoding="UTF-8"?>
<wps:Execute service="WPS" version="1.0.0"
    xmlns:wps="http://www.opengis.net/wps/1.0.0"
    xmlns:ows="http://www.opengis.net/ows/1.1">
  <ows:Identifier>geometryDrill</ows:Identifier>
  <wps:DataInputs>
    <wps:Input>
      <ows:Identifier>geometry</ows:Identifier>
      <wps:Data><wps:ComplexData mimeType="application/vnd.geo+json">
        {self.GEOM.replace('<', '&lt;')}
      </wps:ComplexData></wps:Data>
    </wps:Input>
    <wps:Input>
      <ows:Identifier>start_datetime</ows:Identifier>
      <wps:Data><wps:LiteralData>2020-01-09T00:00:00.000Z</wps:LiteralData></wps:Data>
    </wps:Input>
  </wps:DataInputs>
</wps:Execute>"""
        status, _, body = _post(env, "/ows?service=WPS", xml.encode())
        assert status == 200, body[:400]
        assert b"ProcessSucceeded" in body

    def test_execute_area_limit(self, env):
        big = json.dumps({"type": "Polygon", "coordinates": [[
            [0, -80], [170, -80], [170, 80], [0, 80], [0, -80]]]})
        import urllib.parse
        status, _, body = _get(
            env, f"/ows?service=WPS&request=Execute&identifier=geometryDrill"
                 f"&datainputs=geometry={urllib.parse.quote(big)}")
        assert status == 400
        assert b"area exceeds" in body

    def test_execute_bad_geometry(self, env):
        status, _, body = _get(
            env, "/ows?service=WPS&request=Execute&identifier=geometryDrill"
                 "&datainputs=geometry={bad json}")
        assert status == 400


def _without_creation_time(body: bytes) -> bytes:
    import re
    return re.sub(rb'creationTime="[^"]*"', b'creationTime=""', body)


class TestDrillStages:
    """A WPS Execute is legible from inside: stage spans where the work
    happens, folded into /debug `drill_stages`."""

    STAGES = ("parse_s", "admission_s", "index_s", "prepare_s",
              "device_s", "host_read_s", "merge_s", "format_s")

    def _execute(self, env):
        import urllib.parse
        return _get(
            env, "/ows?service=WPS&request=Execute&identifier=geometryDrill"
                 f"&datainputs=geometry={urllib.parse.quote(TestWPS.GEOM)}")

    @pytest.fixture
    def fresh(self, env):
        """The server with a metrics logger and a recorder of its own."""
        from gsky_tpu import obs
        obs.reset_recorder()
        before = env["server"].metrics
        env["server"].metrics = MetricsLogger()
        yield env["server"].metrics
        env["server"].metrics = before
        obs.reset_recorder()

    def test_execute_leaves_stage_spans_and_drill_stages(self, env, fresh):
        from gsky_tpu import obs
        sent = 3
        for _ in range(sent):
            status, _, body = self._execute(env)
            assert status == 200, body[:400]
        traces = [t for t in obs.default_recorder().traces()
                  if t["attrs"].get("verb") == "WPS.Execute"]
        assert len(traces) == sent
        for tr in traces:
            spans = {}
            for sp in tr["spans"]:
                spans.setdefault(sp["name"], []).append(sp)
            assert {"wps.parse", "gateway.admission", "drill.index",
                    "drill.prepare", "drill.merge", "wps.format"} \
                <= set(spans), sorted(spans)
            # each file is answered by the device or by host reads
            assert "drill.device" in spans or "drill.host_read" in spans
            assert spans["gateway.admission"][0]["attrs"]["service"] == "WPS"
            assert spans["drill.index"][0]["attrs"]["datasets"] >= 1
            assert spans["drill.index"][0]["attrs"]["timestamps"] >= 1
            assert spans["drill.prepare"][0]["attrs"]["kind"] in (
                "nc", "tiff", "vrt")
            assert len(spans["drill.prepare"][0]["attrs"]["window"]) == 2
            assert sum(sp["attrs"].get("vertices", 0)
                       for sp in spans["wps.parse"]) == 5
            fmt = spans["wps.format"][0]["attrs"]
            assert fmt["rows"] >= 1 and fmt["bytes"] > 0
        ds = fresh.summary()["drill_stages"]
        assert ds["requests"] == sent
        assert ds["files"] >= sent
        for doc in (ds, ds["last"]):
            # files on one grid share a window: never more windows than
            # files, and no file drilled without one
            assert 1 <= doc["windows"] <= doc["files"], doc
            assert all(doc[k] >= 0 for k in self.STAGES), doc
            # the stages run one after another: no request's named
            # stages sum to more than its wall time
            assert sum(doc[k] for k in self.STAGES) <= doc["wall_s"], doc
        assert ds["index_s"] > 0 and ds["prepare_s"] > 0
        # /metrics is fed at the same point
        text = obs.render_metrics()
        assert 'gsky_stage_seconds_count{stage="drill_index"}' in text
        assert 'gsky_stage_seconds_count{stage="drill_wall"}' in text

    def test_trace_off_leaves_no_drill_stages_and_the_same_bytes(
            self, env, fresh, monkeypatch):
        status, _, traced = self._execute(env)
        assert status == 200 and "drill_stages" in fresh.summary()
        env["server"].metrics = MetricsLogger()
        monkeypatch.setenv("GSKY_TRACE", "0")
        status, _, untraced = self._execute(env)
        assert status == 200
        # but for `creationTime`, the wall clock to the second: two
        # Executes either side of a second's end differ there
        assert _without_creation_time(untraced) \
            == _without_creation_time(traced)
        assert b"creationTime" in traced
        assert "drill_stages" not in env["server"].metrics.summary()


class TestConfigSystem:
    def test_tree_namespaces(self, tmp_path):
        (tmp_path / "config.json").write_text(json.dumps(
            {"layers": [{"name": "root_layer"}]}))
        sub = tmp_path / "geoglam"
        sub.mkdir()
        (sub / "config.json").write_text(json.dumps(
            {"layers": [{"name": "sub_layer"}]}))
        cfgs = load_config_tree(str(tmp_path), load_dates=False)
        assert set(cfgs) == {"", "geoglam"}
        assert cfgs[""].layers[0].name == "root_layer"
        assert cfgs["geoglam"].layers[0].name == "sub_layer"

    def test_date_generators(self, tmp_path):
        (tmp_path / "config.json").write_text(json.dumps({"layers": [
            {"name": "reg", "start_isodate": "2020-01-01T00:00:00.000Z",
             "end_isodate": "2020-01-05T00:00:00.000Z", "step_days": 1,
             "time_generator": "regular"},
            {"name": "mon", "start_isodate": "2020-01-01T00:00:00.000Z",
             "end_isodate": "2020-06-30T00:00:00.000Z",
             "time_generator": "monthly"},
            {"name": "chirps", "start_isodate": "2020-01-01T00:00:00.000Z",
             "end_isodate": "2020-02-25T00:00:00.000Z",
             "time_generator": "chirps20"},
        ]}))
        cfgs = load_config_tree(str(tmp_path))
        reg, mon, chirps = cfgs[""].layers
        assert len(reg.dates) == 5
        assert reg.effective_end_date == "2020-01-05T00:00:00.000Z"
        assert len(mon.dates) == 6
        assert chirps.dates[:3] == ["2020-01-01T00:00:00.000Z",
                                    "2020-01-11T00:00:00.000Z",
                                    "2020-01-21T00:00:00.000Z"]

    def test_gdoc_heredoc(self, tmp_path):
        (tmp_path / "config.json").write_text(
            '{"layers": [{"name": "h", "abstract": $gdoc$line "quoted"\n'
            'second$gdoc$}]}')
        cfgs = load_config_tree(str(tmp_path), load_dates=False)
        assert 'line "quoted"\nsecond' == cfgs[""].layers[0].abstract

    def test_template_include_and_comments(self, tmp_path):
        """Jet-pass subset (`config.go:1067-1085`): {{include}} splices
        files (recursively), {* comments *} strip, and gdoc escaping in
        included text still applies (template runs first)."""
        (tmp_path / "palette.json").write_text(
            '{"interpolate": true, "colours": ['
            '{"R": 0, "G": 0, "B": 120, "A": 255}]}')
        (tmp_path / "layer.json").write_text(
            '{"name": "inc", {* a note *} '
            '"abstract": $gdoc$from "include"$gdoc$, '
            '"palette": {{ include "palette.json" }}}')
        (tmp_path / "config.json").write_text(
            '{"layers": [ {{include "layer.json"}} ]}')
        cfgs = load_config_tree(str(tmp_path), load_dates=False)
        lay = cfgs[""].layers[0]
        assert lay.name == "inc"
        assert lay.abstract == 'from "include"'
        assert lay.palette and lay.palette.colours == [(0, 0, 120, 255)]

    def test_template_include_depth_bound(self, tmp_path):
        (tmp_path / "config.json").write_text(
            '{{include "config.json"}}')
        # the explicit bound, not RecursionError-by-accident
        with pytest.raises(ValueError, match="nested too deep"):
            load_config_tree(str(tmp_path), load_dates=False)

    def test_reload(self, tmp_path):
        (tmp_path / "config.json").write_text(json.dumps(
            {"layers": [{"name": "a"}]}))
        w = ConfigWatcher(str(tmp_path), install_signal=False)
        assert w.get("").layers[0].name == "a"
        (tmp_path / "config.json").write_text(json.dumps(
            {"layers": [{"name": "b"}]}))
        w.reload()
        assert w.get("").layers[0].name == "b"


class TestMetrics:
    def test_schema(self, env, capsys):
        ml = env["server"].metrics
        c = ml.collector()
        c.set_url("/ows?service=WMS&foo=1&layers=x",
                  "/ows", {"service": "WMS", "foo": "1", "layers": "x"})
        c.set_remote("10.0.0.1:1234")
        c.log(200)
        info = c.info
        assert info["http_status"] == 200
        assert info["url"]["query"] == {"service": "WMS", "layers": "x"}
        assert info["remote_host"] == "10.0.0.1"
        assert "indexer" in info and "rpc" in info
        assert info["req_duration"] > 0


class TestServerReviewRegressions:
    def test_capabilities_with_braces_in_abstract(self, tmp_path):
        from gsky_tpu.server.config import load_config_file
        from gsky_tpu.server import templates as T
        (tmp_path / "config.json").write_text(json.dumps({"layers": [
            {"name": "x", "abstract": "units in {mm} and {braces}"}]}))
        cfg = load_config_file(str(tmp_path / "config.json"))
        doc = T.wms_capabilities(cfg, "/ows", "http://h")
        assert "{mm}" in doc

    def test_bad_i_j_is_400(self, env):
        status, _, body = _get(
            env, f"/ows?service=WMS&request=GetFeatureInfo&layers=frac_cover"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=64&height=64"
                 f"&i=abc&j=2&time={DATE}")
        assert status == 400
        assert b"invalid i" in body

    def test_multi_subset_clauses(self):
        from multidict import MultiDict
        from gsky_tpu.server.params import normalise_query, parse_wcs
        q = normalise_query(MultiDict([("service", "WCS"),
                                       ("request", "GetCoverage"),
                                       ("subset", "depth(5,10)"),
                                       ("subset", "run(2)")]))
        p = parse_wcs(q)
        assert p.axes["depth"] == (5.0, 10.0)
        assert p.axes["run"] == (2.0, 2.0)

    def test_wcs_temp_file_cleaned(self, env):
        import glob
        before = set(glob.glob(os.path.join(
            env["server"].temp_dir, "wcs_*.tif")))
        status, _, body = _get(
            env, f"/ows?service=WCS&request=GetCoverage&coverage=frac_cover"
                 f"&crs=EPSG:3857&bbox={BBOX3857}&width=32&height=32"
                 f"&format=GeoTIFF&time={DATE}")
        assert status == 200
        after = set(glob.glob(os.path.join(
            env["server"].temp_dir, "wcs_*.tif")))
        assert after == before  # deleted after the response body was read


class TestWCSStreaming:
    def test_large_coverage_streams_to_disk(self, env, tmp_path,
                                            monkeypatch):
        """Coverages beyond WCS_STREAM_PIXELS write tiles straight to a
        GeoTIFFWriter (`ows.go:695,1088-1091` incremental flush) and the
        result must match the in-RAM path."""
        import gsky_tpu.server.ows as ows_mod
        url = (f"/ows?service=WCS&request=GetCoverage&coverage="
               f"frac_cover&crs=EPSG:3857&bbox={BBOX3857}"
               f"&width=512&height=512&format=GeoTIFF&time={DATE}")
        status, _, plain = _get(env, url)
        assert status == 200
        monkeypatch.setattr(ows_mod, "WCS_STREAM_PIXELS", 1000)
        status, _, streamed = _get(env, url)
        assert status == 200
        pp = tmp_path / "plain.tif"
        ps = tmp_path / "stream.tif"
        pp.write_bytes(plain)
        ps.write_bytes(streamed)
        from gsky_tpu.io.geotiff import GeoTIFF
        with GeoTIFF(str(pp)) as a, GeoTIFF(str(ps)) as b:
            assert (a.width, a.height, a.count) == \
                (b.width, b.height, b.count)
            assert b.nodata == -9999.0
            for bi in range(1, a.count + 1):
                np.testing.assert_array_equal(a.read(bi), b.read(bi))


class TestCacheMetrics:
    def test_cache_block_in_metrics(self, tmp_path):
        from gsky_tpu.server.metrics import MetricsLogger

        logger = MetricsLogger(log_dir=str(tmp_path))
        c = logger.collector()
        c.log(200)
        logger._fp.flush()
        import glob, json as _json
        files = glob.glob(str(tmp_path / "*.log"))
        assert files
        with open(files[0]) as fp:
            rec = _json.loads(fp.readline())
        assert "cache" in rec
        assert "scene" in rec["cache"]
        assert {"hits", "misses"} <= set(rec["cache"]["scene"])


class TestDebugSideDoor:
    """The /debug profiling side-door (`ows.go:40` pprof role)."""

    def test_debug_summary_after_requests(self, env):
        import json as _json

        # drive a couple of real requests so the summary has rows
        st, ct, _ = _get(env, "/ows?service=WMS&request=GetCapabilities")
        assert st == 200
        st, ct, _ = _get(
            env, "/ows?service=WMS&request=GetMap&version=1.3.0"
            f"&layers=landsat&crs=EPSG:3857&bbox={BBOX3857}"
            "&width=64&height=64&format=image/png"
            f"&time={DATE}")
        assert st == 200

        st, ct, body = _get(env, "/debug")
        assert st == 200 and ct == "application/json"
        doc = _json.loads(body)
        assert doc["uptime_s"] >= 0
        reqs = doc["requests"]
        assert any(k.lower().startswith("wms.getmap") for k in reqs), reqs
        getmap = next(v for k, v in reqs.items()
                      if k.lower().startswith("wms.getmap"))
        assert getmap["count"] >= 1
        assert getmap["p50_ms"] is not None and getmap["p50_ms"] > 0
        assert "cache" in doc and "scene" in doc["cache"]
        assert "executor" in doc
        # dispatch counters: the GetMap above must have gone through
        # a fused render path
        disp = doc["executor"]["dispatches"]
        assert any(k.startswith(("render_byte", "scene_mosaic",
                                 "window_batch", "render_rgba"))
                   for k in disp), disp
        gw = doc["executor"]["gather_window"]
        assert set(gw) == {"engaged", "declined"}
        assert "jax" in doc and doc["jax"]["backend"] == "cpu"
        # what chip_smoke.py and an operator read a fallback from:
        # device, fresh compiles, prewarm, and the kernel selection
        assert doc["jax"]["device_kind"] == "cpu"
        assert doc["jax"]["compiles"] >= 0
        assert "prewarm" in doc
        assert set(doc["kernels"]) >= {
            "failed", "demoted", "promoted", "lowered",
            "warp_pallas_enabled", "ledger_path"}

    def test_debug_errors_counted(self, env):
        import json as _json

        st, _, _ = _get(env, "/ows?service=WMS&request=GetMap"
                             "&layers=nolayer")
        assert st == 400
        st, _, body = _get(env, "/debug")
        doc = _json.loads(body)
        getmap = next(v for k, v in doc["requests"].items()
                      if k.lower().startswith("wms.getmap"))
        assert getmap["errors"] >= 1

    def test_debug_profile_capture(self, env, tmp_path):
        import json as _json

        env["server"].temp_dir = str(tmp_path)
        st, _, body = _get(env, "/debug/profile?seconds=0.2")
        doc = _json.loads(body)
        if st == 503:
            # profiler unavailable on this backend build: the route
            # must degrade with an explanation, not a 500
            assert "error" in doc
            return
        assert st == 200
        import os as _os
        assert _os.path.isdir(doc["trace_dir"])
