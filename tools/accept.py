#!/usr/bin/env python
"""Acceptance/load harness — port of `acceptance_tests/accept.go:134-199`.

Suites:
  wms      GetCapabilities + concurrent replay of a GetMap URL list file
           (lines contain ``%s`` host placeholders, as `acpt_url.tpl`)
  wps      GetCapabilities + DescribeProcess + concurrent WPS Execute
           POSTs of every XML payload in a directory (response must be
           200 and >= --min-body bytes)
  selftest boots a local gsky-tpu OWS server over a synthetic Landsat
           style archive and replays a generated GetMap grid against it
           (the in-repo equivalent of pointing the harness at
           gsky.nci.org.au)

Exit status 0 = all requests passed.  Reports wall time and request
rate like the reference.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import os
import sys
import time
import urllib.request

WMS_CAPS = "http://%s/ows?service=WMS&version=1.3.0&request=GetCapabilities"
WPS_CAPS = "http://%s/ows?service=WPS&request=GetCapabilities&version=1.0.0"
WPS_DESCR = ("http://%s/ows?service=WPS&request=DescribeProcess"
             "&version=1.0.0&Identifier=geometryDrill")


def _get(url: str, timeout: float = 60.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


def _post(url: str, data: bytes, timeout: float = 120.0):
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "text/plain;charset=UTF-8"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def check_capabilities(host: str, tpl: str) -> bool:
    try:
        status, _ = _get(tpl % host)
        return status == 200
    except Exception as e:
        print(f"  capabilities error: {e}")
        return False


def replay_urls(host: str, urls, conc: int, min_body: int = 100):
    """Concurrent GET replay; every response must be 200 with a body of
    at least min_body bytes (`accept.go:104-124` semantics)."""
    start = time.time()
    failures = []

    def one(u):
        try:
            status, body = _get(u % host if "%s" in u else u)
            if status != 200 or len(body) < min_body:
                return f"{u[:120]}: HTTP {status}, {len(body)} bytes"
        except Exception as e:
            return f"{u[:120]}: {e}"
        return None

    with cf.ThreadPoolExecutor(conc) as ex:
        for err in ex.map(one, urls):
            if err:
                failures.append(err)
    elapsed = time.time() - start
    return failures, elapsed


def suite_wms(host: str, url_file: str, conc: int) -> int:
    print("Testing WMS GetCapabilities: ", end="", flush=True)
    if not check_capabilities(host, WMS_CAPS):
        print("Failed")
        return 1
    print("Passed")
    with open(url_file) as fp:
        urls = [l.strip().replace("%%", "%") for l in fp if l.strip()]
    print(f"Testing WMS GetMap Sending {len(urls)} requests: ",
          end="", flush=True)
    failures, elapsed = replay_urls(host, urls, conc)
    if failures:
        print(f"Failed ({len(failures)}/{len(urls)})")
        for f in failures[:10]:
            print("  " + f)
        return 1
    print(f"Passed {elapsed:.2f}s ({len(urls) / elapsed:.1f} req/s)")
    return 0


def suite_wps(host: str, payload_dir: str, conc: int,
              min_body: int) -> int:
    for name, tpl in (("GetCapabilities", WPS_CAPS),
                      ("DescribeProcess", WPS_DESCR)):
        print(f"Testing WPS {name}: ", end="", flush=True)
        if not check_capabilities(host, tpl):
            print("Failed")
            return 1
        print("Passed")
    payloads = sorted(os.path.join(payload_dir, f)
                      for f in os.listdir(payload_dir))
    print(f"Testing WPS Polygon Drill ({len(payloads)} payloads): ",
          end="", flush=True)
    start = time.time()
    failures = []

    def one(path):
        try:
            with open(path, "rb") as fp:
                status, body = _post(
                    f"http://{host}/ows?service=WPS&request=Execute",
                    fp.read())
            if status != 200 or len(body) < min_body:
                return f"{path}: HTTP {status}, {len(body)} bytes"
        except Exception as e:
            return f"{path}: {e}"
        return None

    with cf.ThreadPoolExecutor(conc) as ex:
        for err in ex.map(one, payloads):
            if err:
                failures.append(err)
    elapsed = time.time() - start
    if failures:
        print(f"Failed ({len(failures)}/{len(payloads)})")
        for f in failures[:10]:
            print("  " + f)
        return 1
    print(f"Passed {elapsed:.2f}s")
    return 0


# ---------------------------------------------------------------------------
# self-hosted suite
# ---------------------------------------------------------------------------

def suite_selftest(conc: int, n_tiles: int) -> int:
    """Boot a real server over a synthetic archive, replay a GetMap
    grid + one WCS export + one WPS drill against it."""
    import asyncio
    import json
    import tempfile
    import threading

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # this process holds the chip (or JAX_PLATFORMS=cpu says CPU);
    # resolve before any pipeline import compiles anything
    from gsky_tpu.device import ensure_platform
    plat = ensure_platform()
    print(f"selftest on {plat['platform']} ({plat['device_kind']})",
          flush=True)
    from tools import sample_archive as B
    from gsky_tpu.index import MASClient
    from gsky_tpu.server.config import ConfigWatcher
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import BBox, transform_bbox

    root = tempfile.mkdtemp(prefix="gsky_accept_")
    store, utm, paths = B.build_archive(root)
    mas_client = MASClient(store)

    # a curvilinear (geolocation-array) swath layer rides along: the
    # acceptance run must exercise the geoloc warp through the full
    # HTTP server, not just unit tests
    import numpy as _np

    from gsky_tpu.index.crawler import extract as _extract
    from gsky_tpu.io.netcdf import write_netcdf3 as _wnc

    swath_dir = os.path.join(root, "swath")
    os.makedirs(swath_dir)
    _gh, _gw = 120, 160
    _ii, _jj = _np.mgrid[0:_gh, 0:_gw].astype(_np.float64)
    _lon = 148.0 + 0.0015 * _jj + 0.0005 * _ii
    _lat = -35.15 - 0.0012 * _ii
    _wnc(os.path.join(swath_dir, "swath_20200110.nc"),
         {"bt": (1000.0 + _ii + _jj).astype(_np.float32),
          "lon": _lon, "lat": _lat},
         _np.arange(_gw, dtype=_np.float64),
         _np.arange(_gh, dtype=_np.float64), EPSG4326, nodata=-9999.0)
    store.ingest(_extract(os.path.join(swath_dir, "swath_20200110.nc")))

    # a native GMT grid layer rides along too (the registry's GMT
    # reader through the full HTTP server — `gmtdataset.cpp` role)
    from gsky_tpu.io.gmt import write_gmt as _wgmt

    gmt_dir = os.path.join(root, "gmt")
    os.makedirs(gmt_dir)
    _rng = _np.random.default_rng(6)
    _wgmt(os.path.join(gmt_dir, "relief_20200110.grd"),
          _rng.uniform(0, 100, (96, 96)).astype(_np.float32),
          (148.0, 148.96), (-35.96, -35.0))
    store.ingest(_extract(os.path.join(gmt_dir, "relief_20200110.grd")))

    # an HDF4 MODIS-style sinusoidal grid rides along (the native HDF4
    # reader through the full HTTP server — GDAL-HDF4-driver role)
    from gsky_tpu.geo.crs import CRS_SINU_MODIS
    from gsky_tpu.geo.transform import GeoTransform as _GT
    from gsky_tpu.io.hdf4 import write_hdf4 as _whdf

    hdf_dir = os.path.join(root, "hdf")
    os.makedirs(hdf_dir)
    _sx, _sy = CRS_SINU_MODIS.from_lonlat(148.0, -35.0)
    _whdf(os.path.join(hdf_dir, "MOD13Q1.A2020010.h29v12.hdf"),
          {"NDVI": _rng.uniform(-2000, 10000, (96, 96))
           .astype(_np.int16)},
          gt=_GT(float(_sx), 463.3127, 0.0, float(_sy), 0.0, -463.3127),
          crs=CRS_SINU_MODIS, fills={"NDVI": -3000.0},
          compress="deflate")
    store.ingest(_extract(os.path.join(hdf_dir,
                                       "MOD13Q1.A2020010.h29v12.hdf")))

    conf_dir = os.path.join(root, "conf")
    os.makedirs(conf_dir)
    config = {
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [{
            "name": "landsat", "title": "synthetic Landsat mosaic",
            "data_source": root,
            "rgb_products": [f"LC08_20200{110 + k}_T1"
                             for k in range(B.N_SCENES)],
            "time_generator": "mas",
        }, {
            "name": "swath", "title": "curvilinear swath",
            "data_source": swath_dir,
            "rgb_products": ["bt"],
            "time_generator": "mas",
        }, {
            "name": "relief", "title": "GMT grid relief",
            "data_source": gmt_dir,
            "rgb_products": ["relief_20200110"],
            "time_generator": "mas",
        }, {
            "name": "modis", "title": "HDF4 sinusoidal NDVI",
            "data_source": hdf_dir,
            "rgb_products": ["NDVI"],
            "time_generator": "mas",
        }],
        "processes": [{
            "identifier": "geometryDrill", "title": "drill",
            "max_area": 100000,
            "data_sources": [{
                "data_source": root,
                "rgb_products": ["LC08_20200110_T1"]}],
            "approx": False,
        }],
    }
    with open(os.path.join(conf_dir, "config.json"), "w") as fp:
        json.dump(config, fp)

    watcher = ConfigWatcher(conf_dir, mas_factory=lambda a: mas_client,
                            install_signal=False)
    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger())

    loop = asyncio.new_event_loop()
    started = threading.Event()
    host_holder = {}

    def run_server():
        asyncio.set_event_loop(loop)
        from aiohttp import web

        async def boot():
            runner = web.AppRunner(server.app())
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            host_holder["host"] = \
                "127.0.0.1:%d" % site._server.sockets[0].getsockname()[1]
            started.set()
        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run_server, daemon=True)
    t.start()
    started.wait(30)
    host = host_holder["host"]

    # GetMap URL grid over the mosaic core (as the soak lays it out)
    span = B.SCENE_SIZE * 30.0
    core = BBox(590000.0 + span * 0.2, 6105000.0 - span * 1.1,
                590000.0 + span * 1.1, 6105000.0 - span * 0.2)
    merc = transform_bbox(transform_bbox(core, utm, EPSG4326),
                          EPSG4326, EPSG3857)
    import math
    grid = max(2, int(math.isqrt(n_tiles)))
    dx, dy = merc.width / grid, merc.height / grid
    urls = []
    for j in range(grid):
        for i in range(grid):
            bb = (f"{merc.xmin + i * dx},{merc.ymin + j * dy},"
                  f"{merc.xmin + (i + 1) * dx},{merc.ymin + (j + 1) * dy}")
            urls.append(
                f"http://{host}/ows?service=WMS&request=GetMap"
                f"&version=1.3.0&layers=landsat&crs=EPSG:3857&bbox={bb}"
                f"&width=256&height=256&format=image/png"
                f"&time=2020-01-10T00:00:00.000Z")

    rc = suite_wms_urls(host, urls, conc)

    # one curvilinear GetMap (geolocation-array warp through the server)
    print("Testing WMS GetMap (curvilinear swath): ", end="", flush=True)
    try:
        status, body = _get(
            f"http://{host}/ows?service=WMS&request=GetMap&version=1.3.0"
            f"&layers=swath&crs=EPSG:4326"
            f"&bbox=-35.28,148.05,-35.17,148.2"
            f"&width=128&height=128&format=image/png"
            f"&time=2020-01-10T00:00:00.000Z")
        ok = status == 200 and body[:8] == b"\x89PNG\r\n\x1a\n" \
            and len(body) > 500
    except Exception as e:  # noqa: BLE001
        ok = False
        print(f"error: {e} ", end="")
    print("Passed" if ok else "Failed")
    if not ok:
        rc = 1

    # one GMT-grid GetMap (registry-dispatched native GMT reader)
    print("Testing WMS GetMap (GMT grid): ", end="", flush=True)
    try:
        status, body = _get(
            f"http://{host}/ows?service=WMS&request=GetMap&version=1.3.0"
            f"&layers=relief&crs=EPSG:4326"
            f"&bbox=-35.8,148.1,-35.2,148.8"
            f"&width=128&height=128&format=image/png"
            f"&time=2020-01-10T00:00:00.000Z")
        ok = status == 200 and body[:8] == b"\x89PNG\r\n\x1a\n" \
            and len(body) > 500
    except Exception as e:  # noqa: BLE001
        ok = False
        print(f"error: {e} ", end="")
    print("Passed" if ok else "Failed")
    if not ok:
        rc = 1

    print("Testing WMS GetMap (HDF4 sinusoidal): ", end="", flush=True)
    try:
        status, body = _get(
            f"http://{host}/ows?service=WMS&request=GetMap&version=1.3.0"
            f"&layers=modis&crs=EPSG:4326"
            f"&bbox=-35.35,148.05,-35.05,148.45"
            f"&width=128&height=128&format=image/png"
            f"&time=2020-01-10T00:00:00.000Z")
        ok = status == 200 and body[:8] == b"\x89PNG\r\n\x1a\n" \
            and len(body) > 500
    except Exception as e:  # noqa: BLE001
        ok = False
        print(f"error: {e} ", end="")
    print("Passed" if ok else "Failed")
    if not ok:
        rc = 1

    # one WCS export
    print("Testing WCS GetCoverage: ", end="", flush=True)
    try:
        status, body = _get(
            f"http://{host}/ows?service=WCS&request=GetCoverage"
            f"&coverage=landsat&crs=EPSG:3857"
            f"&bbox={merc.xmin},{merc.ymin},{merc.xmax},{merc.ymax}"
            f"&width=512&height=512&format=GeoTIFF"
            f"&time=2020-01-10T00:00:00.000Z")
        ok = status == 200 and len(body) > 10000
    except Exception as e:
        print(f"error: {e}")
        ok = False
    print("Passed" if ok else "Failed")
    rc |= 0 if ok else 1

    # one WPS drill over the scene footprint
    print("Testing WPS Execute: ", end="", flush=True)
    ll = transform_bbox(core, utm, EPSG4326)
    cx, cy = (ll.xmin + ll.xmax) / 2, (ll.ymin + ll.ymax) / 2
    d = 0.02
    geojson = json.dumps({"type": "FeatureCollection", "features": [{
        "type": "Feature", "geometry": {
            "type": "Polygon",
            "coordinates": [[[cx - d, cy - d], [cx + d, cy - d],
                             [cx + d, cy + d], [cx - d, cy + d],
                             [cx - d, cy - d]]]}}]})
    payload = (
        '<?xml version="1.0" encoding="UTF-8"?>'
        '<wps:Execute version="1.0.0" service="WPS"'
        ' xmlns:wps="http://www.opengis.net/wps/1.0.0"'
        ' xmlns:ows="http://www.opengis.net/ows/1.1">'
        '<ows:Identifier>geometryDrill</ows:Identifier>'
        '<wps:DataInputs><wps:Input>'
        '<ows:Identifier>geometry</ows:Identifier>'
        '<wps:Data><wps:ComplexData mimeType="application/vnd.geo+json">'
        f'{geojson}'
        '</wps:ComplexData></wps:Data></wps:Input>'
        '</wps:DataInputs></wps:Execute>')
    try:
        status, body = _post(
            f"http://{host}/ows?service=WPS&request=Execute",
            payload.encode())
        ok = status == 200 and b"ExecuteResponse" in body
    except Exception as e:
        print(f"error: {e}")
        ok = False
    print("Passed" if ok else "Failed")
    rc |= 0 if ok else 1

    loop.call_soon_threadsafe(loop.stop)
    return rc


def suite_wms_urls(host: str, urls, conc: int) -> int:
    print("Testing WMS GetCapabilities: ", end="", flush=True)
    if not check_capabilities(host, WMS_CAPS):
        print("Failed")
        return 1
    print("Passed")
    print(f"Testing WMS GetMap Sending {len(urls)} requests: ",
          end="", flush=True)
    failures, elapsed = replay_urls(host, urls, conc)
    if failures:
        print(f"Failed ({len(failures)}/{len(urls)})")
        for f in failures[:10]:
            print("  " + f)
        return 1
    print(f"Passed {elapsed:.2f}s ({len(urls) / elapsed:.1f} req/s)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="gsky-tpu acceptance tests (accept.go port)")
    ap.add_argument("-H", "--host", default="127.0.0.1:8080",
                    help="OWS host:port")
    ap.add_argument("-s", "--suite", default="selftest",
                    choices=("wms", "wps", "selftest"))
    ap.add_argument("-n", "--conc", type=int, default=6,
                    help="concurrency level")
    ap.add_argument("--urls", default="acpt_url.tpl",
                    help="GetMap URL list file (wms suite)")
    ap.add_argument("--payloads", default="polygon_requests/",
                    help="WPS payload dir (wps suite)")
    ap.add_argument("--min-body", type=int, default=10000,
                    help="minimum WPS response size")
    ap.add_argument("--tiles", type=int, default=64,
                    help="GetMap grid size for selftest")
    args = ap.parse_args(argv)

    if args.suite == "wms":
        return suite_wms(args.host, args.urls, args.conc)
    if args.suite == "wps":
        return suite_wps(args.host, args.payloads, args.conc,
                         args.min_body)
    return suite_selftest(args.conc, args.tiles)


if __name__ == "__main__":
    sys.exit(main())
