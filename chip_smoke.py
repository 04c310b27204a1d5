#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on one TPU v5e.

One process holds the chip.  It builds a seeded synthetic archive at a
deployment's sizes, boots the real OWS server through its normal entry
point (`gsky_tpu.server.main.main`, in-process MAS), drives it over
HTTP from client threads — GetCapabilities, GetMap tiles over three
zoom levels on a single-scene nearest layer, a temporal mosaic and an
RGB bilinear composite, one WCS GetCoverage 4096² cubic, one WPS
polygon drill over 1,000 timesteps cold then warm — and checks a few
responses of every verb against the same request computed on the
in-process CPU backend.

It exits non-zero, and prints no result line, when JAX finds no TPU.
It fails if a request failed, a checked response is out of bounds, a
Pallas kernel failed or ran interpreted, prewarm failed, or the device
guard recorded an incident.  The last line of stdout is one JSON
object with exactly these keys, the device as JAX reports it:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
The line before it is the full report as one JSON object (per-verb
legs and failures, kernels, prewarm, compiles, device guard, compile
cache), which also goes to <out>/result.json.

    python chip_smoke.py                       # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal   # tiny, CPU,
                                               # proves the script only
"""

import argparse
import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from collections import namedtuple
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

# --- sizes ------------------------------------------------------------------
# `assumed`: set by us (no network here to fetch a product spec);
# `reduced`: cut from what a deployment holds, with the reason.
SIZES = {
    # assumed: one Landsat-8 OLI scene, 7,601 x 7,761 px int16 at 30 m
    # in UTM (118 MB on disk, 244 MB as the 256-px-bucketed f32 the
    # scene cache keeps in HBM).  No embedded overviews, so every zoom
    # level reads level 1; the 7 band-scenes below are 1.7 GB of the
    # scene cache's byte budget (device.residency_budget).
    "scene_hw": (7601, 7761),
    # >= 4 overlapping scenes on consecutive days, each shifted a third
    # of a scene east and a fifth south (the soak's layout at size)
    "mosaic_scenes": 4,
    # one 3-band scene of the same size (RGB bilinear composite)
    "rgb_bands": 3,
    # 1,000 timesteps (8-day MODIS composites since 2000).  reduced: a
    # MODIS tile is 2,400² (23 GB as f32 x 1,000); 512² x 1,000 f32 =
    # 1.05 GB is the largest stack DrillStackCache keeps on the device
    # (max_item_bytes = 1 GiB)
    "drill_steps": 1000,
    "drill_hw": (512, 512),
    "drill_window": 200,      # polygon edge in pixels
    # distinct 256² EPSG:3857 tiles per layer, by source-px-per-dst-px
    "tiles": {1: 32, 2: 20, 4: 12},
    "wcs_size": 4096,
    "clients": 8,
    # only allocated if the paged leg serves (it does not on a TPU
    # today: pallas_tpu.warp_pallas_enabled)
    "page_pool_mb": 2048,
}
REHEARSAL = dict(SIZES, scene_hw=(700, 720), drill_steps=40,
                 drill_hw=(96, 96), drill_window=40,
                 tiles={1: 3, 2: 2, 4: 1}, wcs_size=512,
                 page_pool_mb=64)

# check bounds: the ones tests_tpu/_onchip_checks.py holds the same
# kernels to (device lowering vs CPU lowering of the same jax code)
BOUND_BYTE_MISMATCH = 0.002     # fused_mosaic_render
BOUND_RGBA_MISMATCH = 0.005     # fused_rgba_render
BOUND_VALID_MISMATCH = 0.001    # warp_* validity
BOUND_CUBIC_ATOL, BOUND_CUBIC_RTOL = 0.05, 1e-5     # warp_cubic
BOUND_DRILL_ABS = 2e-4          # CSV prints 4 decimals of a [0,1] mean

SCENE_RES = 30.0
X0, Y0 = 590000.0, 6105000.0    # EPSG:32755
NODATA = -999
DRILL_NODATA = -9999.0
T_MOSAIC_END = "2020-01-13T00:00:00.000Z"
T_SCENE0 = "2020-01-10T00:00:00.000Z"


def log(msg):
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# --- archive ----------------------------------------------------------------

def build_archive(root, sz, seed):
    """Seeded synthetic archive + its MAS ingest file.  Returns
    (crawl_path, arch): the collection directories plus the drill
    stack the numpy reference needs.  Bands are drawn from spawned
    children of one seeded generator and, like the files, made in
    parallel (numpy and zlib drop the GIL)."""
    import numpy as np

    from gsky_tpu.geo.crs import EPSG4326, parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index.crawler import extract, extract_geotiff
    from gsky_tpu.io import write_geotiff
    from gsky_tpu.io.netcdf import write_netcdf3

    rng = np.random.default_rng(seed)
    utm = parse_crs("EPSG:32755")
    H, W = sz["scene_hw"]
    recs = []

    yy = np.arange(H, dtype=np.float32)
    xx = np.arange(W, dtype=np.float32)

    def band(rng):
        """Imagery-like, not white noise: a smooth field (its phase
        drawn per band, so every scene differs and a wrong mosaic
        winner shows) plus +-2 DN of sensor noise, <= ~11 DN between
        neighbours.  At this scene size the f32 source coordinate
        resolves ~1e-3 px, and the TPU and CPU lowerings of one program
        disagree by that much; on white noise (2,800 DN between
        neighbours) that alone put 1 % of bilinear bytes and 5 DN of
        cubic outside the bounds below (PERF.md "Bring-up")."""
        # python floats, so the float32 axes stay float32 (NEP 50)
        ph = [float(v) for v in rng.uniform(0, 2 * np.pi, 4)]
        wx1, wy1, w2 = 2 * np.pi / 1500, 2 * np.pi / 1100, 2 * np.pi / 500
        f = 1600.0 \
            + 900.0 * np.outer(np.cos(yy * wy1 + ph[0]),
                               np.sin(xx * wx1 + ph[1])) \
            + 200.0 * (np.outer(np.cos(yy * w2 + ph[2]),
                                np.sin(xx * w2 + ph[3]))
                       + np.outer(np.sin(yy * w2 + ph[2]),
                                  np.cos(xx * w2 + ph[3])))
        d = f.astype(np.int16)
        d += rng.integers(-2, 3, (H, W), dtype=np.int16)
        d[: H // 8, : W // 8] = NODATA
        return d

    n_ls, n_rgb = sz["mosaic_scenes"], sz["rgb_bands"]
    with ThreadPoolExecutor(n_ls + n_rgb) as ex:
        bands = list(ex.map(band, rng.spawn(n_ls + n_rgb)))

    ls, rgb, dr = (os.path.join(root, d)
                   for d in ("landsat", "rgb", "drill"))
    for d in (ls, rgb, dr):
        os.makedirs(d)
    jobs = []
    for k in range(n_ls):
        gt = GeoTransform(X0 + k * (W * SCENE_RES // 3), SCENE_RES, 0.0,
                          Y0 - k * (H * SCENE_RES // 5), 0.0, -SCENE_RES)
        # one namespace across the scenes: a temporal mosaic, newest wins
        jobs.append((os.path.join(ls, f"LC08_202001{10 + k:02d}_T1.tif"),
                     bands[k], gt, "nbar"))
    jobs.append((os.path.join(rgb, "S2_20200110_T1.tif"),
                 np.stack(bands[n_ls:]),
                 GeoTransform(X0, SCENE_RES, 0.0, Y0, 0.0, -SCENE_RES),
                 None))

    def write(job):
        p, data, gt, ns = job
        write_geotiff(p, data, gt, utm, nodata=NODATA)
        return extract_geotiff(p, namespace=ns)

    with ThreadPoolExecutor(len(jobs)) as ex:
        recs += list(ex.map(write, jobs))
    del jobs, bands

    T = sz["drill_steps"]
    dh, dw = sz["drill_hw"]
    stack = rng.random((T, dh, dw), dtype=np.float32)
    stack[:, : dh // 16, : dw // 16] = DRILL_NODATA
    xs = 148.0 + (np.arange(dw) + 0.5) * 0.004
    ys = -35.0 - (np.arange(dh) + 0.5) * 0.004
    import datetime as dt
    t0 = dt.datetime(2000, 2, 18, tzinfo=dt.timezone.utc).timestamp()
    p = os.path.join(dr, "frac_cover.nc")
    write_netcdf3(p, {"veg": stack}, xs, ys, EPSG4326,
                  t0 + np.arange(T) * 8 * 86400.0, nodata=DRILL_NODATA)
    recs.append(extract(p))

    for r in recs:
        if r.get("error"):
            raise RuntimeError(f"crawl failed: {r}")
    crawl = os.path.join(root, "crawl.jsonl")
    with open(crawl, "w") as fp:
        for r in recs:
            fp.write(json.dumps(r) + "\n")
    return crawl, {"ls": ls, "rgb": rgb, "drill": dr, "utm": utm,
                   "stack": stack, "xs": xs, "ys": ys}


def write_config(root, arch):
    scale = {"offset_value": 0.0, "clip_value": 3000.0,
             "scale_value": 254.0 / 3000.0, "wms_timeout": 300}
    palette = {"interpolate": True, "colours": [
        {"R": 0, "G": 0, "B": 120, "A": 255},
        {"R": 250, "G": 250, "B": 90, "A": 255}]}
    rgb_ns = [f"S2_20200110_T1_b{b}" for b in (1, 2, 3)]
    conf = os.path.join(root, "conf")
    os.makedirs(conf)
    with open(os.path.join(conf, "config.json"), "w") as fp:
        json.dump({
            "service_config": {"ows_hostname": "", "mas_address": "inproc"},
            "layers": [
                dict(scale, name="single", title="one scene, nearest",
                     data_source=arch["ls"], rgb_products=["nbar"],
                     time_generator="mas", palette=palette),
                dict(scale, name="mosaic", title="temporal mosaic",
                     data_source=arch["ls"], rgb_products=["nbar"],
                     time_generator="mas", accum=True, palette=palette),
                dict(scale, name="rgb", title="RGB bilinear",
                     data_source=arch["rgb"], rgb_products=rgb_ns,
                     time_generator="mas", resample="bilinear"),
                dict(scale, name="cubic", title="one scene, cubic (WCS)",
                     data_source=arch["ls"], rgb_products=["nbar"],
                     time_generator="mas", resample="cubic",
                     wcs_timeout=120),
            ],
            "processes": [{
                "identifier": "geometryDrill", "title": "drill",
                "max_area": 100000, "approx": False,
                "data_sources": [{"data_source": arch["drill"],
                                  "rgb_products": ["veg"],
                                  "wcs_timeout": 600}]}],
        }, fp, indent=1)
    return conf


# --- requests ---------------------------------------------------------------

def tile_bboxes(sz, utm, seed):
    """{zoom factor: [bbox, ...]}: distinct 256-px EPSG:3857 tiles over
    the mosaic core at 1, 2 and 4 source pixels per output pixel."""
    import math

    import numpy as np

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import BBox, transform_bbox

    H, W = sz["scene_hw"]
    sx, sy = W * SCENE_RES, H * SCENE_RES
    core = BBox(X0 + sx * 0.2, Y0 - sy * 1.1, X0 + sx * 1.1, Y0 - sy * 0.2)
    ll = transform_bbox(core, utm, EPSG4326)
    merc = transform_bbox(ll, EPSG4326, EPSG3857)
    lat = math.radians((ll.ymin + ll.ymax) / 2)
    span1 = 256 * SCENE_RES / math.cos(lat)
    rng = np.random.default_rng(seed + 1)
    out = {}
    for f, n in sz["tiles"].items():
        span = span1 * f
        nx = max(1, int(merc.width // span))
        ny = max(1, int(merc.height // span))
        pick = rng.choice(nx * ny, size=min(n, nx * ny), replace=False)
        out[f] = [BBox(merc.xmin + (i % nx) * span,
                       merc.ymin + (i // nx) * span,
                       merc.xmin + (i % nx + 1) * span,
                       merc.ymin + (i // nx + 1) * span)
                  for i in sorted(int(v) for v in pick)]
    return out, merc


def getmap_url(host, layer, bb, t):
    return (f"http://{host}/ows?service=WMS&request=GetMap&version=1.3.0"
            f"&layers={layer}&crs=EPSG:3857"
            f"&bbox={bb.xmin!r},{bb.ymin!r},{bb.xmax!r},{bb.ymax!r}"
            f"&width=256&height=256&format=image/png&time={t}")


# one response; `ok` is filled in by the verb that knows what to expect
Resp = namedtuple("Resp", "status body seconds sheds ok", defaults=(None,))


def http(url, data=None, timeout=900):
    """One request.  A 503 that carries Retry-After is admission
    control shedding load (docs/SERVING.md): like any OGC client this
    one waits and asks again, and counts it in `sheds`."""
    t0 = time.perf_counter()
    sheds = 0
    while True:
        try:
            req = urllib.request.Request(url, data=data)
            if data is not None:
                req.add_header("Content-Type", "text/xml")
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return Resp(r.status, r.read(),
                            time.perf_counter() - t0, sheds)
        except urllib.error.HTTPError as e:
            wait = e.headers.get("Retry-After")
            if e.code == 503 and wait and sheds < 5:
                sheds += 1
                time.sleep(min(float(wait), 5.0))
                continue
            return Resp(e.code, e.read(), time.perf_counter() - t0, sheds)
        except (OSError, urllib.error.URLError) as e:
            return Resp(0, str(e).encode(), time.perf_counter() - t0, sheds)


def wps_payload(arch, sz):
    """Execute body for a rectangle whose edges run through pixel
    centres, so the all-touched burn is exactly rows r0..r1 x cols
    c0..c1 and a plain numpy mean is an unambiguous reference."""
    dh, dw = sz["drill_hw"]
    n = sz["drill_window"]
    r0, c0 = dh // 4, dw // 4
    r1, c1 = r0 + n - 1, c0 + n - 1
    xs, ys = arch["xs"], arch["ys"]
    ring = [[xs[c0], ys[r1]], [xs[c1], ys[r1]], [xs[c1], ys[r0]],
            [xs[c0], ys[r0]], [xs[c0], ys[r1]]]
    gj = json.dumps({"type": "FeatureCollection", "features": [{
        "type": "Feature", "geometry": {
            "type": "Polygon",
            "coordinates": [[[float(x), float(y)] for x, y in ring]]}}]})
    body = (
        '<?xml version="1.0" encoding="UTF-8"?>'
        '<wps:Execute version="1.0.0" service="WPS"'
        ' xmlns:wps="http://www.opengis.net/wps/1.0.0"'
        ' xmlns:ows="http://www.opengis.net/ows/1.1">'
        '<ows:Identifier>geometryDrill</ows:Identifier>'
        '<wps:DataInputs><wps:Input>'
        '<ows:Identifier>geometry</ows:Identifier>'
        '<wps:Data><wps:ComplexData mimeType="application/vnd.geo+json">'
        f'{gj}</wps:ComplexData></wps:Data></wps:Input>'
        '</wps:DataInputs></wps:Execute>')
    return body.encode(), (r0, r1, c0, c1)


def legs_delta(before, after):
    """executor.dispatches delta, summed by leg name (the part of the
    counter key before the shape bucket)."""
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0)
        if d:
            leg = k.split(":", 1)[0]
            out[leg] = out.get(leg, 0) + d
    return out


# --- reference checks (CPU backend, same process) ---------------------------

class Reference:
    """The same requests through the same library entry points, on
    `jax.devices("cpu")[0]`, from a private executor and scene cache —
    what tests_tpu/_onchip_checks.py does for single kernels."""

    def __init__(self, crawl):
        from gsky_tpu.index import MASClient, MASStore
        from gsky_tpu.index.api import ingest_file
        from gsky_tpu.pipeline.executor import WarpExecutor
        from gsky_tpu.pipeline.scene_cache import SceneCache
        from gsky_tpu.pipeline.tile import TilePipeline
        store = MASStore()
        ingest_file(store, crawl)
        self.ex = WarpExecutor()
        self.cache = SceneCache(max_bytes=4 << 30)
        self.pipe = TilePipeline(MASClient(store), executor=self.ex)

    def __enter__(self):
        import jax
        self._prev = jax.config.jax_default_device
        # process-wide, not the thread-local context manager: the
        # executor dispatches on device-guard threads
        jax.config.update("jax_default_device", jax.devices("cpu")[0])
        return self

    def __exit__(self, *exc):
        import jax
        jax.config.update("jax_default_device", self._prev)

    def _req(self, arch, layer, bb, hw, resample):
        from gsky_tpu.geo.crs import EPSG3857
        from gsky_tpu.index.store import parse_time
        from gsky_tpu.pipeline import GeoTileRequest
        if layer == "mosaic":
            t0, t1 = parse_time(T_SCENE0), parse_time(T_MOSAIC_END)
        else:
            t0, t1 = parse_time(T_SCENE0), None
        bands = [f"S2_20200110_T1_b{b}" for b in (1, 2, 3)] \
            if layer == "rgb" else ["nbar"]
        return GeoTileRequest(
            collection=arch["rgb"] if layer == "rgb" else arch["ls"],
            bands=bands, bbox=bb, crs=EPSG3857, width=hw[1],
            height=hw[0], start_time=t0, end_time=t1, resample=resample)

    SCALE = (0.0, 254.0 / 3000.0, 3000.0, 0, False)

    def tile(self, arch, layer, bb):
        """The tile's bytes, or None when the index holds no granule
        under it (the server answers those with an empty tile)."""
        import numpy as np
        if layer == "rgb":
            req = self._req(arch, layer, bb, (256, 256), "bilinear")
            made = self.pipe._bands_prep(req, n_bands=3)
            if made is None:
                return None
            granules, ns_index, out_sel = made
            ns_ids, prio = self.pipe._ns_prios(granules, ns_index)
            out = self.ex.render_rgba_byte(
                granules, ns_ids, prio, out_sel, req.dst_gt(), req.crs,
                256, 256,
                req.resample, *self.SCALE, cache=self.cache)
        else:
            req = self._req(arch, layer, bb, (256, 256), "near")
            made = self.pipe.composite_prep(req)
            if made is None:
                return None
            granules, ns_ids, prio, n_ns = made
            out = self.ex.render_byte_scenes(
                granules, ns_ids, prio, req.dst_gt(), req.crs, 256, 256,
                n_ns, req.resample, *self.SCALE, cache=self.cache)
        return np.asarray(out)

    def coverage_block(self, arch, bb, hw):
        import numpy as np

        from gsky_tpu.pipeline.tile import ns_prio
        req = self._req(arch, "cubic", bb, hw, "cubic")
        gs = self.pipe.index(req)
        names, ns_ids, prio = ns_prio(gs)
        canv, ok = self.ex.warp_mosaic_scenes(
            gs, ns_ids, prio, req.dst_gt(), req.crs, hw[0], hw[1],
            len(names), "cubic", cache=self.cache)
        return np.asarray(canv[0]), np.asarray(ok[0])


# --- the drive --------------------------------------------------------------

class Smoke:
    def __init__(self, sz, seed, arch, crawl, rehearsal):
        self.sz, self.seed, self.arch, self.crawl = sz, seed, arch, crawl
        self.rehearsal = rehearsal
        self.result = {"verbs": {}, "checks": {}, "seconds": {}}
        self.problems = []

    def fail(self, msg):
        log("PROBLEM: " + msg)
        self.problems.append(msg)

    def debug(self):
        r = http(f"http://{self.host}/debug")
        if r.status != 200:
            raise RuntimeError(
                f"/debug answered {r.status}: {r.body[:200]!r}")
        return json.loads(r.body)

    def verb(self, name, thunk):
        """Run one verb's requests; record count, failures, the legs
        that served them and the slowest request."""
        d0 = self.debug()
        t0 = time.perf_counter()
        outs = thunk()
        d1 = self.debug()
        bad = [r for r in outs if not r.ok]
        for r in bad[:3]:
            self.fail(f"{name}: request failed ({r.status}): "
                      f"{r.body[-300:]!r}")
        self.result["verbs"][name] = {
            "requests": len(outs), "failures": len(bad),
            "shed_then_served": sum(r.sheds for r in outs),
            "legs": legs_delta(d0["executor"]["dispatches"],
                               d1["executor"]["dispatches"]),
            "fresh_compiles": d1["jax"]["compiles"] - d0["jax"]["compiles"],
            "slowest_request_s": round(max(r.seconds for r in outs), 3),
            "wall_s": round(time.perf_counter() - t0, 3)}
        log(f"{name}: {self.result['verbs'][name]}")
        return outs

    # aiohttp's run_app signature: main() calls this instead
    def run_app(self, app, host=None, port=None, **kw):
        import asyncio

        from aiohttp import web
        loop = asyncio.new_event_loop()
        started = threading.Event()
        runner = web.AppRunner(
            app, handler_cancellation=kw.get("handler_cancellation", True))

        def serve():
            asyncio.set_event_loop(loop)

            async def boot():
                await runner.setup()
                site = web.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                self.host = "127.0.0.1:%d" % \
                    site._server.sockets[0].getsockname()[1]
                started.set()
            loop.run_until_complete(boot())
            loop.run_forever()

        th = threading.Thread(target=serve, daemon=True, name="smoke-ows")
        th.start()
        if not started.wait(60):
            raise RuntimeError("server did not start listening")
        log(f"gsky-ows serving on {self.host}")
        try:
            self.drive()
        finally:
            # the same graceful drain SIGTERM triggers under run_app
            asyncio.run_coroutine_threadsafe(
                runner.cleanup(), loop).result(120)
            loop.call_soon_threadsafe(loop.stop)
            th.join(30)

    def drive(self):
        import numpy as np
        from PIL import Image

        sz, arch = self.sz, self.arch
        boot = self.debug()
        self.result["prewarm"] = boot.get("prewarm")
        self.result["compiles_at_boot"] = boot["jax"]["compiles"]
        pool = ThreadPoolExecutor(sz["clients"])

        def png(url):
            r = http(url)
            return r._replace(ok=r.status == 200
                              and r.body[:8] == b"\x89PNG\r\n\x1a\n")

        # GetCapabilities
        def caps():
            r = http(
                f"http://{self.host}/ows?service=WMS&request=GetCapabilities")
            return [r._replace(ok=r.status == 200 and all(
                f"<Name>{n}</Name>".encode() in r.body
                for n in ("single", "mosaic", "rgb", "cubic")))]
        self.verb("GetCapabilities", caps)

        # GetMap: three layers x three zoom levels of distinct tiles
        boxes, merc = tile_bboxes(sz, arch["utm"], self.seed)
        flat = [(f, bb) for f, bbs in boxes.items() for bb in bbs]
        served = {}
        for layer, t in (("single", T_SCENE0), ("mosaic", T_MOSAIC_END),
                         ("rgb", T_SCENE0)):
            urls = [getmap_url(self.host, layer, bb, t) for _, bb in flat]
            outs = self.verb(f"GetMap:{layer}",
                             lambda urls=urls: list(pool.map(png, urls)))
            # tile index in `flat` -> served bytes
            served[layer] = {i: r.body for i, r in enumerate(outs) if r.ok}
            self.result["verbs"][f"GetMap:{layer}"]["zoom_levels"] = \
                {str(f): len(bbs) for f, bbs in boxes.items()}

        # WCS GetCoverage, cubic
        n = sz["wcs_size"]
        wcs_url = (
            f"http://{self.host}/ows?service=WCS&request=GetCoverage"
            f"&coverage=cubic&crs=EPSG:3857"
            f"&bbox={merc.xmin!r},{merc.ymin!r},{merc.xmax!r},{merc.ymax!r}"
            f"&width={n}&height={n}&format=GeoTIFF&time={T_SCENE0}")

        def wcs():
            r = http(wcs_url)
            return [r._replace(ok=r.status == 200 and len(r.body) > n * n)]
        cov = self.verb("GetCoverage:cubic", wcs)[0]

        # WPS drill: cold (host reads while the stack uploads), then
        # warm from the device-resident stack
        payload, rect = wps_payload(arch, sz)
        wps_url = f"http://{self.host}/ows?service=WPS&request=Execute"

        def wps():
            r = http(wps_url, payload)
            return [r._replace(ok=r.status == 200
                               and b"ExecuteResponse" in r.body)]
        cold = self.verb("Execute:cold", wps)[0]
        deadline = time.time() + 600
        while True:
            warm = self.verb("Execute:warm", wps)[0]
            legs = self.result["verbs"]["Execute:warm"]["legs"]
            if legs.get("drill_device") or time.time() > deadline:
                break
            time.sleep(2.0)     # the background stack upload is landing
        if not self.result["verbs"]["Execute:warm"]["legs"].get(
                "drill_device"):
            self.fail("warm drill was not answered from the device stack")

        # --- checks against the CPU backend -------------------------------
        t0 = time.perf_counter()
        with Reference(self.crawl) as ref:
            for layer in ("single", "mosaic", "rgb"):
                res = []
                done = set()    # zoom levels with a mostly-data check
                for i, (f, bb) in enumerate(flat):
                    if f in done or i not in served[layer]:
                        continue
                    want = ref.tile(arch, layer, bb)
                    if want is None:
                        continue        # no granule under this tile
                    got = np.asarray(
                        Image.open(io.BytesIO(served[layer][i])))
                    if got.shape != want.shape:
                        self.fail(f"check {layer} z{f}: shape {got.shape}"
                                  f" vs {want.shape}")
                        continue
                    mism = float(np.mean(got != want))
                    bound = BOUND_RGBA_MISMATCH if layer == "rgb" \
                        else BOUND_BYTE_MISMATCH
                    # 255 is the nodata byte / alpha 0: a tile must
                    # carry data for the comparison to mean anything,
                    # so each zoom level is checked until one does
                    data = float(np.mean(
                        want[..., 3] > 0 if layer == "rgb" else want != 255))
                    res.append({"zoom": f, "mismatch": mism,
                                "data_fraction": round(data, 4)})
                    if data > 0.5:
                        done.add(f)
                    if mism > bound:
                        self.fail(f"check {layer} z{f}: byte mismatch "
                                  f"{mism:.4%} > {bound:.2%}")
                if len(done) < len(boxes) and not self.rehearsal:
                    self.fail(f"check {layer}: mostly-data tiles checked "
                              f"at zoom levels {sorted(done)} only")
                self.result["checks"][f"GetMap:{layer}"] = res

            if cov.ok:
                self.check_coverage(ref, cov.body, merc, n)
        if cold.ok and warm.ok:
            self.check_drill(cold.body, warm.body, rect)
        self.result["seconds"]["checks"] = round(time.perf_counter() - t0, 1)

        # --- the process's own account of itself ---------------------------
        end = self.debug()
        self.result["fresh_compiles_serving"] = \
            end["jax"]["compiles"] - boot["jax"]["compiles"]
        self.result["jax"] = end["jax"]
        k = end.get("kernels", {})
        self.result["kernels"] = {
            "lowered": k.get("lowered"), "failed": k.get("failed"),
            "demoted": k.get("demoted"), "promoted": k.get("promoted"),
            "warp_pallas_enabled": k.get("warp_pallas_enabled"),
            "ledger_path": k.get("ledger_path"),
            "ledger": {name: {v: e[v] for v in
                              ("promoted", "demoted", "failed")}
                       | {"entries": e["entries"]}
                       for name, e in k.get("kernels", {}).items()}}
        self.result["executor"] = {
            "dispatches": end["executor"]["dispatches"],
            "gather_window": {w: end["executor"]["gather_window"][w]
                              for w in ("engaged", "declined")},
            "paged": {w: end["executor"]["paged"][w]
                      for w in ("engaged", "declined")}}
        dev = end.get("device", {})
        self.result["device_guard"] = {
            w: dev.get(w) for w in ("state", "hangs", "crashes", "ooms",
                                    "corruptions", "reinits",
                                    "hang_deadline_s", "incidents")}
        self.result["hbm"] = {"scene_cache_bytes": end.get("scene_cache_bytes"),
                              "drill_cache_bytes": end.get("drill_cache_bytes")}

        if k.get("failed"):
            self.fail(f"failed kernels: {k['failed']}")
        for name, e in k.get("kernels", {}).items():
            if e["failed"]:
                self.fail(f"ledger holds a failed verdict for {name}")
        interp = [name for name, modes in (k.get("lowered") or {}).items()
                  if "interpret" in modes]
        if interp:
            self.fail(f"kernels ran interpreted: {interp}")
        if any(dev.get(w) for w in ("hangs", "crashes", "ooms",
                                    "corruptions", "reinits")):
            self.fail(f"device guard incidents: {self.result['device_guard']}")
        if end["executor"]["dispatches"].get("drill_device_error"):
            self.fail("the drill's device path raised (see log)")
        pw = self.result["prewarm"]
        if not pw or pw.get("failures"):
            self.fail(f"prewarm: {pw}")
        pool.shutdown()

    def check_coverage(self, ref, body, merc, n):
        import numpy as np

        from gsky_tpu.geo.transform import split_bbox
        from gsky_tpu.io.geotiff import GeoTIFF
        with tempfile.NamedTemporaryFile(suffix=".tif") as fp:
            fp.write(body)
            fp.flush()
            with GeoTIFF(fp.name) as g:
                if (g.height, g.width) != (n, n):
                    self.fail(f"coverage is {g.height}x{g.width}")
                    return
                got = g.read(1)
        tiles = split_bbox(merc, n, n, 1024, 1024)
        res = []
        # first and last block of the export's own 1024² split
        for tb, ox, oy, tw, th in (tiles[0], tiles[-1]):
            want, ok = ref.coverage_block(self.arch, tb, (th, tw))
            blk = got[oy:oy + th, ox:ox + tw]
            gok = blk != -9999.0
            vm = float(np.mean(gok != ok))
            both = gok & ok
            err = np.abs(blk[both] - want[both])
            worst = float(err.max()) if err.size else 0.0
            bad = float(np.mean(err > BOUND_CUBIC_ATOL
                                + BOUND_CUBIC_RTOL * np.abs(want[both]))) \
                if err.size else 0.0
            res.append({"block": [ox, oy, tw, th],
                        "validity_mismatch": vm, "max_abs_err": worst,
                        "out_of_bound_fraction": bad,
                        "data_fraction": round(float(np.mean(ok)), 4)})
            if vm > BOUND_VALID_MISMATCH:
                self.fail(f"coverage block {ox},{oy}: validity mismatch "
                          f"{vm:.4%}")
            if bad > 0:
                self.fail(f"coverage block {ox},{oy}: {bad:.4%} of values "
                          f"off by more than atol {BOUND_CUBIC_ATOL} "
                          f"(worst {worst:.4g})")
        if not any(r["data_fraction"] > 0.5 for r in res):
            self.fail("coverage: no checked block is mostly data")
        self.result["checks"]["GetCoverage:cubic"] = res

    def check_drill(self, cold, warm, rect):
        import re

        import numpy as np
        r0, r1, c0, c1 = rect
        win = self.arch["stack"][:, r0:r1 + 1, c0:c1 + 1]
        ok = win != DRILL_NODATA
        want = np.where(ok, win, 0).reshape(len(win), -1).sum(-1) \
            / np.maximum(ok.reshape(len(win), -1).sum(-1), 1)
        out = {}
        for name, body in (("cold", cold), ("warm", warm)):
            rows = re.findall(rb"(\d{4}-\d\d-\d\d),([-0-9.eE]+)", body)
            got = np.array([float(v) for _, v in rows])
            if got.shape != want.shape:
                self.fail(f"drill {name}: {got.shape[0]} rows, want "
                          f"{want.shape[0]}")
                continue
            err = float(np.abs(got - want).max())
            out[name] = {"rows": int(got.shape[0]), "max_abs_err": err}
            if err > BOUND_DRILL_ABS:
                self.fail(f"drill {name}: max abs error {err:.3g} > "
                          f"{BOUND_DRILL_ABS}")
        self.result["checks"]["Execute"] = out


def cache_entries(path):
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on JAX_PLATFORMS=cpu: proves the "
                         "script, says nothing about the chip")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args(argv)
    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.rehearsal != on_cpu:
        print("chip_smoke: platform cpu is for --rehearsal only, and "
              "--rehearsal needs JAX_PLATFORMS=cpu "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})",
              file=sys.stderr)
        return 2
    native_dir = os.path.join(ROOT, "gsky_tpu", "native")
    if not os.path.isdir(native_dir):
        print(f"chip_smoke: {native_dir} is missing; this script drives "
              "the gsky_tpu checkout it sits in", file=sys.stderr)
        return 2
    sz = REHEARSAL if args.rehearsal else SIZES
    t_start = time.perf_counter()

    # everything the run writes goes to the output directory, not the
    # system temp dir; the ledger is read at import, so set it first
    os.makedirs(args.out, exist_ok=True)
    os.environ["GSKY_KERNEL_LEDGER"] = os.path.join(
        args.out, "kernel_ledger.jsonl")
    os.environ["GSKY_POOL_JOURNAL"] = os.path.join(
        args.out, "pool_journal.jsonl")
    os.environ["GSKY_PAGE_POOL_MB"] = str(sz["page_pool_mb"])
    for stale in ("GSKY_KERNEL_LEDGER", "GSKY_POOL_JOURNAL"):
        if os.path.exists(os.environ[stale]):
            os.unlink(os.environ[stale])

    # built from what git commits: *.so is ignored, and without it the
    # IO layer silently decodes in pure Python
    if subprocess.run(["make", "-C", native_dir],
                      stdout=sys.stderr).returncode:
        print("chip_smoke: building libgskycodec.so failed",
              file=sys.stderr)
        return 1
    from gsky_tpu import native
    if native._lib is None:
        print("chip_smoke: libgskycodec.so did not load", file=sys.stderr)
        return 1

    # this process takes the chip, here, before anything is built
    from gsky_tpu.device import PlatformError, ensure_platform
    try:
        plat = ensure_platform()
    except PlatformError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if plat["platform"] != ("cpu" if args.rehearsal else "tpu"):
        print(f"chip_smoke: platform {plat['platform']!r}", file=sys.stderr)
        return 2
    log(f"platform {plat}")
    cache0 = cache_entries(plat["cache_dir"])

    import logging
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    root = tempfile.mkdtemp(prefix="gsky_smoke_")
    try:
        t0 = time.perf_counter()
        crawl, arch = build_archive(root, sz, args.seed)
        conf = write_config(root, arch)
        t_archive = round(time.perf_counter() - t0, 1)
        log(f"archive built in {t_archive}s under {root}")

        smoke = Smoke(sz, args.seed, arch, crawl, args.rehearsal)
        from gsky_tpu.server.main import main as ows_main
        t0 = time.perf_counter()
        rc = ows_main(["-conf", conf, "-local_mas", crawl,
                       "-log_dir", args.out, "-temp_dir", root],
                      run_app=smoke.run_app)
        if rc:
            smoke.fail(f"gsky-ows exited {rc}")
        res = smoke.result
        res["seconds"].update(
            archive=t_archive,
            boot_and_drive=round(time.perf_counter() - t0, 1),
            total=round(time.perf_counter() - t_start, 1))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    import importlib.metadata as md

    import jax
    res["versions"] = {p: md.version(p)
                       for p in ("jax", "jaxlib", "libtpu", "numpy")}
    res["sizes"] = sz
    res["seed"] = args.seed
    res["rehearsal"] = args.rehearsal
    res["native_codec"] = native._lib is not None
    res["compile_cache"] = {"dir": plat["cache_dir"],
                            "entries_before": cache0,
                            "entries_after":
                                cache_entries(plat["cache_dir"])}
    res["problems"] = smoke.problems
    verdict = {"ok": not smoke.problems,
               "device": {"platform": jax.devices()[0].platform,
                          "kind": jax.devices()[0].device_kind,
                          "count": len(jax.devices())}}
    report = dict(verdict, **res)
    with open(os.path.join(args.out, "result.json"), "w") as fp:
        json.dump(report, fp, indent=1)
    # the full report, then -- last, and with these keys only -- the
    # line the driver reads
    print(json.dumps(report), flush=True)
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
