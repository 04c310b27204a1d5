"""Benchmark: the five BASELINE.md configs, end-to-end, vs a MEASURED
CPU baseline.

Configs (BASELINE.md "Benchmark configs"):
  1. single-band Landsat-style GeoTIFF -> 256x256 WMS GetMap,
     EPSG:3857, nearest                                  [tiles/sec]
  2. 3-band Sentinel-2-style true-colour RGB composite,
     bilinear                                            [tiles/sec]
  3. multi-granule temporal mosaic over overlapping
     scenes (tile_merger path)                           [tiles/sec]
  4. WCS GetCoverage 4096x4096 reproject, nodata mask,
     cubic                                               [seconds]
  5. WPS drill: polygon time-series over a
     1000-timestep NetCDF stack                          [seconds]

Each runs the full pipeline: MAS index query, decode, batched TPU warp,
newest-wins mosaic, scaling, PNG/GeoTIFF encode.  The baseline is the
SAME workload measured on this repo's own CPU path (in a subprocess with
the accelerator disabled) — not the reference's 0.515 s log anecdote;
`vs_baseline` is the ratio against that measured CPU number (for the
time-valued configs 4/5, baseline_s / measured_s, so >1 is faster).
The bench process takes the chip; without a TPU it exits non-zero
unless JAX_PLATFORMS=cpu asks for the CPU, and every result names
`platform`, `device_kind` and the device count it ran on.

Prints ONE JSON line; headline metric = config 3 (mosaic GetMap).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REF_TILE_SECONDS = 0.515357769  # metrics/log_format.md:28-33 (anecdote)

N_SCENES = 4
SCENE_SIZE = 1536        # 1536x1536 int16 per scene, 30 m pixels
GRID = 8                 # 8x8 = 64 tiles of 256x256
CONCURRENCY = 8          # request-level concurrency (SURVEY §2.8 P1)
DRILL_STEPS = 1000


# ---------------------------------------------------------------------------
# synthetic archives
# ---------------------------------------------------------------------------

def build_archive(root):
    """Overlapping single-band Landsat-style UTM scenes (configs 1/3/4)."""
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index import MASStore
    from gsky_tpu.index.crawler import extract
    from gsky_tpu.io import write_geotiff

    utm = parse_crs("EPSG:32755")
    rng = np.random.default_rng(42)
    paths = []
    for i in range(N_SCENES):
        gt = GeoTransform(590000.0 + i * SCENE_SIZE * 30 // 3, 30.0, 0.0,
                          6105000.0 - i * SCENE_SIZE * 30 // 5, 0.0, -30.0)
        data = rng.uniform(200, 3000, (SCENE_SIZE, SCENE_SIZE)).astype(
            np.int16)
        data[: SCENE_SIZE // 8, : SCENE_SIZE // 8] = -999
        date = f"2020-01-{10 + i:02d}"
        p = os.path.join(root, f"LC08_{date.replace('-', '')}_T1.tif")
        write_geotiff(p, data, gt, utm, nodata=-999)
        paths.append(p)
    store = MASStore()
    for p in paths:
        rec = extract(p)
        assert not rec.get("error"), rec
        store.ingest(rec)
    return store, utm, paths


def build_rgb_archive(root):
    """One 3-band Sentinel-2-style true-colour scene (config 2)."""
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index import MASStore
    from gsky_tpu.index.crawler import extract
    from gsky_tpu.io import write_geotiff

    utm = parse_crs("EPSG:32755")
    rng = np.random.default_rng(7)
    gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
    rgb = rng.uniform(200, 3000,
                      (3, SCENE_SIZE, SCENE_SIZE)).astype(np.int16)
    rgb[:, : SCENE_SIZE // 8, : SCENE_SIZE // 8] = -999
    p = os.path.join(root, "S2_20200110_T1.tif")
    write_geotiff(p, rgb, gt, utm, nodata=-999)
    store = MASStore()
    rec = extract(p)
    assert not rec.get("error"), rec
    store.ingest(rec)
    return store, utm, p


def build_drill_archive(root, name: str = "veg_stack.nc", seed: int = 3):
    """1000-timestep NetCDF stack in EPSG:4326 (config 5)."""
    import datetime as dt

    from gsky_tpu.geo.crs import EPSG4326
    from gsky_tpu.index import MASStore
    from gsky_tpu.index.crawler import extract
    from gsky_tpu.io.netcdf import write_netcdf3

    H = W = 128
    rng = np.random.default_rng(seed)
    data = rng.uniform(0.0, 1.0, (DRILL_STEPS, H, W)).astype(np.float32)
    data[:, :8, :8] = -9999.0
    xs = 148.0 + (np.arange(W) + 0.5) * 0.004
    ys = -35.0 - (np.arange(H) + 0.5) * 0.004
    t0 = dt.datetime(2015, 1, 1, tzinfo=dt.timezone.utc).timestamp()
    times = t0 + np.arange(DRILL_STEPS) * 86400.0
    p = os.path.join(root, name)
    write_netcdf3(p, {"veg": data}, xs, ys, EPSG4326, times,
                  nodata=-9999.0)
    store = MASStore()
    rec = extract(p)
    assert not rec.get("error"), rec
    store.ingest(rec)
    return store, p, t0


# ---------------------------------------------------------------------------
# config harnesses
# ---------------------------------------------------------------------------

def _tile_grid(utm):
    """EPSG:3857 tile grid over the mosaic core."""
    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import BBox, transform_bbox

    span = SCENE_SIZE * 30.0
    core = BBox(590000.0 + span * 0.2, 6105000.0 - span * 1.1,
                590000.0 + span * 1.1, 6105000.0 - span * 0.2)
    ll = transform_bbox(core, utm, EPSG4326)
    merc = transform_bbox(ll, EPSG4326, EPSG3857)
    dx = merc.width / GRID
    dy = merc.height / GRID
    return merc, dx, dy


def _timed_tiles(render, reqs):
    """Warm-up pass (compiles every shape bucket) + timed steady-state
    pass at request concurrency.  Returns (tiles/sec, elapsed,
    {p50_ms, p99_ms, max_ms}) — the per-tile latency percentiles of
    BASELINE.md's metric, measured per request under concurrency."""
    with ThreadPoolExecutor(CONCURRENCY) as ex:
        list(ex.map(render, reqs))
    lat = []
    lock = threading.Lock()

    def timed(req):
        t0 = time.perf_counter()
        out = render(req)
        dt = time.perf_counter() - t0
        with lock:
            lat.append(dt)
        return out

    start = time.time()
    with ThreadPoolExecutor(CONCURRENCY) as ex:
        outs = list(ex.map(timed, reqs))
    elapsed = time.time() - start
    assert all(o is not None and len(o) > 100 for o in outs)
    lat.sort()

    def pct(p):
        return lat[min(int(len(lat) * p), len(lat) - 1)]

    latency = {"p50_ms": round(pct(0.5) * 1e3, 1),
               "p99_ms": round(pct(0.99) * 1e3, 1),
               "max_ms": round(lat[-1] * 1e3, 1)}
    return len(reqs) / elapsed, elapsed, latency


def _grid_reqs(utm, collection, bands, t0_day, t1_day, resample="near"):
    """The shared 8x8 GetMap request grid over the mosaic core."""
    import datetime as dt

    from gsky_tpu.geo.crs import EPSG3857
    from gsky_tpu.geo.transform import BBox
    from gsky_tpu.pipeline import GeoTileRequest

    merc, dx, dy = _tile_grid(utm)
    t0 = dt.datetime(2020, 1, t0_day, tzinfo=dt.timezone.utc).timestamp()
    t1 = dt.datetime(2020, 1, t1_day, tzinfo=dt.timezone.utc).timestamp()
    return [GeoTileRequest(
                collection=collection, bands=list(bands),
                bbox=BBox(merc.xmin + i * dx, merc.ymin + j * dy,
                          merc.xmin + (i + 1) * dx,
                          merc.ymin + (j + 1) * dy),
                crs=EPSG3857, width=256, height=256,
                start_time=t0, end_time=t1, resample=resample)
            for j in range(GRID) for i in range(GRID)]


def _palette_render(pipe, colours):
    """Fused composite GetMap -> palette PNG, with the modular-path
    fallback — the WMS handler's dataflow."""
    import jax.numpy as jnp

    from gsky_tpu.io.png import encode_png
    from gsky_tpu.ops.palette import gradient_palette, with_nodata_entry
    from gsky_tpu.ops.scale import compose_scale_byte

    lut = with_nodata_entry(gradient_palette(colours))

    def render(req):
        sb = pipe.render_composite_byte(req, auto=True)
        if sb is None:
            res = pipe.process(req)
            bands = [jnp.asarray(res.data[n]) for n in res.namespaces
                     if n in res.data]
            valids = [jnp.asarray(res.valid[n]) for n in res.namespaces
                      if n in res.valid]
            sb = compose_scale_byte(jnp.stack(bands), jnp.stack(valids),
                                    auto=True)
        return encode_png([np.asarray(sb)], lut)

    return render


def bench_cfg1_single_nearest(store, utm, tmp):
    """Config 1: single-band single-scene GetMap, nearest."""
    from gsky_tpu.index import MASClient
    from gsky_tpu.pipeline import TilePipeline

    pipe = TilePipeline(MASClient(store))
    render = _palette_render(pipe, [(0, 0, 120, 255), (250, 250, 90, 255)])
    reqs = _grid_reqs(utm, tmp, ["LC08_20200110_T1"], 9, 11)
    tps, elapsed, latency = _timed_tiles(render, reqs)
    return {"value": round(tps, 2), "unit": "tiles/sec",
            "tiles": len(reqs), "elapsed_s": round(elapsed, 3),
            "latency": latency}


def bench_cfg2_rgb_bilinear(tmp_rgb):
    """Config 2: 3-band RGB composite, bilinear."""
    from gsky_tpu.index import MASClient
    from gsky_tpu.io.png import encode_png, encode_rgba_png
    from gsky_tpu.pipeline import TilePipeline

    store, utm, _ = build_rgb_archive(tmp_rgb)
    pipe = TilePipeline(MASClient(store))
    bands = [f"S2_20200110_T1_b{k}" for k in (1, 2, 3)]

    def render(req):
        # the WMS handler's RGB ladder (one index pass)
        made = pipe.render_rgb_auto(req, auto=True)
        if made is None:
            return None
        kind, dev = made
        a = np.asarray(dev)
        if kind == "rgba":
            return encode_rgba_png(a)
        return encode_png([a[0], a[1], a[2]])

    reqs = _grid_reqs(utm, tmp_rgb, bands, 9, 11, resample="bilinear")
    tps, elapsed, latency = _timed_tiles(render, reqs)
    return {"value": round(tps, 2), "unit": "tiles/sec",
            "tiles": len(reqs), "elapsed_s": round(elapsed, 3),
            "latency": latency}


def bench_cfg3_mosaic(store, utm, tmp):
    """Config 3 (headline): multi-granule temporal mosaic GetMap."""
    from gsky_tpu.index import MASClient
    from gsky_tpu.pipeline import TilePipeline

    pipe = TilePipeline(MASClient(store))
    render = _palette_render(
        pipe, [(0, 0, 120, 255), (0, 180, 60, 255), (250, 250, 90, 255),
               (180, 40, 10, 255)])
    reqs = _grid_reqs(
        utm, tmp, [f"LC08_20200{110 + k}_T1" for k in range(N_SCENES)],
        9, 15)
    tps, elapsed, latency = _timed_tiles(render, reqs)
    return {"value": round(tps, 2), "unit": "tiles/sec",
            "tiles": len(reqs), "elapsed_s": round(elapsed, 3),
            "latency": latency}


def bench_cfg4_wcs_cubic(store, utm, tmp):
    """Config 4: WCS GetCoverage 4096x4096, cubic + nodata mask, tiled
    1024^2 (the reference's WcsMaxTileWidth/Height), GeoTIFF output."""
    import datetime as dt

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import (BBox, GeoTransform, split_bbox,
                                        transform_bbox)
    from gsky_tpu.index import MASClient
    from gsky_tpu.io import write_geotiff
    from gsky_tpu.pipeline import GeoTileRequest, TilePipeline

    pipe = TilePipeline(MASClient(store))
    size = 4096
    span = SCENE_SIZE * 30.0
    core = BBox(590000.0 + span * 0.1, 6105000.0 - span * 1.2,
                590000.0 + span * 1.2, 6105000.0 - span * 0.1)
    merc = transform_bbox(transform_bbox(core, utm, EPSG4326),
                          EPSG4326, EPSG3857)
    t0 = dt.datetime(2020, 1, 9, tzinfo=dt.timezone.utc).timestamp()
    t1 = dt.datetime(2020, 1, 15, tzinfo=dt.timezone.utc).timestamp()
    ns = "LC08_20200110_T1"
    nodata = -9999.0

    def run():
        tiles = split_bbox(merc, size, size, 1024, 1024)
        out = np.full((size, size), nodata, np.float32)

        def one(t):
            tb, ox, oy, tw, th = t
            req = GeoTileRequest(
                collection=tmp, bands=[ns], bbox=tb, crs=EPSG3857,
                width=tw, height=th, start_time=t0, end_time=t1,
                resample="cubic")
            res = pipe.process(req)
            if ns in res.data:
                d = np.asarray(res.data[ns])
                v = np.asarray(res.valid[ns])
                out[oy:oy + th, ox:ox + tw] = np.where(v, d, nodata)

        # concurrent tile renders, as the WCS handler's asyncio.gather does
        with ThreadPoolExecutor(CONCURRENCY) as ex:
            list(ex.map(one, tiles))
        gt = GeoTransform.from_bbox(merc, size, size)
        path = os.path.join(tmp, "wcs_bench.tif")
        write_geotiff(path, out, gt, EPSG3857, nodata=nodata)
        sz = os.path.getsize(path)
        os.remove(path)
        return sz

    run()                       # warm-up/compile
    start = time.time()
    sz = run()
    elapsed = time.time() - start
    assert sz > 1 << 20
    return {"value": round(elapsed, 3), "unit": "seconds",
            "pixels": size * size,
            "mpix_per_s": round(size * size / elapsed / 1e6, 2)}


def bench_cfg5_drill(tmp_drill):
    """Config 5: polygon drill over a 1000-timestep stack — COLD (first
    request on a never-seen file: host reads + reductions while the
    device stack uploads in the background) and WARM (device-resident
    stack, KBs of traffic per request) measured separately."""
    from gsky_tpu.index import MASClient
    from gsky_tpu.pipeline.drill import DrillPipeline
    from gsky_tpu.pipeline.drill_cache import default_drill_cache
    from gsky_tpu.pipeline.types import GeoDrillRequest

    wkt = ("POLYGON((148.05 -35.45,148.45 -35.45,148.45 -35.05,"
           "148.05 -35.05,148.05 -35.45))")

    def make(name, seed):
        store, _, t0 = build_drill_archive(tmp_drill, name, seed)
        req = GeoDrillRequest(
            collection=tmp_drill, bands=["veg"], geometry_wkt=wkt,
            start_time=t0, end_time=t0 + DRILL_STEPS * 86400.0,
            approx=False)
        return DrillPipeline(MASClient(store)), req

    # identical-shape warm-up stack: compiles every kernel variant so
    # the measured file's cold number is IO+reduction, not XLA compile
    dpw, reqw = make("veg_warmup.nc", 4)
    dpw.process(reqw)
    default_drill_cache.wait_idle(600)
    dpw.process(reqw)

    dp, req = make("veg_stack.nc", 3)
    start = time.time()
    res = dp.process(req)                    # never-seen file: cold
    cold_s = time.time() - start
    assert len(res.dates) >= DRILL_STEPS - 1, len(res.dates)
    default_drill_cache.wait_idle(600)       # background upload lands
    warms = []
    for _ in range(3):                       # device-resident: warm
        start = time.time()
        res = dp.process(req)
        warms.append(time.time() - start)
        assert len(res.dates) >= DRILL_STEPS - 1, len(res.dates)
    # steady state = best of 3 (one-off stalls — a late compile, a link
    # hiccup — must not masquerade as the warm rate); all runs reported
    elapsed = min(warms)
    return {"value": round(elapsed, 3), "unit": "seconds",
            "cold_s": round(cold_s, 3),
            "warm_runs_s": [round(w, 3) for w in warms],
            "timesteps": DRILL_STEPS,
            "steps_per_s": round(DRILL_STEPS / elapsed, 1)}


def bench_cfg6_wcs_pipelined(store, utm, tmp):
    """Config 6: the staged WCS export engine (pipeline/export.py)
    through the real GetCoverage handler — 4096x4096 streamed GeoTIFF,
    1024^2 tiles — pipelined vs serial (GSKY_EXPORT_PIPELINE=0) on the
    same host, reported as Mpix/s."""
    import asyncio
    import glob

    from gsky_tpu.geo.crs import EPSG3857, EPSG4326
    from gsky_tpu.geo.transform import BBox, transform_bbox
    from gsky_tpu.index import MASClient
    from gsky_tpu.server.config import ConfigWatcher
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from gsky_tpu.server.params import normalise_query, parse_wcs

    size = 5120
    conf_dir = os.path.join(tmp, "conf6")
    os.makedirs(conf_dir, exist_ok=True)
    config = {
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [{
            "name": "export_bench", "title": "export bench",
            "data_source": tmp,
            "rgb_products": [f"LC08_20200{110 + k}_T1"
                             for k in range(N_SCENES)],
            "time_generator": "mas",
            "wcs_max_width": size, "wcs_max_height": size,
            "wcs_max_tile_width": 1024, "wcs_max_tile_height": 1024,
        }],
    }
    with open(os.path.join(conf_dir, "config.json"), "w") as fp:
        fp.write(json.dumps(config))
    mas_client = MASClient(store)
    watcher = ConfigWatcher(conf_dir, mas_factory=lambda a: mas_client,
                            install_signal=False)
    server = OWSServer(watcher, mas_factory=lambda a: mas_client,
                       metrics=MetricsLogger())
    cfg = watcher.configs[""]

    span = SCENE_SIZE * 30.0
    core = BBox(590000.0 + span * 0.1, 6105000.0 - span * 1.2,
                590000.0 + span * 1.2, 6105000.0 - span * 0.1)
    merc = transform_bbox(transform_bbox(core, utm, EPSG4326),
                          EPSG4326, EPSG3857)
    p = parse_wcs(normalise_query({
        "service": "WCS", "request": "GetCoverage",
        "coverage": "export_bench", "crs": "EPSG:3857",
        "bbox": f"{merc.xmin},{merc.ymin},{merc.xmax},{merc.ymax}",
        "width": str(size), "height": str(size), "format": "GeoTIFF",
        "time": "2020-01-09T00:00:00.000Z",
        "until": "2020-01-15T00:00:00.000Z"}))

    def run_once():
        async def go():
            collector = server.metrics.collector()
            await server._getcoverage(cfg, p, collector)
        t0 = time.time()
        asyncio.run(go())
        elapsed = time.time() - t0
        # the handler leaves the streamed file for the FileResponse;
        # the bench is its own consumer, so clean up now
        for f in glob.glob(os.path.join(server.temp_dir, "wcs_*.tif")):
            try:
                os.remove(f)
            except OSError:
                pass
        return elapsed

    prev = os.environ.pop("GSKY_EXPORT_PIPELINE", None)
    try:
        run_once()                                 # warm-up/compile
        piped_s = min(run_once() for _ in range(2))
        os.environ["GSKY_EXPORT_PIPELINE"] = "0"
        serial_s = min(run_once() for _ in range(2))
    finally:
        if prev is None:
            os.environ.pop("GSKY_EXPORT_PIPELINE", None)
        else:
            os.environ["GSKY_EXPORT_PIPELINE"] = prev
    mpix = size * size / 1e6
    ep = server.metrics.summary().get("export_pipeline", {})
    return {"value": round(mpix / piped_s, 2), "unit": "Mpix/s",
            "pixels": size * size,
            "pipelined_s": round(piped_s, 3),
            "serial_s": round(serial_s, 3),
            "serial_mpix_per_s": round(mpix / serial_s, 2),
            "overlap_speedup": round(serial_s / piped_s, 2),
            "stage_s": {k: ep.get("last", {}).get(k)
                        for k in ("decode_s", "warp_s", "encode_s",
                                  "wall_s")}}


def bench_ragged():
    """Heterogeneous-footprint A/B (docs/KERNELS.md, ragged paged
    rendering): K tiles whose gather windows land in several size
    buckets, rendered (a) by the bucketed windowed dispatch — one
    compiled program per window bucket, pow2 window pad billed per
    tile — and (b) as ONE ragged paged dispatch over a shared page
    pool.  Reports Mpix/s for both legs, the pad-waste bytes each
    moves, and the compiled-program count.  On CPU the paged leg runs
    the INTERPRET pallas kernel (labelled as such: its wall time is a
    correctness exercise, not a hardware claim — the pad-waste and
    program-count A/B is platform-independent)."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import paged
    from gsky_tpu.ops.warp import render_scenes_ctrl
    from gsky_tpu.pipeline.executor import (_gather_window,
                                            _granule_bounds)
    from gsky_tpu.pipeline.pages import PagePool

    rng = np.random.default_rng(11)
    B, S, h, w, step = 2, 1024, 256, 256, 16
    stack = jnp.asarray(
        rng.uniform(200, 3000, (B, S, S)).astype(np.float32))
    params = np.zeros((B, 11), np.float64)
    for k in range(B):
        params[k] = [3.0 * k, 1.0, 0.0, 2.0 * k, 0.0, 1.0, S, S,
                     -999.0, float(B - k), 0.0]
    params32 = jnp.asarray(params.astype(np.float32))
    sp = jnp.zeros(3, np.float32)
    gh = (h - 1 + step - 1) // step + 1
    # footprint extents chosen to scatter across window buckets —
    # the shape diversity a tile server sees across zoom levels
    exts = (140.0, 260.0, 420.0, 700.0, 180.0, 520.0, 330.0, 620.0)
    K = len(exts)
    ctrls = []
    for i, ext in enumerate(exts):
        base = 30.0 + 7.0 * i
        lin = np.linspace(base, base + ext, gh, dtype=np.float32)
        ctrls.append(np.stack([lin[None, :].repeat(gh, 0),
                               lin[:, None].repeat(gh, 1)]))
    interp = jax.devices()[0].platform == "cpu"

    def timeit(fn, n):
        fn()                       # compile + warm every program
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn()
        np.asarray(r)              # block
        return (time.perf_counter() - t0) / n

    # -- bucketed leg: one windowed dispatch per tile -----------------
    wins = []
    bucket_waste = 0
    for c in ctrls:
        made = _gather_window(params, np.asarray(c[0], np.float64),
                              np.asarray(c[1], np.float64), S, S)
        win, win0, raw = made
        wins.append((win, jnp.asarray(np.asarray(win0))))
        raw_area = (raw[1] - raw[0]) * (raw[3] - raw[2])
        bucket_waste += (win[0] * win[1] - raw_area) * 4 * B

    def run_bucketed():
        out = None
        for c, (win, win0) in zip(ctrls, wins):
            out = render_scenes_ctrl(stack, jnp.asarray(c), params32,
                                     sp, "near", 1, (h, w), step,
                                     True, 0, win=win, win0=win0)
        return out

    t_bucket = timeit(run_bucketed, 3)

    # -- paged leg: ONE ragged dispatch over the shared pool ----------
    pool = PagePool()
    pr, pc = pool.page_rows, pool.page_cols
    spans = []
    max_npg = 1
    for c in ctrls:
        per_tile = []
        for k in range(B):
            r_lo, r_hi, c_lo, c_hi = _granule_bounds(
                params[k], np.asarray(c[0], np.float64),
                np.asarray(c[1], np.float64))
            i0, i1 = max(0, r_lo) // pr, min(-(-S // pr) - 1,
                                             r_hi // pr)
            j0, j1 = max(0, c_lo) // pc, min(-(-S // pc) - 1,
                                             c_hi // pc)
            per_tile.append((i0, i1, j0, j1))
            max_npg = max(max_npg, (i1 - i0 + 1) * (j1 - j0 + 1))
        spans.append(per_tile)
    Ssl = 1
    while Ssl < max_npg:
        Ssl *= 2
    tables = np.zeros((K, B, Ssl), np.int32)
    p16 = np.zeros((K, B, paged.PARAMS_W), np.float32)
    real_pages = 0
    for i, per_tile in enumerate(spans):
        p16[i, :, :11] = params[:, :11]
        for k, (i0, i1, j0, j1) in enumerate(per_tile):
            t = pool.table_for(stack[k], k + 1, i0, i1, j0, j1)
            tables[i, k, :t.size] = t
            real_pages += int(t.size)
            p16[i, k, 11] = i0 * pr
            p16[i, k, 12] = j0 * pc
            p16[i, k, 13] = (i1 - i0 + 1) * pr
            p16[i, k, 14] = (j1 - j0 + 1) * pc
            p16[i, k, 15] = j1 - j0 + 1
            pool.unpin(t)          # bench holds the pool: no eviction
    paged_waste = (K * B * Ssl - real_pages) * pr * pc * 4
    tab_dev = jnp.asarray(tables)
    p16_dev = jnp.asarray(p16.reshape(K * B, paged.PARAMS_W))
    ctrl_dev = jnp.asarray(np.stack(ctrls))
    sps_dev = jnp.tile(sp[None], (K, 1))

    def run_paged():
        with pool.locked_pool() as parr:
            return paged.render_byte_paged(
                parr, tab_dev, p16_dev, ctrl_dev, sps_dev, "near", 1,
                (h, w), step, True, 0, interpret=interp)

    t_paged = timeit(run_paged, 2 if interp else 10)

    mpix = K * h * w / 1e6
    out = {
        "workload": f"{K} heterogeneous-footprint 256px tiles, "
                    f"{B}x{S}px scenes, window extents {exts}",
        "unit": "Mpix/s",
        "value": round(mpix / t_paged, 2),
        "paged": {
            "mpix_s": round(mpix / t_paged, 2),
            "pad_waste_bytes": int(paged_waste),
            "programs": 1,
            "pages_real": real_pages,
            "page_slots_padded": int(K * B * Ssl),
            # host->HBM staging is content-keyed: overlapping tiles
            # share pages, so the link moves these bytes ONCE for the
            # whole mix (the bucketed leg re-gathers per tile)
            "hbm_staged_bytes": int(pool.stats()["staged"]
                                    * pr * pc * 4),
            "interpret": interp,
        },
        "bucketed": {
            "mpix_s": round(mpix / t_bucket, 2),
            "pad_waste_bytes": int(bucket_waste),
            "programs": len({win for win, _ in wins}),
        },
        "pad_waste_ratio": (round(bucket_waste / paged_waste, 2)
                            if paged_waste else None),
        "pool": pool.stats(),
    }
    if interp:
        out["note"] = ("paged leg ran the interpret-mode pallas kernel "
                       "on CPU: its Mpix/s is not a hardware number; "
                       "pad-waste bytes and program counts are "
                       "platform-independent")
    return out


def bench_cfg_wave():
    """Wave-dispatch A/B (docs/PERF.md "Wave-level serving"): a cfg3-
    shaped mosaic storm — GRID*GRID multi-granule tiles — dispatched
    (a) per-call, one paged program invocation per tile (the
    GSKY_WAVES=0 path), and (b) through the wave scheduler, which
    coalesces up to GSKY_WAVE_MAX tiles into ONE stacked invocation
    per wave.  The headline is dispatch amortisation: device program
    invocations per 1000 tiles, per leg, plus the per-wave occupancy
    histogram — platform-independent numbers (on CPU the paged
    programs run the interpret pallas kernel, so wall times are a
    correctness exercise, not hardware claims; what a dispatch costs on
    a directly attached v5e is not measured)."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import paged
    from gsky_tpu.ops.warp import render_scenes_ctrl
    from gsky_tpu.pipeline import waves as W
    from gsky_tpu.pipeline.pages import PagePool

    interp = jax.devices()[0].platform == "cpu"
    prev_pallas = os.environ.get("GSKY_PALLAS")
    if interp and not prev_pallas:
        # the raced wave dispatch needs a live pallas lane on CPU
        os.environ["GSKY_PALLAS"] = "interpret"
    try:
        n_tiles = GRID * GRID              # the cfg3 storm size
        B, S, h, w, step, n_ns = 2, 96, 64, 64, 16, 1
        wave_cap = 16
        rng = np.random.default_rng(17)
        pool = PagePool(capacity=64, page_rows=64, page_cols=128)
        stack = rng.uniform(1.0, 4000.0, (B, S, S)).astype(np.float32)
        stack[0, 10:20, 10:20] = np.nan
        params = np.zeros((B, 11), np.float32)
        for k in range(B):
            params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                         0.99, S, S, -999.0, 100.0 - k, 0.0]
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        statics = ("near", n_ns, (h, w), step, True, 0)
        gh = (h - 1 + step - 1) // step + 1

        def tile_ctrl(i):
            base = 4.0 + (i % 8) * 1.5
            lin = np.linspace(base, S - 12.0, gh, dtype=np.float32)
            return np.stack([lin[None, :].repeat(gh, 0),
                             lin[:, None].repeat(gh, 1)])

        ctrls = [tile_ctrl(i) for i in range(n_tiles)]

        def stage():
            # content-keyed: every tile shares the SAME staged pages,
            # each call pins its own table (the executor's contract)
            tabs = []
            ni = -(-S // pool.page_rows)
            nj = -(-S // pool.page_cols)
            for k in range(B):
                t = pool.table_for(jnp.asarray(stack[k]), k + 1,
                                   0, ni - 1, 0, nj - 1)
                tabs.append(t)
            Ssl = 1
            while Ssl < max(t.size for t in tabs):
                Ssl *= 2
            tables = np.zeros((B, Ssl), np.int32)
            p16 = np.zeros((B, paged.PARAMS_W), np.float32)
            p16[:, :11] = params
            for k, t in enumerate(tabs):
                tables[k, :t.size] = t
                p16[k, 13] = ni * pool.page_rows
                p16[k, 14] = nj * pool.page_cols
                p16[k, 15] = nj
            return tables, p16

        # -- per-call leg: one program invocation per tile ------------
        tables0, p160 = stage()

        def percall_one(c):
            with pool.locked_pool() as parr:
                return paged.render_byte_paged(
                    parr, jnp.asarray(tables0[None]),
                    jnp.asarray(p160), jnp.asarray(c)[None],
                    jnp.asarray(sp)[None], *statics, interpret=interp)

        np.asarray(percall_one(ctrls[0]))          # compile + warm
        t0 = time.perf_counter()
        for c in ctrls:
            np.asarray(percall_one(c))
        percall_s = time.perf_counter() - t0
        pool.unpin(tables0)

        # -- wave leg: the storm through the scheduler ----------------
        sched = W.WaveScheduler(max_entries=wave_cap, tick_ms=5000.0)
        results = [None] * n_tiles
        errors = []

        def submit(i):
            tb, p16 = stage()

            def go():
                try:
                    results[i] = sched.render_byte(
                        pool, tb, p16, ctrls[i], sp, statics,
                        (jnp.asarray(stack), jnp.asarray(params),
                         None, None), None)
                except Exception as e:   # noqa: BLE001 - reported
                    errors.append(repr(e))
            t = threading.Thread(target=go)
            t.start()
            return t

        t0 = time.perf_counter()
        ts = [submit(i) for i in range(n_tiles)]
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:     # let the storm queue up
            with sched._lock:
                if len(sched._pending) >= n_tiles:
                    break
            time.sleep(0.002)
        while sched.run_wave():                # deterministic stepping
            pass
        for t in ts:
            t.join(timeout=300)
        wave_s = time.perf_counter() - t0
        st = sched.stats()
        sched.shutdown()

        ref = np.asarray(render_scenes_ctrl(
            jnp.asarray(stack), jnp.asarray(ctrls[0]),
            jnp.asarray(params), jnp.asarray(sp), *statics))
        parity = (not errors and results[0] is not None
                  and bool(np.array_equal(ref, results[0])))
        disp = max(1, st["dispatches"])
        ratio = round(n_tiles / disp, 2)
        out = {
            "workload": f"{n_tiles} multi-granule mosaic tiles "
                        f"({B} granules, {h}px) — the cfg3 storm "
                        f"shape at wave_max {wave_cap}",
            "unit": "x fewer dispatches (per-call/wave)",
            "value": ratio,
            "amortisation_ok": ratio >= 8.0,
            "per_call": {"dispatches": n_tiles,
                         "dispatches_per_1k_tiles": 1000.0,
                         "elapsed_s": round(percall_s, 3)},
            "wave": {"dispatches": st["dispatches"],
                     "waves": st["waves"],
                     "dispatches_per_1k_tiles":
                         round(st["dispatches"] / n_tiles * 1e3, 1),
                     "occupancy": st["occupancy"],
                     "wave_max": wave_cap,
                     "fallbacks": st["fallbacks"],
                     "ring": st["ring"],
                     "elapsed_s": round(wave_s, 3)},
            "parity_near_bit_exact": parity,
            "errors": errors[:3],
            "interpret": interp,
        }
        if interp:
            out["note"] = ("both legs ran the interpret-mode pallas "
                           "kernel on CPU: elapsed_s is not a hardware "
                           "number; the dispatch counts and occupancy "
                           "are platform-independent")
        return out
    finally:
        if interp and not prev_pallas:
            os.environ.pop("GSKY_PALLAS", None)


def bench_cfg_occupancy():
    """Synchronous-vs-pipelined wave ticker A/B (docs/PERF.md
    "Continuous device occupancy"): the cfg_wave mosaic storm pushed
    through two live schedulers — (a) GSKY_WAVE_PIPELINE=0, the
    synchronous ticker that plans, stacks, uploads AND dispatches on
    one thread, and (b) the two-stage pipeline, where the assembly
    stage stages wave N+1 into the donated input ring while wave N
    executes.  The headline is the host-side inter-wave dispatch gap
    (p50/p99 idle between consecutive wave dispatch enqueues) plus a
    device-idle-fraction estimate, with BIT-EXACT tile parity between
    the legs.  On a 1-core CI host the overlap is capped by the GIL —
    the gap ratio is reported honestly, whatever it measures; the
    parity and staging counters are platform-independent."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import paged
    from gsky_tpu.ops.warp import render_scenes_ctrl
    from gsky_tpu.pipeline import waves as W
    from gsky_tpu.pipeline.pages import PagePool

    interp = jax.devices()[0].platform == "cpu"
    prev_pallas = os.environ.get("GSKY_PALLAS")
    prev_pipe = os.environ.get("GSKY_WAVE_PIPELINE")
    prev_queue = os.environ.get("GSKY_WAVE_QUEUE")
    if interp and not prev_pallas:
        os.environ["GSKY_PALLAS"] = "interpret"
    try:
        n_tiles = GRID * GRID
        B, S, h, w, step, n_ns = 2, 96, 64, 64, 16, 1
        wave_cap = 16
        rng = np.random.default_rng(23)
        pool = PagePool(capacity=64, page_rows=64, page_cols=128)
        stack = rng.uniform(1.0, 4000.0, (B, S, S)).astype(np.float32)
        stack[0, 10:20, 10:20] = np.nan
        params = np.zeros((B, 11), np.float32)
        for k in range(B):
            params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                         0.99, S, S, -999.0, 100.0 - k, 0.0]
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        statics = ("near", n_ns, (h, w), step, True, 0)
        gh = (h - 1 + step - 1) // step + 1

        def tile_ctrl(i):
            base = 4.0 + (i % 8) * 1.5
            lin = np.linspace(base, S - 12.0, gh, dtype=np.float32)
            return np.stack([lin[None, :].repeat(gh, 0),
                             lin[:, None].repeat(gh, 1)])

        ctrls = [tile_ctrl(i) for i in range(n_tiles)]

        def stage():
            tabs = []
            ni = -(-S // pool.page_rows)
            nj = -(-S // pool.page_cols)
            for k in range(B):
                t = pool.table_for(jnp.asarray(stack[k]), k + 1,
                                   0, ni - 1, 0, nj - 1)
                tabs.append(t)
            Ssl = 1
            while Ssl < max(t.size for t in tabs):
                Ssl *= 2
            tables = np.zeros((B, Ssl), np.int32)
            p16 = np.zeros((B, paged.PARAMS_W), np.float32)
            p16[:, :11] = params
            for k, t in enumerate(tabs):
                tables[k, :t.size] = t
                p16[k, 13] = ni * pool.page_rows
                p16[k, 14] = nj * pool.page_cols
                p16[k, 15] = nj
            return tables, p16

        def run_leg(pipelined):
            """One storm through a LIVE scheduler (real ticker +
            dispatcher threads — the overlap under test is between
            them), tiles submitted from request threads exactly as
            the executor does."""
            os.environ["GSKY_WAVE_PIPELINE"] = \
                "1" if pipelined else "0"
            os.environ["GSKY_WAVE_QUEUE"] = "2"
            sched = W.WaveScheduler(max_entries=wave_cap, tick_ms=0.5)
            results = [None] * n_tiles
            errors = []

            def go(i, tb, p16):
                try:
                    results[i] = sched.render_byte(
                        pool, tb, p16, ctrls[i], sp, statics,
                        (jnp.asarray(stack), jnp.asarray(params),
                         None, None), None)
                except Exception as e:   # noqa: BLE001 - reported
                    errors.append(repr(e))

            t0 = time.perf_counter()
            ts = []
            for i in range(n_tiles):
                tb, p16 = stage()
                t = threading.Thread(target=go, args=(i, tb, p16))
                t.start()
                ts.append(t)
            for t in ts:
                t.join(timeout=300)
            elapsed = time.perf_counter() - t0
            st = sched.stats()
            sched.shutdown()
            return results, st, errors, elapsed

        run_leg(False)                       # compile + warm pass
        res_sync, st_sync, err_s, el_s = run_leg(False)
        res_pipe, st_pipe, err_p, el_p = run_leg(True)

        ref = np.asarray(render_scenes_ctrl(
            jnp.asarray(stack), jnp.asarray(ctrls[0]),
            jnp.asarray(params), jnp.asarray(sp), *statics))
        parity = (not err_s and not err_p
                  and res_sync[0] is not None
                  and bool(np.array_equal(ref, res_sync[0]))
                  and all(a is not None and b is not None
                          and np.array_equal(a, b)
                          for a, b in zip(res_sync, res_pipe)))
        assert parity or err_s or err_p, \
            "sync vs pipelined wave legs diverged bitwise"
        p50_s, p50_p = st_sync["gap_ms_p50"], st_pipe["gap_ms_p50"]
        ratio = round(p50_s / p50_p, 2) if p50_p else None

        def leg(st, elapsed):
            return {"gap_ms_p50": st["gap_ms_p50"],
                    "gap_ms_p99": st["gap_ms_p99"],
                    "gap_samples": st["gap_samples"],
                    "device_idle_fraction":
                        st["device_idle_fraction"],
                    "dispatches": st["dispatches"],
                    "waves": st["waves"],
                    "occupancy": st["occupancy"],
                    "fallbacks": st["fallbacks"],
                    "elapsed_s": round(elapsed, 3)}

        out = {
            "workload": f"{n_tiles} multi-granule mosaic tiles "
                        f"({B} granules, {h}px) through live "
                        f"sync vs pipelined tickers, wave_max "
                        f"{wave_cap}",
            "unit": "x lower p50 inter-wave gap (sync/pipelined)",
            "value": ratio,
            "synchronous": leg(st_sync, el_s),
            "pipelined": {**leg(st_pipe, el_p),
                          "staged_waves": st_pipe["staged_waves"],
                          "staging": st_pipe["staging"]},
            "parity_bit_exact": parity,
            "errors": (err_s + err_p)[:3],
            "interpret": interp,
        }
        if interp:
            out["note"] = ("1-core CI host: assembly and dispatch "
                           "share the GIL, so the gap ratio under-"
                           "states what a real host+device overlap "
                           "gives; parity and staging counters are "
                           "platform-independent")
        return out
    finally:
        for k, v in (("GSKY_WAVE_PIPELINE", prev_pipe),
                     ("GSKY_WAVE_QUEUE", prev_queue)):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if interp and not prev_pallas:
            os.environ.pop("GSKY_PALLAS", None)


def bench_cfg_plan():
    """Dataflow-autoplanner A/B (docs/PERF.md "Dataflow planning"): an
    overlapping pan-walk — adjacent GetMap tiles sliding one page row
    at a time over a shared scene — plus a 4K-export-shaped block mix,
    dispatched through the wave scheduler twice: (a) GSKY_PLAN=0, every
    lane gathering its own page window (today's independent-window
    dispatch), and (b) planner on, overlapping windows merged into
    shared-halo superblocks gathered ONCE.  The headline is gathered
    HBM bytes (the eager `ops.paged` gather accounting) per leg:
    acceptance wants >= 30% fewer bytes with BIT-EXACT tile parity
    between the legs.  Byte counts and superblock counts are platform-
    independent; on CPU wall times are a correctness exercise."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import paged
    from gsky_tpu.ops.warp import render_scenes_ctrl
    from gsky_tpu.pipeline import autoplan
    from gsky_tpu.pipeline import waves as W
    from gsky_tpu.pipeline.pages import PagePool

    interp = jax.devices()[0].platform == "cpu"
    prev_pallas = os.environ.get("GSKY_PALLAS")
    prev_plan = os.environ.get("GSKY_PLAN")
    if interp and not prev_pallas:
        os.environ["GSKY_PALLAS"] = "interpret"
    try:
        B, S, h, w, step, n_ns = 2, 256, 64, 64, 16, 1
        pr, pc = 64, 128
        npr, npc = S // pr, S // pc          # 4 x 2 page grid
        n_pan, n_export = 12, 4
        wave_cap = 16
        rng = np.random.default_rng(23)
        stack = rng.uniform(1.0, 4000.0, (B, S, S)).astype(np.float32)
        stack[0, 30:50, 30:50] = np.nan
        params = np.zeros((B, 11), np.float32)
        for k in range(B):
            params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                         0.99, S, S, -999.0, 100.0 - k, 0.0]
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        statics = ("near", n_ns, (h, w), step, True, 0)
        statics4k = ("near", n_ns, (2 * h, 2 * w), step, True, 0)

        def grid_ctrl(hw_out, lo, hi):
            g = (hw_out - 1 + step - 1) // step + 1
            lin = np.linspace(lo, hi, g, dtype=np.float32)
            return np.stack([lin[None, :].repeat(g, 0),
                             lin[:, None].repeat(g, 1)])

        # pan-walk tiles: tile i samples source rows around page row
        # i % (npr-1), so consecutive tiles' 2-page-row windows overlap
        # by one page row — the superblock planner's bread and butter
        pan = []
        for i in range(n_pan):
            ri = i % (npr - 1)
            lo = ri * pr + 6.0
            hi = min(S - 10.0, (ri + 2) * pr - 8.0)
            pan.append((ri, grid_ctrl(h, lo, hi)))
        # export-shaped blocks: 2x-sized outputs over the full scene
        exp_ctrls = [grid_ctrl(2 * h, 6.0, S - 10.0)
                     for _ in range(n_export)]

        def run_leg(pool):
            def stage(i0, i1):
                tabs = []
                for k in range(B):
                    t = pool.table_for(jnp.asarray(stack[k]), k + 1,
                                       i0, i1, 0, npc - 1)
                    tabs.append(t)
                Ssl = 1
                while Ssl < max(t.size for t in tabs):
                    Ssl *= 2
                tables = np.zeros((B, Ssl), np.int32)
                p16 = np.zeros((B, paged.PARAMS_W), np.float32)
                p16[:, :11] = params
                for k, t in enumerate(tabs):
                    tables[k, :t.size] = t
                    p16[k, 11] = i0 * pr
                    p16[k, 13] = (i1 - i0 + 1) * pr
                    p16[k, 14] = npc * pc
                    p16[k, 15] = npc
                return tables, p16

            sched = W.WaveScheduler(max_entries=wave_cap,
                                    tick_ms=5000.0)
            n_tiles = n_pan + n_export
            results = [None] * n_tiles
            errors = []
            ts = []

            def submit(i, st_key, ctrl, win):
                tb, p16 = stage(*win)

                def go():
                    try:
                        results[i] = sched.render_byte(
                            pool, tb, p16, ctrl, sp, st_key,
                            (jnp.asarray(stack), jnp.asarray(params),
                             None, None), None)
                    except Exception as e:   # noqa: BLE001 - reported
                        errors.append(repr(e))
                t = threading.Thread(target=go)
                t.start()
                ts.append(t)

            paged.reset_gather_bytes()
            t0 = time.perf_counter()
            for i, (ri, ctrl) in enumerate(pan):
                submit(i, statics, ctrl, (ri, ri + 1))
            for j, ctrl in enumerate(exp_ctrls):
                submit(n_pan + j, statics4k, ctrl, (0, npr - 1))
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                with sched._lock:
                    if len(sched._pending) >= n_tiles:
                        break
                time.sleep(0.002)
            while sched.run_wave():
                pass
            for t in ts:
                t.join(timeout=300)
            elapsed = time.perf_counter() - t0
            st = sched.stats()
            sched.shutdown()
            return (results, errors, paged.gather_bytes_total(),
                    elapsed, st)

        os.environ["GSKY_PLAN"] = "0"
        r_off, err_off, bytes_off, s_off, _ = run_leg(
            PagePool(capacity=64, page_rows=pr, page_cols=pc))
        os.environ.pop("GSKY_PLAN", None)
        autoplan.reset_plan_state()
        r_on, err_on, bytes_on, s_on, _ = run_leg(
            PagePool(capacity=64, page_rows=pr, page_cols=pc))
        pst = autoplan.plan_stats()

        parity = (not err_off and not err_on
                  and all(a is not None and b is not None
                          and np.array_equal(a, b)
                          for a, b in zip(r_off, r_on)))
        saved = ((bytes_off - bytes_on) / bytes_off
                 if bytes_off else 0.0)
        out = {
            "workload": f"{n_pan} overlapping pan-walk tiles ({h}px, "
                        f"1-page-row slide over a {S}px scene) + "
                        f"{n_export} export-shaped {2 * h}px blocks",
            "unit": "gathered-HBM-bytes reduction (plan off -> on)",
            "value": round(saved, 3),
            "reduction_ok": saved >= 0.30,
            "plan_off": {"gathered_bytes": int(bytes_off),
                         "elapsed_s": round(s_off, 3)},
            "plan_on": {"gathered_bytes": int(bytes_on),
                        "superblocks": pst["superblocks"],
                        "merged_lanes": pst["merged_lanes"],
                        "routes": pst["routes"],
                        "elapsed_s": round(s_on, 3)},
            "parity_bit_exact": parity,
            "errors": (err_off + err_on)[:3],
            "interpret": interp,
        }
        # spot-check one pan tile against the per-call bucketed
        # reference too (both legs must equal it, not just each other)
        ref = np.asarray(render_scenes_ctrl(
            jnp.asarray(stack), jnp.asarray(pan[0][1]),
            jnp.asarray(params), jnp.asarray(sp), *statics))
        out["parity_vs_reference"] = bool(
            r_on[0] is not None and np.array_equal(ref, r_on[0]))
        if interp:
            out["note"] = ("interpret-mode pallas on CPU: byte counts, "
                           "superblock counts and parity are platform-"
                           "independent; elapsed_s is not a hardware "
                           "number")
        return out
    finally:
        if prev_plan is None:
            os.environ.pop("GSKY_PLAN", None)
        else:
            os.environ["GSKY_PLAN"] = prev_plan
        if interp and not prev_pallas:
            os.environ.pop("GSKY_PALLAS", None)


def bench_cfg_animation():
    """Temporal-wave A/B (docs/PERF.md "Temporal waves"): a 24-step
    TIME-range animation over 6 distinct timesteps (WMS-T nearest
    semantics resolve 4 consecutive frames to each timestep's granule
    set), rendered (a) as today's per-frame loop — one wave dispatch
    and one page gather per frame — and (b) as ONE temporal wave:
    every frame a lane, the serial-aware autoplanner merging
    same-timestep lanes into shared superblocks gathered once per
    SEQUENCE.  Headlines: device programs per sequence (acceptance
    wants <= 2 vs 24), gathered-HBM-bytes reduction (>= 40%) and e2e
    p50 per frame, all with bit-exact frame parity between the legs."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import paged
    from gsky_tpu.ops.warp import render_scenes_ctrl
    from gsky_tpu.pipeline import autoplan
    from gsky_tpu.pipeline import waves as W
    from gsky_tpu.pipeline.pages import PagePool

    interp = jax.devices()[0].platform == "cpu"
    prev_pallas = os.environ.get("GSKY_PALLAS")
    if interp and not prev_pallas:
        os.environ["GSKY_PALLAS"] = "interpret"
    try:
        T, F = 6, 24
        B, S, h, w, step, n_ns = 2, 128, 64, 64, 16, 1
        pr, pc = 64, 128
        ni, nj = S // pr, S // pc            # 2 x 1 page grid
        frame_ts = [i * T // F for i in range(F)]
        rng = np.random.default_rng(31)
        stacks = []
        for t in range(T):
            st = rng.uniform(1.0, 4000.0, (B, S, S)).astype(np.float32)
            st[0, 20:30, 20:30] = np.nan
            stacks.append(st)
        params = np.zeros((B, 11), np.float32)
        for k in range(B):
            params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                         0.99, S, S, -999.0, 100.0 - k, 0.0]
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        statics = ("near", n_ns, (h, w), step, True, 0)
        g = (h - 1 + step - 1) // step + 1
        lin = np.linspace(6.0, S - 10.0, g, dtype=np.float32)
        ctrl = np.stack([lin[None, :].repeat(g, 0),
                         lin[:, None].repeat(g, 1)])

        def stage(pool, t):
            # full-scene tables per frame lane: the content-keyed pool
            # dedups same-serial pages, so same-timestep lanes carry
            # identical tables (the superblock-merge precondition)
            tabs = []
            for k in range(B):
                tb = pool.table_for(jnp.asarray(stacks[t][k]),
                                    100 * (t + 1) + k,
                                    0, ni - 1, 0, nj - 1)
                tabs.append(tb)
            Ssl = 1
            while Ssl < max(tb.size for tb in tabs):
                Ssl *= 2
            tables = np.zeros((B, Ssl), np.int32)
            p16 = np.zeros((B, paged.PARAMS_W), np.float32)
            p16[:, :11] = params
            for k, tb in enumerate(tabs):
                tables[k, :tb.size] = tb
                p16[k, 13] = ni * pr
                p16[k, 14] = nj * pc
                p16[k, 15] = nj
            return tables, p16

        def run_leg(per_frame):
            pool = PagePool(capacity=64, page_rows=pr, page_cols=pc)
            sched = W.WaveScheduler(
                max_entries=1 if per_frame else 32, tick_ms=5000.0)
            results = [None] * F
            errors = []
            lat_ms = [None] * F
            paged.reset_gather_bytes()

            def submit(i):
                t = frame_ts[i]
                tb, p16 = stage(pool, t)
                serials = tuple(100 * (t + 1) + k for k in range(B))

                def go():
                    ti = time.perf_counter()
                    try:
                        results[i] = sched.render_byte(
                            pool, tb, p16, ctrl, sp, statics,
                            (jnp.asarray(stacks[t]),
                             jnp.asarray(params), None, None), None,
                            serials=serials)
                        lat_ms[i] = (time.perf_counter() - ti) * 1e3
                    except Exception as e:  # noqa: BLE001 - reported
                        errors.append(repr(e))
                th = threading.Thread(target=go)
                th.start()
                return th

            def pending(n):
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    with sched._lock:
                        if len(sched._pending) >= n:
                            return
                    time.sleep(0.002)

            t0 = time.perf_counter()
            if per_frame:
                for i in range(F):
                    th = submit(i)
                    pending(1)
                    while sched.run_wave():
                        pass
                    th.join(timeout=300)
            else:
                ts = [submit(i) for i in range(F)]
                pending(F)
                while sched.run_wave():
                    pass
                for th in ts:
                    th.join(timeout=300)
            elapsed = time.perf_counter() - t0
            st = sched.stats()
            sched.shutdown()
            live = sorted(x for x in lat_ms if x is not None)
            p50 = live[len(live) // 2] if live else None
            return {
                "results": results, "errors": errors,
                "gathered_bytes": paged.gather_bytes_total(),
                "elapsed_s": elapsed, "dispatches": st["dispatches"],
                "frame_p50_ms": p50,
                "per_frame_ms": elapsed * 1e3 / F}

        autoplan.reset_plan_state()
        leg_pf = run_leg(per_frame=True)
        leg_tw = run_leg(per_frame=False)
        pst = autoplan.plan_stats()

        parity = (not leg_pf["errors"] and not leg_tw["errors"]
                  and all(a is not None and b is not None
                          and np.array_equal(a, b)
                          for a, b in zip(leg_pf["results"],
                                          leg_tw["results"])))
        # every frame must also equal the per-call bucketed reference
        # of ITS timestep (nearest: bit-exact parity contract)
        refs = [np.asarray(render_scenes_ctrl(
            jnp.asarray(stacks[t]), jnp.asarray(ctrl),
            jnp.asarray(params), jnp.asarray(sp), *statics))
            for t in range(T)]
        parity_ref = all(
            r is not None and np.array_equal(refs[frame_ts[i]], r)
            for i, r in enumerate(leg_tw["results"]))
        b_pf = leg_pf["gathered_bytes"]
        b_tw = leg_tw["gathered_bytes"]
        saved = (b_pf - b_tw) / b_pf if b_pf else 0.0
        out = {
            "workload": f"{F}-frame TIME-range animation over {T} "
                        f"timesteps ({h}px frames, {S}px scenes, "
                        f"B={B}), per-frame loop vs one temporal wave",
            "unit": "gathered-HBM-bytes reduction (per-frame -> wave)",
            "value": round(saved, 3),
            "reduction_ok": saved >= 0.40,
            "per_frame": {
                "dispatches_per_sequence": leg_pf["dispatches"],
                "gathered_bytes": int(b_pf),
                "frame_p50_ms": round(leg_pf["frame_p50_ms"], 3)
                if leg_pf["frame_p50_ms"] else None,
                "elapsed_s": round(leg_pf["elapsed_s"], 3)},
            "temporal_wave": {
                "dispatches_per_sequence": leg_tw["dispatches"],
                "gathered_bytes": int(b_tw),
                "frame_p50_ms": round(leg_tw["per_frame_ms"], 3),
                "elapsed_s": round(leg_tw["elapsed_s"], 3),
                "superblocks": pst["superblocks"],
                "merged_lanes": pst["merged_lanes"]},
            "programs_ok": leg_tw["dispatches"] <= 2,
            "parity_bit_exact": parity,
            "parity_vs_reference": parity_ref,
            "errors": (leg_pf["errors"] + leg_tw["errors"])[:3],
            "interpret": interp,
        }
        if interp:
            out["note"] = ("interpret-mode pallas on CPU: dispatch "
                           "counts, byte counts and parity are "
                           "platform-independent; elapsed_s and p50 "
                           "are not hardware numbers")
        return out
    finally:
        if interp and not prev_pallas:
            os.environ.pop("GSKY_PALLAS", None)


def _ulp_diff_f32(a, b):
    """Element-wise f32 ULP distance (sign-magnitude int ordering)."""
    ai = a.view(np.int32).astype(np.int64)
    bi = b.view(np.int32).astype(np.int64)
    ai = np.where(ai < 0, np.int64(-0x80000000) - ai, ai)
    bi = np.where(bi < 0, np.int64(-0x80000000) - bi, bi)
    return np.abs(ai - bi)


def bench_cfg_algebra():
    """Fused band-algebra A/B (GSKY_EXPR_FUSE, docs/KERNELS.md
    "Expression epilogue"): an NDVI + ternary cloud-mask storm over a
    two-band scene pair, rendered (a) UNFUSED — this repo's expression
    leg before fusion: one per-call scored-mosaic dispatch per tile,
    both bands' f32 planes handed to `evaluate_expressions`, then a
    per-tile byte scale — and (b) FUSED — the same tiles as expression
    wave lanes, grouped by structural fingerprint, each group ONE
    paged program (warp + mosaic + traced expression epilogue + scale)
    whose cross-band gather windows the autoplanner merges into
    superblocks.  The mask storm varies its threshold per tile, so the
    fused leg must prove distinct same-structure expressions share one
    program.  Headlines: paged dispatches per 1000 tiles, gathered
    pool->VMEM HBM bytes, and programs compiled per leg; acceptance
    wants >= 50% reduction in BOTH dispatch and byte counts with f32
    parity <= 2 ULP and byte-exact tiles after scale."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import paged
    from gsky_tpu.ops.expr import (BandExpressions, compile_expr,
                                   fingerprint)
    from gsky_tpu.ops.scale import scale_to_byte
    from gsky_tpu.pipeline import autoplan
    from gsky_tpu.pipeline import waves as W
    from gsky_tpu.pipeline.pages import PagePool
    from gsky_tpu.pipeline.tile import evaluate_expressions

    interp = jax.devices()[0].platform == "cpu"
    prev_pallas = os.environ.get("GSKY_PALLAS")
    prev_plan = os.environ.get("GSKY_PLAN")
    prev_fuse = os.environ.get("GSKY_EXPR_FUSE")
    if interp and not prev_pallas:
        os.environ["GSKY_PALLAS"] = "interpret"
    os.environ.pop("GSKY_PLAN", None)        # planner on: fused rides it
    os.environ.pop("GSKY_EXPR_FUSE", None)
    try:
        B, S, h, w, step = 2, 512, 64, 64, 16
        pr, pc = 64, 128
        npr, npc = S // pr, S // pc              # 8 x 4 page grid
        n_per = 16                               # tiles per expression
        n_windows = 4                            # 2-page-row pan walk
        rng = np.random.default_rng(29)
        stack = rng.uniform(1.0, 4000.0, (B, S, S)).astype(np.float32)
        stack[0, 70:110, 40:200] = np.nan        # nir cloud hole
        stack[1, 90:140, 120:300] = np.nan       # red cloud hole
        params = np.zeros((B, 11), np.float32)
        for k in range(B):
            params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                         0.99, S, S, -999.0, 100.0 - k, k]
        sp = np.array([10.0, 250.0, 0.0], np.float32)

        # NDVI + a threshold storm: every mask tile is a DISTINCT
        # source text but one structure — the fused leg's program
        # count must stay at two
        ndvi = "(nir - red) / (nir + red)"
        masks = [f"nir > {1200.0 + 37.0 * i} ? red : nir"
                 for i in range(n_per)]
        srcs = [ndvi] * n_per + masks
        n_tiles = len(srcs)
        # granule k is variable k by first use in BOTH expressions, so
        # the staged ns_id column doubles as the fingerprint slot id
        fps = [fingerprint(compile_expr(s)) for s in srcs]
        assert all(fp.slots == ("nir", "red") for fp in fps)

        def grid_ctrl(wi):
            lo = wi * pr + 6.0
            hi = (wi + 2) * pr - 12.0
            g = (h - 1 + step - 1) // step + 1
            lin = np.linspace(lo, hi, g, dtype=np.float32)
            return np.stack([lin[None, :].repeat(g, 0),
                             lin[:, None].repeat(g, 1)])

        wins = [i % n_windows for i in range(n_tiles)]
        ctrls = [grid_ctrl(wi) for wi in wins]

        def stage(pool, wi):
            tabs = [pool.table_for(jnp.asarray(stack[k]), k + 1,
                                   wi, wi + 1, 0, npc - 1)
                    for k in range(B)]
            Ssl = 1
            while Ssl < max(t.size for t in tabs):
                Ssl *= 2
            tables = np.zeros((B, Ssl), np.int32)
            p16 = np.zeros((B, paged.PARAMS_W), np.float32)
            p16[:, :11] = params
            for k, t in enumerate(tabs):
                tables[k, :t.size] = t
                p16[k, 11] = wi * pr
                p16[k, 13] = 2 * pr
                p16[k, 14] = npc * pc
                p16[k, 15] = npc
            return tables, p16

        def bx(src):
            ce = compile_expr(src)
            return BandExpressions(
                expressions=[ce], expr_names=["e0"],
                var_list=list(ce.variables),
                expr_var_ref=[list(ce.variables)],
                expr_text=[src], passthrough=False)

        def unfused_leg(pool):
            """One scored paged dispatch per tile (both bands, f32
            planes off-device), `evaluate_expressions`, byte scale —
            the pre-fusion expression path, per call."""
            paged.reset_gather_bytes()
            outs, planes = [], []
            t0 = time.perf_counter()
            for i, src in enumerate(srcs):
                tables, p16 = stage(pool, wins[i])
                paged.note_gather(paged.table_gather_bytes(
                    tables[None], pr, pc))
                try:
                    with pool.locked_pool() as parr:
                        c, b = paged.warp_scored_paged(
                            parr, jnp.asarray(tables[None]),
                            jnp.asarray(p16),
                            jnp.asarray(ctrls[i])[None], "near", B,
                            (h, w), step,
                            interpret=paged.pallas_interpret())
                finally:
                    pool.unpin(tables)
                env = {"nir": c[0, 0], "red": c[0, 1]}
                venv = {"nir": b[0, 0] > -jnp.inf,
                        "red": b[0, 1] > -jnp.inf}
                res = evaluate_expressions(bx(src), env, venv, h, w)
                plane = jnp.asarray(res.data["e0"])
                ok = jnp.asarray(res.valid["e0"])
                planes.append((np.asarray(plane), np.asarray(ok)))
                outs.append(np.asarray(scale_to_byte(
                    plane[None], ok[None], float(sp[0]), float(sp[1]),
                    float(sp[2]), 0, True)[0]))
            elapsed = time.perf_counter() - t0
            return outs, planes, paged.gather_stats(), elapsed

        def fused_leg(pool):
            """The same storm as expression wave lanes: fingerprint
            groups, one fused paged program per group, superblock-
            merged gathers."""
            paged.reset_gather_bytes()
            paged.reset_expr_fused_stats()
            autoplan.reset_plan_state()
            sched = W.WaveScheduler(max_entries=2 * n_tiles,
                                    tick_ms=5000.0)
            results = [None] * n_tiles
            errors = []
            ts = []

            def submit(i):
                tables, p16 = stage(pool, wins[i])
                fp = fps[i]
                statics = ("near", B, (h, w), step, True, 0, fp.key)

                def go():
                    try:
                        results[i] = sched.render_expr(
                            pool, tables, p16, ctrls[i], sp,
                            fp.const_array(), statics,
                            (jnp.asarray(stack), jnp.asarray(params),
                             None, None), None)
                    except Exception as e:   # noqa: BLE001 - reported
                        errors.append(repr(e))
                t = threading.Thread(target=go)
                t.start()
                ts.append(t)

            t0 = time.perf_counter()
            for i in range(n_tiles):
                submit(i)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                with sched._lock:
                    if len(sched._pending) >= n_tiles:
                        break
                time.sleep(0.002)
            while sched.run_wave():
                pass
            for t in ts:
                t.join(timeout=300)
            elapsed = time.perf_counter() - t0
            st = sched.stats()
            sched.shutdown()
            return (results, errors, paged.gather_stats(), elapsed,
                    st, paged.expr_fused_stats())

        u_out, u_planes, u_gather, u_s = unfused_leg(
            PagePool(capacity=96, page_rows=pr, page_cols=pc))
        f_out, f_err, f_gather, f_s, f_st, f_expr = fused_leg(
            PagePool(capacity=96, page_rows=pr, page_cols=pc))
        pst = autoplan.plan_stats()

        parity_byte = (not f_err
                       and all(b is not None and np.array_equal(a, b)
                               for a, b in zip(u_out, f_out)))
        # f32 plane parity: re-run ONE tile per expression structure
        # through the fused program (no scale) against the unfused
        # evaluate_expressions plane
        max_ulp = 0
        pool_p = PagePool(capacity=96, page_rows=pr, page_cols=pc)
        for i in (0, n_per):
            tables, p16 = stage(pool_p, wins[i])
            try:
                with pool_p.locked_pool() as parr:
                    c, b = paged.warp_scored_paged(
                        parr, jnp.asarray(tables[None]),
                        jnp.asarray(p16),
                        jnp.asarray(ctrls[i])[None], "near", B,
                        (h, w), step,
                        interpret=paged.pallas_interpret())
                    plane, ok = paged.expr_epilogue(
                        c, b, fps[i].key,
                        jnp.asarray(fps[i].const_array()[None]))
            finally:
                pool_p.unpin(tables)
            u_plane, u_ok = u_planes[i]
            both = np.asarray(ok[0]) & u_ok
            if not np.array_equal(np.asarray(ok[0]), u_ok):
                max_ulp = 1 << 30       # valid masks must agree
            if both.any():
                max_ulp = max(max_ulp, int(_ulp_diff_f32(
                    np.asarray(plane[0])[both], u_plane[both]).max()))

        d_red = (1.0 - f_gather["dispatches"] / u_gather["dispatches"]
                 if u_gather["dispatches"] else 0.0)
        b_red = (1.0 - f_gather["bytes"] / u_gather["bytes"]
                 if u_gather["bytes"] else 0.0)
        out = {
            "workload": f"{n_per} NDVI + {n_per} ternary cloud-mask "
                        f"tiles ({h}px, {n_windows}-window pan over a "
                        f"2-band {S}px scene pair; every mask tile a "
                        "distinct threshold)",
            "unit": "paged-dispatch reduction (unfused -> fused)",
            "value": round(d_red, 3),
            "reduction_ok": d_red >= 0.50 and b_red >= 0.50,
            "unfused": {
                "paged_dispatches": u_gather["dispatches"],
                "dispatches_per_1k_tiles": round(
                    u_gather["dispatches"] / n_tiles * 1000.0, 1),
                "gathered_bytes": u_gather["bytes"],
                "programs_compiled": {
                    "scored_mosaic": 1, "byte_scale": 1,
                    "expression_sources_traced": n_per + 1},
                "elapsed_s": round(u_s, 3)},
            "fused": {
                "paged_dispatches": f_gather["dispatches"],
                "dispatches_per_1k_tiles": round(
                    f_gather["dispatches"] / n_tiles * 1000.0, 1),
                "gathered_bytes": f_gather["bytes"],
                "programs_compiled": f_expr["programs"],
                "wave_requests": f_st["requests"],
                "wave_dispatches": f_st["dispatches"],
                "superblocks": pst["superblocks"],
                "merged_lanes": pst["merged_lanes"],
                "routes": pst["routes"],
                "elapsed_s": round(f_s, 3)},
            "gathered_bytes_reduction": round(b_red, 3),
            "parity_byte_exact": parity_byte,
            "parity_f32_max_ulp": max_ulp,
            "parity_f32_ok": max_ulp <= 2,
            "one_program_per_structure": f_expr["programs"] == 2,
            "errors": f_err[:3],
            "interpret": interp,
        }
        if interp:
            out["note"] = ("interpret-mode pallas on CPU: dispatch "
                           "counts, gathered bytes, program counts and "
                           "parity are platform-independent; elapsed_s "
                           "is not a hardware number")
        return out
    finally:
        for key, prev in (("GSKY_PLAN", prev_plan),
                          ("GSKY_EXPR_FUSE", prev_fuse)):
            if prev is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prev
        if interp and not prev_pallas:
            os.environ.pop("GSKY_PALLAS", None)


def bench_cfg_mesh():
    """Mesh serving A/B (docs/MESH.md): the cfg_wave mosaic storm
    dispatched (a) through single-chip waves (GSKY_MESH unset) and
    (b) through the mesh dispatcher, whose granule layout shards each
    wave's stacked tables across every chip so ONE device program
    spans the mesh.  Headlines: Mpix/s per leg, scaling efficiency
    (mesh Mpix/s over single-chip Mpix/s x chips), and dispatches per
    1000 tiles per chip — with the mesh, one launch serves n_chips
    more tiles-per-chip-program than a single-chip wave.  On CPU the
    8 virtual devices share the same cores, so Mpix/s and efficiency
    are correctness-exercise numbers; the dispatch amortisation and
    the byte parity are platform-independent."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.mesh import dispatch as mesh_dispatch
    from gsky_tpu.ops import paged
    from gsky_tpu.pipeline import waves as W
    from gsky_tpu.pipeline.pages import PagePool

    n_chips = len(jax.devices())
    interp = jax.devices()[0].platform == "cpu"
    prev_pallas = os.environ.get("GSKY_PALLAS")
    prev_mesh = os.environ.get("GSKY_MESH")
    if interp and not prev_pallas:
        os.environ["GSKY_PALLAS"] = "interpret"

    n_tiles = GRID * GRID
    B, S, h, w, step, n_ns = 2, 96, 64, 64, 16, 1
    wave_cap = 16
    rng = np.random.default_rng(17)
    stack = rng.uniform(1.0, 4000.0, (B, S, S)).astype(np.float32)
    stack[0, 10:20, 10:20] = np.nan
    params = np.zeros((B, 11), np.float32)
    for k in range(B):
        params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                     0.99, S, S, -999.0, 100.0 - k, 0.0]
    sp = np.array([10.0, 250.0, 0.0], np.float32)
    statics = ("near", n_ns, (h, w), step, True, 0)
    gh = (h - 1 + step - 1) // step + 1

    def tile_ctrl(i):
        base = 4.0 + (i % 8) * 1.5
        lin = np.linspace(base, S - 12.0, gh, dtype=np.float32)
        return np.stack([lin[None, :].repeat(gh, 0),
                         lin[:, None].repeat(gh, 1)])

    ctrls = [tile_ctrl(i) for i in range(n_tiles)]

    def stage(pool):
        tabs = []
        ni = -(-S // pool.page_rows)
        nj = -(-S // pool.page_cols)
        for k in range(B):
            t = pool.table_for(jnp.asarray(stack[k]), k + 1,
                               0, ni - 1, 0, nj - 1)
            tabs.append(t)
        Ssl = 1
        while Ssl < max(t.size for t in tabs):
            Ssl *= 2
        tables = np.zeros((B, Ssl), np.int32)
        p16 = np.zeros((B, paged.PARAMS_W), np.float32)
        p16[:, :11] = params
        for k, t in enumerate(tabs):
            tables[k, :t.size] = t
            p16[k, 13] = ni * pool.page_rows
            p16[k, 14] = nj * pool.page_cols
            p16[k, 15] = nj
        return tables, p16

    def leg(mesh_on):
        """One storm pass to warm the programs, a second timed — the
        mesh leg's first wave pays the shard_map compile and that must
        not masquerade as serving throughput."""
        if mesh_on:
            os.environ["GSKY_MESH"] = "1"
        else:
            os.environ.pop("GSKY_MESH", None)
        mesh_dispatch.reset_mesh()
        pool = PagePool(capacity=64, page_rows=64, page_cols=128)
        elapsed = None
        st = mesh_st = None
        errors = []
        results = [None] * n_tiles
        for timed in (False, True):
            sched = W.WaveScheduler(max_entries=wave_cap,
                                    tick_ms=5000.0)
            results = [None] * n_tiles

            def submit(i):
                tb, p16 = stage(pool)

                def go():
                    try:
                        results[i] = sched.render_byte(
                            pool, tb, p16, ctrls[i], sp, statics,
                            (jnp.asarray(stack), jnp.asarray(params),
                             None, None), None)
                    except Exception as e:   # noqa: BLE001 - reported
                        errors.append(repr(e))
                t = threading.Thread(target=go)
                t.start()
                return t

            t0 = time.perf_counter()
            ts = [submit(i) for i in range(n_tiles)]
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                with sched._lock:
                    if len(sched._pending) >= n_tiles:
                        break
                time.sleep(0.002)
            while sched.run_wave():
                pass
            for t in ts:
                t.join(timeout=300)
            if timed:
                elapsed = time.perf_counter() - t0
                st = sched.stats()
                mesh_st = mesh_dispatch.mesh_stats()
            sched.shutdown()
        return results, elapsed, st, mesh_st, errors, pool

    try:
        res_1c, s_1c, st_1c, _, err_1c, pool_1c = leg(False)
        res_m, s_m, st_m, mesh_st, err_m, pool_m = leg(True)
        mpix = n_tiles * h * w / 1e6
        mpix_1c = round(mpix / s_1c, 2) if s_1c else None
        mpix_m = round(mpix / s_m, 2) if s_m else None
        disp_1c = max(1, st_1c["dispatches"])
        disp_m = max(1, st_m["dispatches"])
        parity = (not err_1c and not err_m
                  and all(r is not None for r in res_1c + res_m)
                  and all(np.array_equal(a, b)
                          for a, b in zip(res_1c, res_m)))
        # one mesh launch spans every chip, so each chip's share of
        # the storm rides disp_m launches: tiles-per-chip per launch
        eff = (round(mpix_m / (mpix_1c * n_chips), 3)
               if mpix_1c and mpix_m else None)
        out = {
            "workload": f"{n_tiles} multi-granule mosaic tiles "
                        f"({B} granules, {h}px) through the wave "
                        f"scheduler, single-chip vs {n_chips}-chip "
                        "granule-sharded mesh waves",
            "unit": "Mpix/s",
            "value": mpix_m,
            "chips": n_chips,
            "single_chip": {
                "mpix_s": mpix_1c,
                "dispatches": st_1c["dispatches"],
                "dispatches_per_1k_tiles":
                    round(disp_1c / n_tiles * 1e3, 1),
                "tiles_per_dispatch_per_chip":
                    round(n_tiles / disp_1c, 2),
                "elapsed_s": round(s_1c, 3)},
            "mesh": {
                "mpix_s": mpix_m,
                "dispatches": st_m["dispatches"],
                "dispatches_per_1k_tiles":
                    round(disp_m / n_tiles * 1e3, 1),
                "tiles_per_dispatch_per_chip":
                    round(n_tiles / disp_m / n_chips, 2),
                "waves_by_layout": mesh_st.get("waves_by_layout"),
                "skew_ms_last": mesh_st.get("skew_ms_last"),
                "elapsed_s": round(s_m, 3)},
            "scaling_efficiency": eff,
            "parity_bit_exact": parity,
            "errors": (err_1c + err_m)[:3],
            "interpret": interp,
        }
        if interp:
            out["note"] = ("the 8 'chips' are XLA host-platform "
                           "devices sharing one CPU: Mpix/s and "
                           "efficiency are correctness-exercise "
                           "numbers; dispatch amortisation and byte "
                           "parity are platform-independent")
        return out
    finally:
        if prev_mesh is None:
            os.environ.pop("GSKY_MESH", None)
        else:
            os.environ["GSKY_MESH"] = prev_mesh
        mesh_dispatch.reset_mesh()
        if interp and not prev_pallas:
            os.environ.pop("GSKY_PALLAS", None)


def bench_cfg_ingest(store, utm, tmp):
    """Config ingest: ranged-vs-whole-file A/B (docs/INGEST.md).

    A sparse pan walk — two tile rows of the grid, each tile visited
    once, the access pattern of a client dragging the map — decoded two
    ways over the SAME archive: leg A through whole-scene residency
    (``GSKY_INGEST=0``, the classic path), leg B routed through
    chunk-granular ranged windows (``GSKY_INGEST_WINDOW_FRAC`` set, so
    the scene cache declines residency for the small footprints and the
    modular fallback reads only touched chunks).  Reports per leg the
    bytes the decode layer pulled (the ledger's whole+ranged counters),
    the decode-stage p50 (the windowed decode timed alone, outside the
    render path) and e2e tiles/sec."""
    from gsky_tpu.index import MASClient
    from gsky_tpu.ingest import (reset_sources, reset_staging_pool,
                                 stats as ingest_stats)
    from gsky_tpu.pipeline import TilePipeline
    from gsky_tpu.pipeline.decode import decode_window

    bands = [f"LC08_20200{110 + k}_T1" for k in range(N_SCENES)]
    # rows j=3,4 of the shared 8x8 grid: 16 tiles, one visit each
    reqs = _grid_reqs(utm, tmp, bands, 9, 15)[3 * GRID:5 * GRID]

    def leg(env):
        keys = ("GSKY_INGEST", "GSKY_INGEST_WINDOW_FRAC",
                "GSKY_INGEST_WINDOW_PROMOTE")
        saved = {k: os.environ.get(k) for k in keys}
        os.environ.update(env)
        try:
            ingest_stats.reset()
            reset_sources()
            reset_staging_pool()
            pipe = TilePipeline(MASClient(store))
            render = _palette_render(
                pipe, [(0, 0, 120, 255), (250, 250, 90, 255)])
            tps, elapsed, latency = _timed_tiles(render, reqs)
            # decode stage alone: the same windows, timed without the
            # warp/encode tail (handle cache is warm from the render)
            dts = []
            for req in reqs[:4]:
                for g in pipe.index(req):
                    t0 = time.perf_counter()
                    decode_window(g, req.bbox, req.crs,
                                  resample=req.resample)
                    dts.append((time.perf_counter() - t0) * 1e3)
            dts.sort()
            snap = ingest_stats.snapshot()
            return {
                "tiles_per_sec": round(tps, 2),
                "elapsed_s": round(elapsed, 3),
                "latency": latency,
                "decode_p50_ms": (round(dts[len(dts) // 2], 3)
                                  if dts else None),
                "bytes_read": int(snap["ranged_read_bytes"]
                                  + snap["whole_read_bytes"]),
                "ranged_reads": snap["ranged_reads"],
                "ranged_windows": snap["ranged_windows"],
                "fallbacks": snap["fallbacks"],
                "overlap_ratio": snap["overlap_ratio"],
            }
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            ingest_stats.reset()
            reset_sources()
            reset_staging_pool()

    whole = leg({"GSKY_INGEST": "0", "GSKY_INGEST_WINDOW_FRAC": "0",
                 "GSKY_INGEST_WINDOW_PROMOTE": "0"})
    ranged = leg({"GSKY_INGEST": "1", "GSKY_INGEST_WINDOW_FRAC": "0.5",
                  "GSKY_INGEST_WINDOW_PROMOTE": "0"})
    ratio = (round(whole["bytes_read"] / ranged["bytes_read"], 2)
             if ranged["bytes_read"] else None)
    return {"value": ratio, "unit": "x fewer bytes (whole/ranged)",
            "tiles": len(reqs), "whole": whole, "ranged": ranged}


# ---------------------------------------------------------------------------
# device-kernel microbenchmarks (VERDICT r4 #2: chip time, not link time)
# ---------------------------------------------------------------------------

# peak HBM bandwidth by `device_kind` (Google Cloud documentation,
# "TPU v5e": 819 GB/s).  A device that is not here is an error, not a
# default.
_PEAK_HBM_GBPS = {"TPU v5 lite": 819.0}


def bench_kernels():
    """Pure device-kernel timings on PRE-STAGED inputs: the chip's own
    per-tile cost with the host link out of the loop.  ``sync_ms`` times
    dispatch->block per call (single-request latency floor);
    ``pipelined_ms`` times N back-to-back dispatches with one final
    block (the throughput the chip sustains when the host keeps the
    queue full — what a PCIe-attached deployment would see).
    ``approx_hbm_gbps`` divides a traffic model (gather reads
    B*h*w*taps*itemsize + output write) by the pipelined time — an
    estimate, labelled as such."""
    import jax
    import jax.numpy as jnp

    from gsky_tpu.ops import drill as D
    from gsky_tpu.ops.warp import render_rgba_ctrl, render_scenes_ctrl

    rng = np.random.default_rng(5)
    out = {}

    def timeit(fn, n=50):
        fn().block_until_ready()           # compile + warm
        t0 = time.perf_counter()
        for _ in range(n):
            fn().block_until_ready()
        sync_ms = (time.perf_counter() - t0) / n * 1e3
        t0 = time.perf_counter()
        r = None
        for _ in range(n):
            r = fn()
        r.block_until_ready()
        pipe_ms = (time.perf_counter() - t0) / n * 1e3
        return round(sync_ms, 3), round(pipe_ms, 3)

    # --- fused mosaic render at the cfg3 shape: 4 int16 scenes -> tile
    B, S, h, w = N_SCENES, SCENE_SIZE, 256, 256
    stack = jnp.asarray(
        rng.uniform(200, 3000, (B, S, S)).astype(np.int16))
    gh = (h - 1 + 15) // 16 + 1
    base = rng.uniform(100, S - 100)
    ctrl = jnp.asarray(np.stack(
        [np.linspace(base, base + h, gh)[None, :].repeat(gh, 0),
         np.linspace(base, base + w, gh)[:, None].repeat(gh, 1)])
        .astype(np.float32))
    params = np.zeros((B, 11), np.float32)
    for k in range(B):
        params[k, :6] = (k * 3.0, 1.0, 0.0, k * 2.0, 0.0, 1.0)
        params[k, 6] = S
        params[k, 7] = S
        params[k, 8] = np.nan
        params[k, 9] = float(B - k)
        params[k, 10] = 0.0
    params = jnp.asarray(params)
    sp = jnp.zeros(3, np.float32)

    def render():
        return render_scenes_ctrl(stack, ctrl, params, sp, "near", 1,
                                  (h, w), 16, True, 0)

    sync_ms, pipe_ms = timeit(render)
    traffic = B * h * w * 1 * stack.dtype.itemsize + h * w
    out["render_mosaic_256"] = {
        "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
        "chip_tiles_per_s": round(1e3 / pipe_ms, 1),
        "approx_hbm_gbps": round(traffic / (pipe_ms * 1e-3) / 1e9, 2)}

    # --- same render through the gather window (GSKY_WARP_WINDOW
    # path): the full-vs-window split is the direct measure of how much
    # of the kernel wall is gather-source extent
    from gsky_tpu.pipeline.executor import _gather_window
    ctrl_np = np.asarray(ctrl, np.float64)
    made_w = _gather_window(np.asarray(params, np.float64),
                            ctrl_np[0], ctrl_np[1], S, S)
    if made_w is not None:
        winb, win0b, _ = made_w
        win0_dev = jnp.asarray(win0b)

        def render_win():
            return render_scenes_ctrl(stack, ctrl, params, sp, "near",
                                      1, (h, w), 16, True, 0,
                                      win=winb, win0=win0_dev)

        sync_ms, pipe_ms = timeit(render_win)
        out["render_mosaic_256_win"] = {
            "window": list(winb),
            "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
            "chip_tiles_per_s": round(1e3 / pipe_ms, 1)}

    # --- batched N-tile render (the RenderBatcher kernel): how much of
    # the per-tile cost is per-dispatch overhead the batcher amortises
    from gsky_tpu.ops.warp import render_scenes_ctrl_many
    NB = 8
    ctrls = jnp.asarray(np.stack(
        [np.asarray(ctrl) + k * 7.0 for k in range(NB)]))
    paramss = jnp.asarray(np.stack([np.asarray(params)] * NB))
    sps = jnp.zeros((NB, 3), np.float32)

    def render_many():
        return render_scenes_ctrl_many(stack, ctrls, paramss, sps,
                                       "near", 1, (h, w), 16, True, 0)

    sync_ms, pipe_ms = timeit(render_many, n=20)
    out["render_mosaic_256_x8"] = {
        "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
        "per_tile_ms": round(pipe_ms / NB, 3),
        "chip_tiles_per_s": round(NB * 1e3 / pipe_ms, 1)}

    # --- channel-packed RGB render at the cfg2 shape (bilinear)
    rgb = (tuple(jnp.asarray(b) for b in
                 rng.uniform(200, 3000, (3, S, S)).astype(np.int16)),)
    prio1 = jnp.ones((1, 3), jnp.float32)
    param1 = jnp.asarray(np.array(
        [0.0, 1.0, 0.0, 0.0, 0.0, 1.0, S, S, np.nan, 0, 0], np.float32))

    def render_rgb():
        return render_rgba_ctrl(rgb, ctrl, param1[None], prio1, sp,
                                "bilinear", (h, w), 16, True, 0)

    sync_ms, pipe_ms = timeit(render_rgb)
    traffic = h * w * 4 * 3 * rgb[0][0].dtype.itemsize + h * w * 4
    out["render_rgba_256"] = {
        "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
        "chip_tiles_per_s": round(1e3 / pipe_ms, 1),
        "approx_hbm_gbps": round(traffic / (pipe_ms * 1e-3) / 1e9, 2)}

    made_w = _gather_window(np.asarray(param1, np.float64)[None, :],
                            ctrl_np[0], ctrl_np[1], S, S)
    if made_w is not None:
        winr, win0r, _ = made_w
        win0r_dev = jnp.asarray(win0r)

        def render_rgb_win():
            return render_rgba_ctrl(rgb, ctrl, param1[None], prio1, sp,
                                    "bilinear", (h, w), 16, True, 0,
                                    win=winr, win0=win0r_dev[None])

        sync_ms, pipe_ms = timeit(render_rgb_win)
        out["render_rgba_256_win"] = {
            "window": list(winr),
            "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
            "chip_tiles_per_s": round(1e3 / pipe_ms, 1)}

    # --- drill reductions from a resident (1000, 128, 128) f32 stack
    T, H, W = DRILL_STEPS, 128, 128
    dstack = jnp.asarray(
        rng.uniform(0, 1, (T, H, W)).astype(np.float32))
    tsel = jnp.asarray(np.arange(1024, dtype=np.int32) % T)
    mask = jnp.asarray(rng.uniform(0, 1, (H, W)) < 0.6)
    nd = np.float32(-9999.0)

    def drill():
        dataf, validf = D.window_gather(
            dstack, tsel, np.int32(0), np.int32(0), mask, nd,
            np.bool_(True), (H, W))
        v, c = D.masked_mean(dataf, validf)
        return v + c          # one dependent scalar chain to block on

    sync_ms, pipe_ms = timeit(drill, n=20)
    traffic = 1024 * H * W * 4 * 2
    out["drill_stats_1000"] = {
        "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
        "chip_drills_per_s": round(1e3 / pipe_ms, 1),
        "approx_hbm_gbps": round(traffic / (pipe_ms * 1e-3) / 1e9, 2)}

    # --- pallas-vs-xla A/B at the cfg3 (warp render) and cfg5 (drill
    # stats) shapes: the record shows which implementation actually
    # serves, not just the raced winner's time
    from gsky_tpu.ops import kernel_ledger
    from gsky_tpu.ops import pallas_tpu as pt
    if pt.use_pallas():
        interp = pt.pallas_interpret()

        def ab(pallas_fn, xla_fn, n=10):
            try:
                ps, pp = timeit(pallas_fn, n=n)
            except Exception as e:    # noqa: BLE001 - A/B must not
                return {"pallas_error":     # kill the whole bench run
                        f"{type(e).__name__}: {e}"[:200]}
            xs, xp = timeit(xla_fn, n=n)
            return {"pallas_sync_ms": ps, "pallas_pipelined_ms": pp,
                    "xla_sync_ms": xs, "xla_pipelined_ms": xp,
                    "speedup_pipelined":
                        round(xp / pp, 2) if pp else None,
                    "interpret": interp}

        def render_pallas():
            return pt.render_scenes_pallas(stack, ctrl, params, sp,
                                           "near", 1, (h, w), 16, True,
                                           0, interpret=interp)

        out["warp_render_ab_cfg3"] = ab(render_pallas, render)

        if "render_mosaic_256_win" in out:
            def render_pallas_win():
                return pt.render_scenes_pallas(stack, ctrl, params, sp,
                                               "near", 1, (h, w), 16,
                                               True, 0, win=winb,
                                               win0=win0_dev,
                                               interpret=interp)

            out["warp_render_ab_cfg3_win"] = ab(render_pallas_win,
                                                render_win)

        sdata = jnp.asarray(
            rng.uniform(0, 1, (1024, 16384)).astype(np.float32))
        svalid = jnp.asarray(rng.uniform(0, 1, (1024, 16384)) < 0.6)

        def stats_pallas():
            s, c = pt.masked_stats_pallas(sdata, svalid,
                                          interpret=interp)
            return s + c

        def stats_xla():
            v, c = D.masked_mean(sdata, svalid)
            return v + c

        out["drill_stats_ab_cfg5"] = ab(stats_pallas, stats_xla)
    else:
        out["pallas_xla_ab"] = {
            "skipped": "pallas disabled (GSKY_PALLAS=0 / no TPU "
                       "backend; set GSKY_PALLAS=interpret to force)"}
    out["kernel_ledger"] = kernel_ledger.stats()

    dev = jax.devices()[0]
    out["platform"] = dev.platform
    if dev.platform != "cpu":
        peak = _PEAK_HBM_GBPS[dev.device_kind]
        for k in ("render_mosaic_256", "render_rgba_256",
                  "drill_stats_1000"):
            out[k]["approx_hbm_util_pct"] = round(
                out[k]["approx_hbm_gbps"] / peak * 100, 2)
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_all():
    tmp = tempfile.mkdtemp(prefix="gsky_bench_")
    tmp_rgb = tempfile.mkdtemp(prefix="gsky_bench_rgb_")
    tmp_drill = tempfile.mkdtemp(prefix="gsky_bench_drill_")
    store, utm, _ = build_archive(tmp)
    return {
        "cfg1_single_nearest": bench_cfg1_single_nearest(store, utm, tmp),
        "cfg2_rgb_bilinear": bench_cfg2_rgb_bilinear(tmp_rgb),
        "cfg3_mosaic": bench_cfg3_mosaic(store, utm, tmp),
        "cfg4_wcs_4k_cubic": bench_cfg4_wcs_cubic(store, utm, tmp),
        "cfg5_drill_1000": bench_cfg5_drill(tmp_drill),
        "cfg6_wcs_pipelined": bench_cfg6_wcs_pipelined(store, utm, tmp),
        "cfg_ragged": bench_ragged(),
        "cfg_wave": bench_cfg_wave(),
        "cfg_occupancy": bench_cfg_occupancy(),
        "cfg_plan": bench_cfg_plan(),
        "cfg_animation": bench_cfg_animation(),
        "cfg_algebra": bench_cfg_algebra(),
        "cfg_mesh": bench_cfg_mesh(),
        "cfg_ingest": bench_cfg_ingest(store, utm, tmp),
    }


def _host_overhead(configs, kernels):
    """Per-config host-overhead split: e2e p50 tile latency minus the
    matching device kernel's single-dispatch wall (``sync_ms`` on
    pre-staged inputs) = everything the HOST adds per tile — index,
    scene decode, dispatch glue, readback, PNG encode.  This is the
    number the staged tile path attacks; the device term is the floor
    it cannot cross."""
    mapping = {"cfg1_single_nearest": "render_mosaic_256",
               "cfg3_mosaic": "render_mosaic_256",
               "cfg2_rgb_bilinear": "render_rgba_256"}
    out = {}
    for cfg_key, kern_key in mapping.items():
        p50 = (configs.get(cfg_key, {}).get("latency") or {}).get("p50_ms")
        kern = kernels.get(kern_key) or {}
        dev = kern.get("sync_ms")
        if p50 is None or dev is None:
            continue
        host = round(max(0.0, p50 - dev), 3)
        out[cfg_key] = {
            "e2e_p50_ms": p50, "device_sync_ms": dev, "host_ms": host,
            "host_fraction": round(host / p50, 3) if p50 else None,
            "device_pipelined_ms": kern.get("pipelined_ms")}
    return out


def _ratio(cfg_key, measured, baseline):
    """>1 == faster than the measured CPU baseline."""
    m, b = measured[cfg_key], baseline[cfg_key]
    if m["unit"] in ("tiles/sec", "Mpix/s"):    # higher is better
        return round(m["value"] / b["value"], 2) if b["value"] else None
    return round(b["value"] / m["value"], 2) if m["value"] else None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--child-cpu", action="store_true",
                    help="internal: run configs on CPU, print raw JSON")
    args = ap.parse_args(argv)

    from gsky_tpu.device import PlatformError, ensure_platform
    try:
        plat = ensure_platform()
    except PlatformError as e:
        sys.exit(f"bench: {e}")

    if args.child_cpu:
        if plat["platform"] != "cpu":
            sys.exit("bench --child-cpu is the CPU baseline: it runs "
                     "under JAX_PLATFORMS=cpu only")
        print(json.dumps(run_all()))
        return

    t_setup = time.time()
    configs = run_all()
    setup_s = time.time() - t_setup
    kernels = bench_kernels()
    # dispatch amortisation belongs with the chip numbers: how many
    # program launches the host pays per 1000 tiles, per leg
    cw = configs.get("cfg_wave") or {}
    if cw.get("wave"):
        kernels["wave_dispatch"] = {
            "dispatches_per_1k_tiles": {
                "per_call": cw["per_call"]["dispatches_per_1k_tiles"],
                "wave": cw["wave"]["dispatches_per_1k_tiles"]},
            "occupancy": cw["wave"]["occupancy"],
            "amortisation_x": cw.get("value")}
    co = configs.get("cfg_occupancy") or {}
    if co.get("pipelined"):
        # the inter-wave host gap belongs with the chip numbers:
        # how long the device sits idle between wave dispatches,
        # per ticker leg, and the idle fraction that gap implies
        kernels["interwave_gap_ms"] = {
            "sync": {
                "p50": co["synchronous"]["gap_ms_p50"],
                "p99": co["synchronous"]["gap_ms_p99"]},
            "pipelined": {
                "p50": co["pipelined"]["gap_ms_p50"],
                "p99": co["pipelined"]["gap_ms_p99"]},
            "device_idle_fraction": {
                "sync":
                    co["synchronous"]["device_idle_fraction"],
                "pipelined":
                    co["pipelined"]["device_idle_fraction"]},
            "gap_reduction_x": co.get("value"),
            "parity_bit_exact": co.get("parity_bit_exact")}
    cp = configs.get("cfg_plan") or {}
    if cp.get("plan_on"):
        # gathered HBM bytes belong with the chip numbers: what
        # the superblock plan actually pulled pool->VMEM per leg
        kernels["gathered_hbm_bytes"] = {
            "plan_off": cp["plan_off"]["gathered_bytes"],
            "plan_on": cp["plan_on"]["gathered_bytes"],
            "reduction": cp.get("value"),
            "superblocks": cp["plan_on"]["superblocks"],
            "routes": cp["plan_on"]["routes"]}
    cn = configs.get("cfg_animation") or {}
    if cn.get("temporal_wave"):
        # temporal-wave amortisation belongs with the chip
        # numbers: device programs and gathered pool->VMEM bytes
        # per animation SEQUENCE, per leg, plus e2e p50 per frame
        kernels["temporal_wave"] = {
            "dispatches_per_sequence": {
                "per_frame":
                    cn["per_frame"]["dispatches_per_sequence"],
                "temporal_wave":
                    cn["temporal_wave"]["dispatches_per_sequence"]},
            "gathered_hbm_bytes": {
                "per_frame": cn["per_frame"]["gathered_bytes"],
                "temporal_wave":
                    cn["temporal_wave"]["gathered_bytes"],
                "reduction": cn.get("value")},
            "frame_p50_ms": {
                "per_frame": cn["per_frame"]["frame_p50_ms"],
                "temporal_wave":
                    cn["temporal_wave"]["frame_p50_ms"]},
            "superblocks": cn["temporal_wave"]["superblocks"],
            "programs_ok": cn.get("programs_ok"),
            "parity_bit_exact": cn.get("parity_bit_exact")}
    ca = configs.get("cfg_algebra") or {}
    if ca.get("fused"):
        # expression fusion belongs with the chip numbers: one
        # paged program per structure vs a dispatch per tile, and
        # the pool->VMEM bytes the merged cross-band gather saves
        kernels["expr_fusion"] = {
            "paged_dispatches_per_1k_tiles": {
                "unfused": ca["unfused"]["dispatches_per_1k_tiles"],
                "fused": ca["fused"]["dispatches_per_1k_tiles"]},
            "gathered_hbm_bytes": {
                "unfused": ca["unfused"]["gathered_bytes"],
                "fused": ca["fused"]["gathered_bytes"],
                "reduction": ca.get("gathered_bytes_reduction")},
            "programs_compiled": {
                "unfused": ca["unfused"]["programs_compiled"],
                "fused": ca["fused"]["programs_compiled"]},
            "dispatch_reduction": ca.get("value"),
            "parity_byte_exact": ca.get("parity_byte_exact"),
            "parity_f32_max_ulp": ca.get("parity_f32_max_ulp")}
    cm = configs.get("cfg_mesh") or {}
    if cm.get("mesh"):
        kernels["mesh_dispatch"] = {
            "chips": cm.get("chips"),
            "mpix_s": {"single_chip": cm["single_chip"]["mpix_s"],
                       "mesh": cm["mesh"]["mpix_s"]},
            "scaling_efficiency": cm.get("scaling_efficiency"),
            "dispatches_per_1k_tiles": {
                "single_chip":
                    cm["single_chip"]["dispatches_per_1k_tiles"],
                "mesh": cm["mesh"]["dispatches_per_1k_tiles"]},
            "tiles_per_dispatch_per_chip": {
                "single_chip":
                    cm["single_chip"]["tiles_per_dispatch_per_chip"],
                "mesh": cm["mesh"]["tiles_per_dispatch_per_chip"]},
            "waves_by_layout": cm["mesh"]["waves_by_layout"]}

    # CPU baseline: the same workloads in a child TOLD to use the CPU
    # (JAX_PLATFORMS=cpu — it never asks for the chip this parent
    # holds).  Its numbers are labelled platform cpu; none of them is
    # a chip number.
    if plat["platform"] == "cpu":
        baseline = configs
        baseline_src = "self (bench already on CPU)"
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child-cpu"],
                capture_output=True, timeout=3600, env=env, text=True)
            if r.returncode != 0:
                raise RuntimeError(
                    f"child exited {r.returncode}: {r.stderr[-500:]}")
            baseline = json.loads(r.stdout.strip().splitlines()[-1])
            baseline_src = ("platform cpu: this repo's CPU path in a "
                            "JAX_PLATFORMS=cpu child")
        except Exception as e:  # noqa: BLE001 - report, don't die
            baseline = None
            baseline_src = f"CPU baseline failed: {e}"

    head = configs["cfg3_mosaic"]
    result = {
        "metric": "WMS GetMap tiles/sec (256x256 EPSG:3857, "
                  f"{N_SCENES}-scene Landsat mosaic, e2e incl. decode+PNG)",
        "value": head["value"],
        "unit": "tiles/sec",
        "vs_baseline": (_ratio("cfg3_mosaic", configs, baseline)
                        if baseline else None),
        "baseline": baseline_src,
        "platform": plat["platform"],
        "device_kind": plat["device_kind"],
        "device_count": plat["device_count"],
        "setup_s": round(setup_s, 1),
        "p50_tile_ms": head["latency"]["p50_ms"],
        "configs": configs,
        "device_kernels": kernels,
        "host_overhead": _host_overhead(configs, kernels),
        "cpu_baseline": baseline if baseline is not configs else None,
        "vs_baseline_per_config": (
            {k: _ratio(k, configs, baseline) for k in configs}
            if baseline else None),
        "cfg5_cold_vs_baseline": (
            round(baseline["cfg5_drill_1000"]["cold_s"]
                  / configs["cfg5_drill_1000"]["cold_s"], 2)
            if baseline and configs["cfg5_drill_1000"].get("cold_s")
            else None),
        "vs_ref_anecdote": round(head["value"] * REF_TILE_SECONDS, 2),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
