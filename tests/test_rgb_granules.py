"""Three-band (true-colour) tiles over adjacent Sentinel-2-like granules
against the plain reference (`benchmarks/reference_rgb.py`): through
`render_staged` and through HTTP, for a tile inside one granule, on an
overlap strip and on the four-corner overlap (`render_rgba_ctrl` over
one, two and four granules), over a granule's nodata wedge that its
neighbour fills, over the archive's outer edge and off the data; and a
band set that is no true-colour triple, which the per-band kernel
serves (`render_scenes_bands_ctrl`).  The archive is the benchmark's
own kind (`benchmarks/archives/sentinel2_granules.py`) at a small size;
the route counter says which program served each tile."""

import asyncio
import datetime as dt
import json

import numpy as np
import pytest

from benchmarks import reference, reference_rgb
from benchmarks.archives import sentinel2_granules as s2
from gsky_tpu.geo.crs import EPSG3857
from gsky_tpu.geo.transform import BBox
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.io.png import decode_png
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
from gsky_tpu.pipeline.tile_stages import render_staged
from gsky_tpu.server.config import ConfigWatcher
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

SEED = 27
X0, Y0 = 399960.0, 6200020.0
ARCHIVE = {
    "kind": "sentinel2_granules", "collection": "s2",
    "file_prefix": "S2A_T55H", "crs": "EPSG:32755", "origin": [X0, Y0],
    "res": 10.0, "granule_hw": [320, 320], "pitch_m": 2900.0,
    "grid": [2, 2], "date": "2020-01-10",
    "bands": [{"name": "red", "namespace": "nbart_red", "base": 1600},
              {"name": "green", "namespace": "nbart_green", "base": 1450},
              {"name": "blue", "namespace": "nbart_blue", "base": 1300}],
    "nodata": -999, "wedge_px": 24, "compress": False}
NAMESPACES = [b["namespace"] for b in ARCHIVE["bands"]]
RED_GREEN_RED = [NAMESPACES[0], NAMESPACES[1], NAMESPACES[0]]
TIME = s2.dates(ARCHIVE)[0]
CLIP, SCALE = 3000.0, 254.0 / 3000.0
# f32 source coordinates and f32 tap sums against float64: a byte on a
# level's edge falls to the other side in well under 0.5 % of bytes
# (the traffic file's bound), by one level
BOUND = 0.005

# name -> (centre in UTM metres from the archive's corner, half-size in
# metres, granules the tile touches, the program that serves it)
CASES = {
    "interior": ((1000.0, -1000.0), 320.0, 1, "rgba"),
    "overlap_strip": ((3050.0, -900.0), 320.0, 2, "rgba"),
    "four_corner": ((3050.0, -3050.0), 320.0, 4, "rgba"),
    # granule (0, 0) lacks the last 17..22 columns of rows 230..290:
    # granule (0, 1) fills them
    "nodata_wedge": ((3080.0, -2600.0), 150.0, 2, "rgba"),
    "outer_edge": ((0.0, -1500.0), 320.0, 1, "rgba"),
    "off_the_data": ((-2000.0, -1500.0), 320.0, 0, "empty"),
}


def _bbox(case):
    (cx, cy), half, _, _ = CASES[case]
    xs = np.array([X0 + cx - half, X0 + cx + half])
    ys = np.array([Y0 + cy - half, Y0 + cy + half])
    mx, my = reference.project(xs, ys, ARCHIVE["crs"], "EPSG:3857")
    return (float(mx[0]), float(my[0]), float(mx[1]), float(my[1]))


def _want(sources, bbox, namespaces=NAMESPACES):
    return reference_rgb.render_rgba(
        reference_rgb.select_rgb(
            sources, namespaces,
            dt.datetime.fromisoformat(ARCHIVE["date"]).replace(
                tzinfo=dt.timezone.utc).timestamp()),
        bbox, "EPSG:3857", 256, 256, "bilinear", 0.0, SCALE, CLIP)


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("s2rgb")
    store = MASStore()
    for rec in s2.build(ARCHIVE, SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    conf = root / "conf"
    conf.mkdir()
    (conf / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [dict(style, name=name, rgb_products=bands)
                   for name, bands in (("truecolour", NAMESPACES),
                                       ("red_green_red", RED_GREEN_RED))
                   for style in [{
                       "data_source": str(root / "s2"),
                       "resample": "bilinear", "time_generator": "mas",
                       "offset_value": 0.0, "clip_value": CLIP,
                       "scale_value": SCALE}]]}))
    mas = MASClient(store)
    watcher = ConfigWatcher(str(conf), mas_factory=lambda addr: mas,
                            install_signal=False)
    server = OWSServer(watcher, mas_factory=lambda addr: mas,
                       metrics=MetricsLogger(), gateway=None)
    return {"server": server, "mas": mas, "root": str(root / "s2"),
            "sources": s2.sources(ARCHIVE, SEED)}


def _check(got, want, case):
    rec = reference_rgb.compare(got, want)
    assert rec["mismatch"] <= BOUND, rec
    assert rec["max_byte_diff"] <= 1, rec
    opaque = float(np.mean(want[..., 3] == 255))
    if case == "outer_edge":
        assert 0.3 < opaque < 0.7       # half the tile lies off the data
    else:
        assert opaque == 1.0            # the neighbour fills the wedge


@pytest.mark.parametrize("window", ["whole_scene", "gather_window"])
@pytest.mark.parametrize("case", list(CASES))
def test_render_staged_matches_reference(env, case, window, monkeypatch):
    """Both as the CPU serves it (whole scenes) and as the chip does:
    from a gather window of each band, each granule's at its own
    origin (`executor._gather_windows`)."""
    monkeypatch.setenv("GSKY_WARP_WINDOW",
                       "1" if window == "gather_window" else "0")
    bbox = _bbox(case)
    stamp = dt.datetime.fromisoformat(ARCHIVE["date"]).replace(
        tzinfo=dt.timezone.utc).timestamp()
    req = GeoTileRequest(
        collection=env["root"], bands=NAMESPACES, bbox=BBox(*bbox),
        crs=EPSG3857, width=256, height=256, start_time=stamp,
        end_time=None, resample="bilinear")
    pipe = TilePipeline(env["mas"])
    legs0 = dict(pipe.executor.bucket_stats)
    made = render_staged(pipe, req, 3, 0.0, SCALE, CLIP, 0, False)
    if case == "off_the_data":          # no granule: no fused program
        assert made is None
        return
    kind, arr = made
    assert kind == CASES[case][3]
    leg, = (k for k, n in pipe.executor.bucket_stats.items()
            if n != legs0.get(k, 0))
    if window == "gather_window":
        # a 64-px footprint: never the 512-px bucket, however far apart
        # the granules it touches lie
        assert leg.endswith(("(96, 96))", "(128, 128))", "(96, 128))",
                             "(128, 96))", "(64, 96))", "(96, 64))",
                             "(64, 64))", "(64, 128))", "(128, 64))")), leg
    else:
        assert leg.endswith("None)"), leg
    if kind == "planes":                # (3, H, W) -> RGBA by the rule
        rgb = np.moveaxis(arr, 0, -1)
        alpha = np.where((rgb == 255).all(-1), 0, 255).astype(np.uint8)
        arr = np.concatenate([rgb, alpha[..., None]], -1)
    _check(arr, _want(env["sources"], bbox), case)


def _get(server, path):
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


def _getmap(server, layer, bbox):
    """(body, the routes that the request moved)."""
    before = server.metrics.summary()["rgb_routes"]
    status, body = _get(server, (
        f"/ows?service=WMS&request=GetMap&version=1.3.0&layers={layer}"
        f"&crs=EPSG:3857&bbox={bbox[0]!r},{bbox[1]!r},{bbox[2]!r},"
        f"{bbox[3]!r}&width=256&height=256&format=image/png&time={TIME}"))
    assert status == 200, body[:300]
    after = server.metrics.summary()["rgb_routes"]
    return body, {k: after[k] - before[k] for k in after
                  if after[k] != before[k]}


@pytest.mark.parametrize("case", list(CASES))
def test_http_matches_reference_and_counts_route(env, case):
    bbox = _bbox(case)
    body, moved = _getmap(env["server"], "truecolour", bbox)
    assert moved == {CASES[case][3]: 1}
    got = decode_png(body)
    if case == "off_the_data":          # the empty tile: no colour to hold
        assert (got[..., 3] == 0).all()
    else:
        _check(got, _want(env["sources"], bbox), case)


@pytest.mark.parametrize("case", ["interior", "overlap_strip",
                                  "nodata_wedge"])
def test_no_true_colour_triple_takes_the_per_band_kernel(env, case):
    """Two namespaces in three channels: `render_scenes_bands_ctrl`,
    from the same resident bands, each granule's window at its own
    origin, held to the same reference."""
    bbox = _bbox(case)
    body, moved = _getmap(env["server"], "red_green_red", bbox)
    assert moved == {"planes": 1}
    _check(decode_png(body), _want(env["sources"], bbox, RED_GREEN_RED),
           case)


def test_cases_touch_the_granules_they_name(env):
    """The cases are what their names say: the generator's own count of
    granules under each footprint."""
    from benchmarks.generators.xyz_rgb_sessions import Generator
    gen = Generator({"zoom_shares": {"15": 1.0}, "layers": {"truecolour": 1}},
                    {"archive": ARCHIVE, "layers": [
                        {"name": "truecolour", "rgb_products": NAMESPACES}]},
                    s2, SEED)
    for case, (_, _, granules, _) in CASES.items():
        assert gen.granules_touched("truecolour", TIME, _bbox(case)) \
            == granules, case


def test_a_dropped_granule_or_swapped_channel_shows(env):
    """What the bound has to catch: the reference without granule
    (0, 1) leaves (0, 0)'s wedge transparent, and with red and blue
    swapped nearly every byte differs."""
    bbox = _bbox("nodata_wedge")
    want = _want(env["sources"], bbox)
    fewer = [s for s in env["sources"] if s.x0 == X0]
    assert reference_rgb.compare(_want(fewer, bbox), want)["mismatch"] > BOUND
    swapped = want[..., [2, 1, 0, 3]]
    assert reference_rgb.compare(swapped, want)["mismatch"] > 0.3
