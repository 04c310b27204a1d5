"""Band sets whose channels lie on several pixel grids of one granule:
Sentinel-2's 20 m SWIR beside its 10 m NIR and green, served by the
fused band-set kernels (`ops.warp.render_rgba_ctrl`, `render_expr_ctrl`
with `grid_of`) through `executor.render_rgba_byte` and
`render_expr_byte`.

Held to the plain references (`benchmarks/reference_rgb.py`,
`reference_expr.py`), through `render_staged` and over HTTP, on a tile
inside one granule, on an overlap strip, on the four-corner overlap and
over a granule's nodata wedge (whose edge the 10 m and 20 m bands draw a
pixel apart); a one-grid set traces the program it traced before grids
existed and renders the same bytes (the parent's `_mosaic_band_sets` is
kept here as that reference); and the grouping rules with the
`band_grids` counters.  The archive is the benchmark's own kind
(`benchmarks/archives/sentinel2_bands_by_res.py`) at a small size."""

import asyncio
import datetime as dt
import importlib
import json
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference, reference_expr, reference_rgb
from benchmarks.archives import sentinel2_bands_by_res as s2r
from gsky_tpu.geo.crs import EPSG3857, parse_crs
from gsky_tpu.geo.transform import BBox, GeoTransform
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.io.png import decode_png
from gsky_tpu.ops import paged
from gsky_tpu.ops.expr import compile_expr, fingerprint
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
from gsky_tpu.pipeline import executor as ex_mod
from gsky_tpu.pipeline.executor import (WarpExecutor, _grid_sets,
                                        _grid_windows)
from gsky_tpu.pipeline.tile_stages import render_staged
from gsky_tpu.server.config import ConfigWatcher
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

# the module: the package exports a function of that name too
warp = importlib.import_module("gsky_tpu.ops.warp")

SEED = 43
X0, Y0 = 399960.0, 6200020.0
ARCHIVE = {
    "kind": "sentinel2_bands_by_res", "collection": "s2",
    "file_prefix": "S2A_T55H", "crs": "EPSG:32755", "origin": [X0, Y0],
    "pitch_m": 2900.0, "grid": [2, 2], "date": "2020-01-10",
    "resolutions": {
        "r10m": {"res": 10.0, "granule_hw": [320, 320], "wedge_px": 24},
        "r20m": {"res": 20.0, "granule_hw": [160, 160], "wedge_px": 12}},
    "bands": [
        {"name": "green", "namespace": "nbart_green", "base": 900,
         "resolution": "r10m"},
        {"name": "nir", "namespace": "nbart_nir_1", "base": 3200,
         "resolution": "r10m"},
        {"name": "swir2", "namespace": "nbart_swir_2", "base": 2400,
         "resolution": "r20m"},
        {"name": "swir3", "namespace": "nbart_swir_3", "base": 1600,
         "resolution": "r20m"}],
    "nodata": -999, "compress": False}
TIME = s2r.dates(ARCHIVE)[0]
STAMP = dt.datetime.fromisoformat(ARCHIVE["date"]).replace(
    tzinfo=dt.timezone.utc).timestamp()
FALSE_COLOUR = ["nbart_swir_2", "nbart_nir_1", "nbart_green"]
NBR = "nbr = (nbart_nir_1 - nbart_swir_3) / (nbart_nir_1 + nbart_swir_3)"
# the configuration's styles (benchmarks/configs/sentinel2-swir.json)
STYLES = {"falsecolour": (0.0, 254.0 / 4500.0, 4500.0),
          "nbr": (1.0, 127.0, 2.0)}
PALETTE = {"interpolate": True, "colours": [
    {"R": 120, "G": 0, "B": 0, "A": 255},
    {"R": 230, "G": 90, "B": 30, "A": 255},
    {"R": 250, "G": 220, "B": 120, "A": 255},
    {"R": 160, "G": 210, "B": 110, "A": 255},
    {"R": 20, "G": 120, "B": 50, "A": 255}]}
# the cell's bound (traffic/swir-pan-cold.json): 0.5 % of a tile's
# bytes, no byte further than one level
BOUND = 0.005

# name -> (centre in UTM metres from the archive's corner, half-size in
# metres, granule sets the tile touches)
CASES = {
    "interior": ((1000.0, -1000.0), 320.0, 1),
    "overlap_strip": ((3050.0, -900.0), 320.0, 2),
    "four_corner": ((3050.0, -3050.0), 320.0, 4),
    # granule (0, 0) lacks a wedge along its east edge that granule
    # (0, 1) fills, 10 m and 20 m bands a pixel apart along its edge
    "nodata_wedge": ((3080.0, -2600.0), 150.0, 2),
}


def _bbox(case):
    (cx, cy), half, _ = CASES[case]
    xs = np.array([X0 + cx - half, X0 + cx + half])
    ys = np.array([Y0 + cy - half, Y0 + cy + half])
    mx, my = reference.project(xs, ys, ARCHIVE["crs"], "EPSG:3857")
    return (float(mx[0]), float(my[0]), float(mx[1]), float(my[1]))


def _want(sources, layer, bbox):
    offset, scale, clip = STYLES[layer]
    if layer == "falsecolour":
        return reference_rgb.render_rgba(
            reference_rgb.select_rgb(sources, FALSE_COLOUR, STAMP), bbox,
            "EPSG:3857", 256, 256, "bilinear", offset, scale, clip)
    text = reference_expr.split_product(NBR)[1]
    names = reference_expr.variables(reference_expr.parse(text))
    return reference_expr.render_byte(
        text, reference_expr.select_vars(sources, names, STAMP), bbox,
        "EPSG:3857", 256, 256, "bilinear", offset, scale, clip)


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("s2swir")
    store = MASStore()
    for rec in s2r.build(ARCHIVE, SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    conf = root / "conf"
    conf.mkdir()
    layers = []
    for name, products in (("falsecolour", FALSE_COLOUR), ("nbr", [NBR])):
        offset, scale, clip = STYLES[name]
        layers.append(dict({
            "name": name, "rgb_products": products,
            "data_source": str(root / "s2"), "resample": "bilinear",
            "time_generator": "mas", "offset_value": offset,
            "clip_value": clip, "scale_value": scale},
            **({"palette": PALETTE} if name == "nbr" else {})))
    (conf / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": layers}))
    mas = MASClient(store)
    watcher = ConfigWatcher(str(conf), mas_factory=lambda addr: mas,
                            install_signal=False)
    server = OWSServer(watcher, mas_factory=lambda addr: mas,
                       metrics=MetricsLogger(), gateway=None)
    return {"server": server, "mas": mas, "root": str(root / "s2"),
            "sources": s2r.sources(ARCHIVE, SEED)}


def _request(env, layer, bbox):
    return GeoTileRequest(
        collection=env["root"],
        bands=FALSE_COLOUR if layer == "falsecolour" else [NBR],
        bbox=BBox(*bbox), crs=EPSG3857, width=256, height=256,
        start_time=STAMP, end_time=None, resample="bilinear")


def _check(got, want):
    compare = reference_rgb.compare if got.ndim == 3 \
        else reference_expr.compare
    rec = compare(got, want)
    assert rec["mismatch"] <= BOUND and rec["max_byte_diff"] <= 1, rec


# --- the tiles, through render_staged and over HTTP ---------------------------

@pytest.mark.parametrize("window", ["whole_scene", "gather_window"])
@pytest.mark.parametrize("layer", ["falsecolour", "nbr"])
@pytest.mark.parametrize("case", list(CASES))
def test_render_staged_matches_reference(env, case, layer, window,
                                         monkeypatch):
    """Both as the CPU serves it (whole scenes) and as the chip does:
    from each grid's own gather window, the 20 m one derived from the
    10 m one (`executor._grid_windows`)."""
    monkeypatch.setenv("GSKY_WARP_WINDOW",
                       "1" if window == "gather_window" else "0")
    bbox = _bbox(case)
    pipe = TilePipeline(env["mas"], executor=WarpExecutor())
    made = render_staged(pipe, _request(env, layer, bbox),
                         3 if layer == "falsecolour" else 1,
                         *STYLES[layer], 0, False)
    kind, got = made
    assert kind == ("rgba" if layer == "falsecolour" else "composite")
    (leg, n), = pipe.executor.bucket_stats.items()
    name = "render_rgba_mg" if layer == "falsecolour" else "render_expr_mg"
    chans = 3 if layer == "falsecolour" else 2
    assert n == 1 and leg.startswith(
        f"{name}:(({CASES[case][2]}, 2, {chans}), "), leg
    assert leg.endswith("None)") == (window == "whole_scene"), leg
    assert pipe.executor.band_grids == {
        "sets_one_grid": 0, "sets_multi_grid": CASES[case][2],
        "multi_grid_declined": 0}
    want = _want(env["sources"], layer, bbox)
    _check(got, want)
    if layer == "falsecolour":
        assert (want[..., 3] == 255).all()      # the neighbour fills it
    else:
        assert (want != 255).all()


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


def _moved(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("layer", ["falsecolour", "nbr"])
@pytest.mark.parametrize("case", list(CASES))
def test_http_takes_the_fused_leg(env, case, layer):
    """A GetMap: the three-band route counts `rgba`, the expression
    route `bucketed`, and `/debug` `band_grids` counts the tile's sets
    as sets on several grids."""
    server = env["server"]

    def state():
        doc = json.loads(_get(server, "/debug")[1])
        return {"routes": server.metrics.summary()["rgb_routes"],
                "paths": paged.expr_fused_stats()["paths"],
                "grids": doc["band_grids"]}
    s0 = state()
    bbox = _bbox(case)
    status, body = _get(server, (
        f"/ows?service=WMS&request=GetMap&version=1.3.0&layers={layer}"
        f"&crs=EPSG:3857&bbox={bbox[0]!r},{bbox[1]!r},{bbox[2]!r},"
        f"{bbox[3]!r}&width=256&height=256&format=image/png&time={TIME}"))
    assert status == 200, body[:300]
    s1 = state()
    if layer == "falsecolour":
        assert _moved(s0["routes"], s1["routes"]) == {"rgba": 1}
        got = decode_png(body)
    else:
        assert _moved(s0["paths"], s1["paths"]) == {"bucketed": 1}
        from PIL import Image
        import io
        got = np.asarray(Image.open(io.BytesIO(body)))
    assert _moved(s0["grids"], s1["grids"]) == {
        "sets_multi_grid": CASES[case][2]}
    _check(got, _want(env["sources"], layer, bbox))


def test_the_bound_sees_a_wrong_grid_or_a_dropped_band(env):
    """What the bound has to catch on these tiles: the 20 m band read as
    if it lay on the 10 m grid, and the SWIR channel lost."""
    import dataclasses
    bbox = _bbox("overlap_strip")
    want = _want(env["sources"], "falsecolour", bbox)
    wrong = [dataclasses.replace(s, dx=10.0, dy=-10.0)
             if s.dx == 20.0 else s for s in env["sources"]]
    assert reference_rgb.compare(_want(wrong, "falsecolour", bbox),
                                 want)["mismatch"] > 10 * BOUND
    lost = [s for s in env["sources"] if s.namespace != "nbart_swir_2"]
    assert reference_rgb.compare(_want(lost, "falsecolour", bbox),
                                 want)["mismatch"] > 10 * BOUND


# --- a one-grid set is the program it was ---------------------------------------

def _parent_mosaic_band_sets(granules, ctrl, params, prios, method,
                             out_hw, step, win, win0, grid_of=None,
                             crs_of=None):
    """`ops.warp._mosaic_band_sets` as it was before a set could span
    several grids, line for line (and before sets could lie in several
    CRSs: ``crs_of`` is the later keyword, None here)."""
    assert grid_of is None and crs_of is None
    h, w = out_hw
    C = len(granules[0])
    sx = warp._bilerp_grid(ctrl[0], h, w, step)
    sy = warp._bilerp_grid(ctrl[1], h, w, step)
    data = jnp.zeros((h, w, C), jnp.float32)
    best = jnp.full((h, w, C), -jnp.inf, jnp.float32)
    for k, bands in enumerate(granules):
        p = params[k]
        cols = (p[0] + p[1] * sx + p[2] * sy) - 0.5
        rows = (p[3] + p[4] * sx + p[5] * sy) - 0.5
        oob = (rows < -0.5) | (rows > p[6] - 0.5) \
            | (cols < -0.5) | (cols > p[7] - 0.5)
        rows = jnp.where(oob, jnp.nan, rows)
        if win is not None:
            cut = [warp._window_slice(b, win, win0[k], axis=0)
                   for b in bands]
            bands = [c[0] for c in cut]
            rows = rows - cut[0][1]
            cols = cols - cut[0][2]
        d, o = warp._resample_c(jnp.stack(bands, axis=-1), p[8], rows,
                                cols, method)
        score = jnp.where(o, prios[k], -jnp.inf)
        take = score > best
        data = jnp.where(take, d, data)
        best = jnp.where(take, score, best)
    return data, best


def _one_grid_operands(G, C, windowed, seed=7):
    """G sets of C (96, 96) scenes on one grid, NaN holes and a nodata
    block, the control grid of a 64-px tile over them."""
    rng = np.random.default_rng(seed)
    bands = []
    for k in range(G):
        got = []
        for c in range(C):
            a = rng.uniform(500, 4000, (96, 96)).astype(np.float32)
            a[10 + k:20, 30 + c:40] = np.nan
            a[50:60, :8 + k] = -999.0
            got.append(jnp.asarray(a))
        bands.append(tuple(got))
    params = np.zeros((G, 11), np.float32)
    for k in range(G):
        params[k] = [-3.0 * k, 1.0, 0.0, 2.0 * k, 0.0, 1.0, 90, 92,
                     -999.0, 0.0, 0.0]
    gh = gw = (64 - 1 + 15) // 16 + 1
    ctrl = np.stack(np.meshgrid(np.linspace(3.0, 70.0, gw),
                                np.linspace(5.0, 66.0, gh))).astype(
                                    np.float32)
    prios = rng.permutation(G * C).reshape(G, C).astype(np.float32) + 1.0
    win = (64, 64) if windowed else None
    win0 = jnp.asarray(np.array([[4, 2 + k] for k in range(G)], np.int32)) \
        if windowed else None
    return (tuple(bands), jnp.asarray(ctrl), jnp.asarray(params),
            jnp.asarray(prios)), win, win0


KERNELS = {
    "truecolour": (3, None),
    "ndvi": (2, "(a - b) / (a + b)"),
    "evi": (3, "2.5 * (a - b) / (a + 6 * b - 7.5 * c + 10000)"),
}


@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_one_grid_is_the_parents_program(kernel, G, windowed, monkeypatch):
    """The jaxpr of a one-grid call and its bytes are those of the
    parent's `_mosaic_band_sets`: the true-colour and NDVI/EVI cells run
    the program they ran."""
    C, text = KERNELS[kernel]
    operands, win, win0 = _one_grid_operands(G, C, windowed)
    sp = jnp.asarray(np.array([0.0, 254.0 / 3000.0, 3000.0], np.float32))
    if text is None:
        fn = warp.render_rgba_ctrl.__wrapped__
        kw = dict(method="bilinear", out_hw=(64, 64), step=16, auto=False,
                  colour_scale=0, win=win)
        args = operands + (sp,)
    else:
        fp = fingerprint(compile_expr(text))
        fn = warp.render_expr_ctrl.__wrapped__
        kw = dict(fp=fp.key, method="bilinear", out_hw=(64, 64), step=16,
                  auto=False, colour_scale=0, win=win)
        args = operands + (jnp.asarray(np.array([0.0, 254.0, 1.0],
                                                np.float32)),
                           jnp.asarray(fp.const_array()))

    def run():
        traced = jax.make_jaxpr(lambda *a: fn(*a, win0=win0, **kw))(*args)
        return str(traced), np.asarray(jax.jit(
            lambda *a: fn(*a, win0=win0, **kw))(*args))
    now_jaxpr, now = run()
    monkeypatch.setattr(warp, "_mosaic_band_sets", _parent_mosaic_band_sets)
    then_jaxpr, then = run()
    assert now_jaxpr == then_jaxpr
    np.testing.assert_array_equal(now, then)
    assert (now != 255).any()


# --- the grouping rules -----------------------------------------------------------

def _scene(x0, y0, res, n, crs="EPSG:32755", nodata=-999.0):
    bucket = max(256, -(-n // 256) * 256)
    return SimpleNamespace(
        gt=GeoTransform(x0, res, 0.0, y0, 0.0, -res), crs=parse_crs(crs),
        height=n, width=n, nodata=nodata, bucket=(bucket, bucket),
        dtype=np.dtype(np.float32))


def _granule(x0, y0, crs="EPSG:32755"):
    """A false-colour granule: SWIR at 20 m, NIR and green at 10 m."""
    return [_scene(x0, y0, 20.0, 5490, crs), _scene(x0, y0, 10.0, 10980, crs),
            _scene(x0, y0, 10.0, 10980, crs)]


def test_a_granules_bands_form_one_set_on_two_grids():
    made = _grid_sets(_granule(0.0, 0.0), [0, 1, 2], 3)
    assert made == ([[0, 1, 2]], (1, 0, 0))     # the 10 m grid first
    # an RGB request's channel order
    assert _grid_sets(_granule(0.0, 0.0), [0, 1, 2], 3, order=[1, 2, 0]) \
        == ([[1, 2, 0]], (0, 0, 1))


def test_adjacent_granules_never_merge():
    scenes = _granule(0.0, 0.0) + _granule(100000.0, 0.0)
    sets, grid_of = _grid_sets(scenes, [0, 1, 2] * 2, 3)
    assert sets == [[0, 1, 2], [3, 4, 5]] and grid_of == (1, 0, 0)


@pytest.mark.parametrize("spoil", [
    "lacks_a_channel", "two_dates", "four_crs", "other_split", "four_grids"])
def test_what_declines(spoil):
    scenes = _granule(0.0, 0.0) + _granule(100000.0, 0.0)
    chans = [0, 1, 2] * 2
    n_chan = 3
    if spoil == "lacks_a_channel":
        scenes, chans = scenes[:5], chans[:5]
    elif spoil == "two_dates":
        scenes, chans = scenes + _granule(0.0, 0.0), chans + [0, 1, 2]
    elif spoil == "four_crs":
        # sets in more CRSs than `executor._MAX_CRS` control grids
        scenes = [s for z in range(4)
                  for s in _granule(100000.0 * z, 0.0, f"EPSG:3275{4 + z}")]
        chans = [0, 1, 2] * 4
    elif spoil == "other_split":
        # the second granule's green at 20 m: its sets split otherwise
        scenes[5] = _scene(100000.0, 0.0, 20.0, 5490)
    else:
        scenes = [_scene(0.0, 0.0, res, int(109800 // res))
                  for res in (10.0, 20.0, 30.0, 60.0)]
        chans, n_chan = [0, 1, 2, 3], 4
    assert _grid_sets(scenes, chans, n_chan) is None
    ex = WarpExecutor()
    ex._note_grids(None, scenes)
    assert ex.band_grids == {"sets_one_grid": 0, "sets_multi_grid": 0,
                             "multi_grid_declined": 1}


def test_the_counters_count_sets_by_their_grids():
    ex = WarpExecutor()
    one = [_scene(0.0, 0.0, 10.0, 10980) for _ in range(3)]
    ex._note_grids(_grid_sets(one, [0, 1, 2], 3), one)
    two = _granule(0.0, 0.0) + _granule(100000.0, 0.0)
    ex._note_grids(_grid_sets(two, [0, 1, 2] * 2, 3), two)
    # a one-grid list that forms no set is no multi-grid decline
    ex._note_grids(None, one[:2])
    assert ex.band_grids == {"sets_one_grid": 1, "sets_multi_grid": 2,
                             "multi_grid_declined": 0}


@pytest.mark.parametrize("px", [0.39, 0.8, 1.6, 3.1])
def test_a_coarser_window_follows_from_the_finest(px):
    """The 20 m window's size is a function of the 10 m window's alone,
    and it holds the 20 m footprint, for tiles from 0.39 to 3.1 source
    pixels a pixel over the granule's corner and its middle."""
    cx = np.linspace(0.0, 255.0 * px * 10.0, 17)
    for x, y in ((0.0, 0.0), (52000.0, -61000.0), (108500.0, -1200.0)):
        ctrl = np.meshgrid(x + cx, y - cx)
        rows = []
        for res, n in ((10.0, 10980), (20.0, 5490)):
            rows.append(list(ex_mod._inv_gt_params(
                GeoTransform(0.0, res, 0.0, 0.0, 0.0, -res), 0.0, 0.0))
                + [n, n, -999.0, 0.0, 0.0])
        params = np.array([rows, rows], np.float64)
        params[1, :, 10] = -1.0                 # a padding set
        made = _grid_windows(params, ctrl[0], ctrl[1],
                             [(11008, 11008), (5632, 5632)])
        assert made is not None
        (fine, coarse), win0 = made
        size = {64: 64, 96: 64, 128: 96, 192: 128, 256: 192, 384: 256,
                512: 384, 768: 512, 1024: 768, 1536: 1024, 2048: 1536}
        assert coarse == (size[fine[0]], size[fine[1]]), (fine, coarse)
        b = ex_mod._granule_bounds(params[0, 1], ctrl[0], ctrl[1])
        r0, c0 = win0[0, 1]
        assert r0 <= max(b[0], 0) and b[1] <= r0 + coarse[0]
        assert c0 <= max(b[2], 0) and b[3] <= c0 + coarse[1]
