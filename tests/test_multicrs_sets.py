"""Band sets of one tile in two CRSs: Sentinel-2 granules either side of
the line between UTM zones 55 and 56 at 150 E, served by the fused
band-set kernels (`ops.warp.render_rgba_ctrl`, `render_expr_ctrl` with
``crs_of``: a control grid a CRS) through `executor.render_rgba_byte`
and `render_expr_byte`.

Held to the plain float64 references (`benchmarks/reference_rgb.py`,
`reference_expr.py`, whose `reference.mosaic` projects every source from
its own CRS), through `render_staged` and over HTTP: true colour and
NDVI on one pixel grid, false colour and NBR with a 20 m band beside 10
m ones, on a tile over the seam, over both rows of both zones, over a
granule's nodata wedge, and inside one zone.  The bound is the cells':
0.5 % of a tile's bytes and one byte level, because the program projects
a 16-px control grid and interpolates it in float32 where the reference
projects every pixel centre in float64, so a value on a level's edge may
fall to the other side.  The bound catches a set warped on the other
zone's grid, a zone's sets dropped and bfloat16 rasters; ties across
zones follow `ops.mosaic.priority_order`; a one-CRS tile traces the
program it traced before (the parent's `_mosaic_band_sets` is kept here
as that reference).  The archive is the benchmark's own kind
(`benchmarks/archives/sentinel2_zone_seam.py`) at a small size."""

import asyncio
import dataclasses
import datetime as dt
import importlib
import io
import json
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import reference, reference_expr, reference_rgb
from benchmarks.archives import sentinel2_zone_seam as seam
from gsky_tpu.geo.crs import EPSG3857, parse_crs
from gsky_tpu.geo.transform import BBox, GeoTransform
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.io.png import decode_png
from gsky_tpu.ops import paged
from gsky_tpu.ops.expr import compile_expr, fingerprint
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
from gsky_tpu.pipeline import executor as ex_mod
from gsky_tpu.pipeline.executor import WarpExecutor, _grid_sets, _set_crs
from gsky_tpu.pipeline.tile_stages import render_staged
from gsky_tpu.server.config import ConfigWatcher
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

# the module: the package exports a function of that name too
warp = importlib.import_module("gsky_tpu.ops.warp")

SEED = 45
ARCHIVE = {
    "kind": "sentinel2_zone_seam", "collection": "s2",
    "zones": [
        {"crs": "EPSG:32755", "x0": 772000.0, "file_prefix": "S2A_T55HG",
         "wedge_edge": "east"},
        {"crs": "EPSG:32756", "x0": 221000.0, "file_prefix": "S2A_T56HK",
         "wedge_edge": "west"}],
    "rows_y0": [6214000.0, 6208200.0], "res": 10.0,
    "granule_hw": [640, 640], "date": "2020-01-10",
    "resolutions": {"r20m": {"res": 20.0, "granule_hw": [320, 320],
                             "wedge_px": 22}},
    "bands": [
        {"name": "red", "namespace": "nbart_red", "base": 1600},
        {"name": "green", "namespace": "nbart_green", "base": 1450},
        {"name": "blue", "namespace": "nbart_blue", "base": 1300},
        {"name": "nir", "namespace": "nbart_nir_1", "base": 3200},
        {"name": "swir3", "namespace": "nbart_swir_3", "base": 1600,
         "resolution": "r20m"}],
    "nodata": -999, "wedge_px": 44, "compress": False}
TIME = seam.dates(ARCHIVE)[0]
STAMP = dt.datetime.fromisoformat(ARCHIVE["date"]).replace(
    tzinfo=dt.timezone.utc).timestamp()
PALETTE = {"interpolate": True, "colours": [
    {"R": 120, "G": 0, "B": 0, "A": 255},
    {"R": 250, "G": 220, "B": 120, "A": 255},
    {"R": 20, "G": 120, "B": 50, "A": 255}]}
# name -> (products, offset, scale, clip, pixel grids a set spans)
LAYERS = {
    "truecolour": (["nbart_red", "nbart_green", "nbart_blue"],
                   0.0, 254.0 / 3000.0, 3000.0, 1),
    "falsecolour": (["nbart_swir_3", "nbart_nir_1", "nbart_green"],
                    0.0, 254.0 / 4500.0, 4500.0, 2),
    "ndvi": (["ndvi = (nbart_nir_1 - nbart_red) / (nbart_nir_1 + nbart_red)"],
             1.0, 127.0, 2.0, 1),
    "nbr": (["nbr = (nbart_nir_1 - nbart_swir_3) / "
             "(nbart_nir_1 + nbart_swir_3)"], 1.0, 127.0, 2.0, 2),
}
# the cells' bound (traffic/seam-pan-cold.json): 0.5 % of a tile's
# bytes, no byte further than one level
BOUND = 0.005

# name -> (centre in zone 55's UTM metres, half-size in metres, granule
# sets the tile reads, zones they lie in).  In zone 55's metres zone 55's
# granules span eastings 772,000-778,400 and zone 56's ~773,300-780,400,
# its northings ~150-350 m north of zone 55's.
CASES = {
    "seam": ((775400.0, 6211900.0), 300.0, 2, 2),
    # the rows of both zones overlap near 6,207,600-6,208,200
    "seam_both_rows": ((775400.0, 6207900.0), 250.0, 4, 2),
    # across the edge of zone 55's wedge, along its east edge and widest
    # at the south, which zone 56's granule fills
    "wedge55": ((777950.0, 6202300.0), 150.0, 2, 2),
    # across the edge of zone 56's wedge, along its west edge and widest
    # at the south, which zone 55's granule fills
    "wedge56": ((773600.0, 6202300.0), 150.0, 2, 2),
    "one_zone": ((773000.0, 6211900.0), 300.0, 1, 1),
}


def _bbox(case):
    (cx, cy), half, _, _ = CASES[case]
    xs = np.array([cx - half, cx + half])
    ys = np.array([cy - half, cy + half])
    mx, my = reference.project(xs, ys, "EPSG:32755", "EPSG:3857")
    return (float(mx[0]), float(my[0]), float(mx[1]), float(my[1]))


def _is_expr(layer):
    return "=" in LAYERS[layer][0][0]


def _want(sources, layer, bbox):
    products, offset, scale, clip, _ = LAYERS[layer]
    if not _is_expr(layer):
        return reference_rgb.render_rgba(
            reference_rgb.select_rgb(sources, products, STAMP), bbox,
            "EPSG:3857", 256, 256, "bilinear", offset, scale, clip)
    text = reference_expr.split_product(products[0])[1]
    names = reference_expr.variables(reference_expr.parse(text))
    return reference_expr.render_byte(
        text, reference_expr.select_vars(sources, names, STAMP), bbox,
        "EPSG:3857", 256, 256, "bilinear", offset, scale, clip)


def _server(store, root, layers):
    conf = root / "conf"
    conf.mkdir()
    lays = []
    for name in layers:
        products, offset, scale, clip, _ = LAYERS[name]
        lays.append(dict({
            "name": name, "rgb_products": products,
            "data_source": str(root / "s2"), "resample": "bilinear",
            "time_generator": "mas", "offset_value": offset,
            "clip_value": clip, "scale_value": scale},
            **({"palette": PALETTE} if _is_expr(name) else {})))
    (conf / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": lays}))
    mas = MASClient(store)
    watcher = ConfigWatcher(str(conf), mas_factory=lambda addr: mas,
                            install_signal=False)
    return mas, OWSServer(watcher, mas_factory=lambda addr: mas,
                          metrics=MetricsLogger(), gateway=None)


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("s2seam")
    store = MASStore()
    for rec in seam.build(ARCHIVE, SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    mas, server = _server(store, root, LAYERS)
    return {"server": server, "mas": mas, "root": str(root / "s2"),
            "sources": seam.sources(ARCHIVE, SEED)}


def _request(env, layer, bbox):
    return GeoTileRequest(
        collection=env["root"], bands=LAYERS[layer][0], bbox=BBox(*bbox),
        crs=EPSG3857, width=256, height=256, start_time=STAMP,
        end_time=None, resample="bilinear")


def _staged(env, layer, bbox, executor=None):
    pipe = TilePipeline(env["mas"], executor=executor or WarpExecutor())
    _, offset, scale, clip, _ = LAYERS[layer]
    made = render_staged(pipe, _request(env, layer, bbox),
                         1 if _is_expr(layer) else 3, offset, scale, clip,
                         0, False)
    assert made is not None
    kind, got = made
    assert kind == ("composite" if _is_expr(layer) else "rgba")
    return pipe.executor, got


def _mismatch(got, want):
    compare = reference_rgb.compare if got.ndim == 3 \
        else reference_expr.compare
    return compare(got, want)


def _check(got, want):
    rec = _mismatch(got, want)
    assert rec["mismatch"] <= BOUND and rec["max_byte_diff"] <= 1, rec


# --- the tiles, through render_staged and over HTTP ---------------------------

@pytest.mark.parametrize("window", ["whole_scene", "gather_window"])
@pytest.mark.parametrize("layer", list(LAYERS))
@pytest.mark.parametrize("case", list(CASES))
def test_render_staged_matches_reference(env, case, layer, window,
                                         monkeypatch):
    """As the CPU serves it (whole scenes) and as the chip does (each
    set's gather window read on its own zone's control grid): one
    dispatch, its leg `_mc` where the sets lie in two CRSs, keyed by
    (G, K, C) or (G, K, R, C)."""
    monkeypatch.setenv("GSKY_WARP_WINDOW",
                       "1" if window == "gather_window" else "0")
    bbox = _bbox(case)
    ex, got = _staged(env, layer, bbox)
    _, _, _, _, grids = LAYERS[layer]
    _, _, n_sets, n_crs = CASES[case]
    chans = 2 if _is_expr(layer) else 3
    name = "render_expr" if _is_expr(layer) else "render_rgba"
    (leg, n), = ex.bucket_stats.items()
    if n_crs > 1:
        shape = (n_sets, n_crs) + ((grids,) if grids > 1 else ()) \
            + (chans,)
        assert leg.startswith(f"{name}_mc:({shape}, "), leg
    else:
        assert leg.startswith(name + ("_mg:" if grids > 1 else ":")), leg
    assert n == 1 and leg.endswith("None)") == (window == "whole_scene")
    assert ex.band_crs == {
        "sets_one_crs": n_sets if n_crs == 1 else 0,
        "sets_multi_crs": n_sets if n_crs > 1 else 0,
        "multi_crs_declined": 0}
    want = _want(env["sources"], layer, bbox)
    _check(got, want)
    if _is_expr(layer):
        assert (want != 255).all()
    else:
        assert (want[..., 3] == 255).all()      # the other zone fills it


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


def _getmap(layer, bbox):
    return (f"/ows?service=WMS&request=GetMap&version=1.3.0&layers={layer}"
            f"&crs=EPSG:3857&bbox={bbox[0]!r},{bbox[1]!r},{bbox[2]!r},"
            f"{bbox[3]!r}&width=256&height=256&format=image/png"
            f"&time={TIME}")


@pytest.mark.parametrize("layer", list(LAYERS))
@pytest.mark.parametrize("case", ["seam", "seam_both_rows"])
def test_http_takes_the_fused_leg(env, case, layer):
    """A GetMap over the seam: the three-band route counts `rgba` and no
    `fallback`, the expression route `bucketed` and not `unfused`, and
    `/debug` `band_crs` counts the tile's sets as sets in several
    CRSs."""
    server = env["server"]

    def state():
        doc = json.loads(_get(server, "/debug")[1])
        return {"routes": server.metrics.summary()["rgb_routes"],
                "paths": paged.expr_fused_stats()["paths"],
                "crs": doc["band_crs"]}
    s0 = state()
    bbox = _bbox(case)
    status, body = _get(server, _getmap(layer, bbox))
    assert status == 200, body[:300]
    s1 = state()
    if _is_expr(layer):
        assert _moved(s0["paths"], s1["paths"]) == {"bucketed": 1}
        from PIL import Image
        got = np.asarray(Image.open(io.BytesIO(body)))
    else:
        assert _moved(s0["routes"], s1["routes"]) == {"rgba": 1}
        got = decode_png(body)
    assert _moved(s0["crs"], s1["crs"]) == {
        "sets_multi_crs": CASES[case][2]}
    _check(got, _want(env["sources"], layer, bbox))


# --- what the bound catches ------------------------------------------------------

@pytest.mark.parametrize("layer", ["truecolour", "ndvi"])
@pytest.mark.parametrize("case", ["seam", "wedge55"])
def test_a_set_on_the_other_zones_grid_fails_the_bound(env, case, layer,
                                                       monkeypatch):
    """The program with every set warped on the first zone's control
    grid (each set's param rows still on its own zone's origin): the
    other zone's sets land ~550 km off, and the tile is outside the
    bound by far."""
    real = ex_mod._set_crs

    def first_grid(scenes, sets):
        origins, crs_of = real(scenes, sets)
        return origins, crs_of and (0,) * len(crs_of)
    monkeypatch.setattr(ex_mod, "_set_crs", first_grid)
    bbox = _bbox(case)
    _, got = _staged(env, layer, bbox)
    assert _mismatch(got, _want(env["sources"], layer, bbox))[
        "mismatch"] > 10 * BOUND


def _bf16(a):
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _dropped(zone):
    def spoil(srcs):
        return [s for s in srcs if s.crs != zone]
    return spoil


def _in_bf16(srcs):
    return [dataclasses.replace(s, nodata=float(_bf16(s.nodata)),
                                read=lambda s=s: _bf16(s.read()))
            for s in srcs]


@pytest.mark.parametrize("spoil", ["drop_zone55", "drop_zone56", "in_bf16"])
@pytest.mark.parametrize("layer", ["truecolour", "ndvi"])
def test_the_bound_sees_a_dropped_zone_or_bf16(env, layer, spoil):
    """The served tile across the edge of one zone's wedge against the
    reference without the other zone's sets, which fill the wedge, and
    the reference with its rasters in bfloat16 (the precision below the
    float32 the scene cache keeps) against the true one: each outside
    the bound by far."""
    if spoil == "in_bf16":
        bbox = _bbox("seam")
        share = _mismatch(_want(_in_bf16(env["sources"]), layer, bbox),
                          _want(env["sources"], layer, bbox))["mismatch"]
    else:
        zone = "EPSG:32755" if spoil == "drop_zone55" else "EPSG:32756"
        bbox = _bbox("wedge56" if spoil == "drop_zone55" else "wedge55")
        _, got = _staged(env, layer, bbox)
        share = _mismatch(got, _want(_dropped(zone)(env["sources"]),
                                     layer, bbox))["mismatch"]
    assert share > 10 * BOUND, share


# --- ties across zones ---------------------------------------------------------------

def _tie_archive(root, order):
    """Two one-band-a-channel granules of one timestamp, one in each
    zone, over the same ground near 150 E, each of constant values (so
    the tile shows which one wins), crawled in ``order``."""
    from gsky_tpu.index.crawler import extract_geotiff
    from gsky_tpu.io import write_geotiff
    (root / "s2").mkdir()
    recs = {}
    for name, crs, x0, base in (("z55", "EPSG:32755", 774000.0, 1000),
                                ("z56", "EPSG:32756", 221000.0, 2000)):
        for c, ns in enumerate(LAYERS["truecolour"][0]):
            path = str(root / "s2" / f"{name}_20200110_{ns}.tif")
            write_geotiff(path, np.full((400, 400), base + 300 * c,
                                        np.int16),
                          GeoTransform(x0, 10.0, 0.0, 6213000.0, 0.0, -10.0),
                          parse_crs(crs), nodata=-999)
            recs.setdefault(name, []).append(
                extract_geotiff(path, namespace=ns))
    store = MASStore()
    for name in order:
        for rec in recs[name]:
            store.ingest(rec)
    return store


@pytest.mark.parametrize("order", [("z55", "z56"), ("z56", "z55")])
def test_ties_across_zones_follow_priority_order(tmp_path, order):
    """Granules of one timestamp either side of the seam: the later
    arrival wins where both hold data, as `ops.mosaic.priority_order`
    ranks them and `reference.mosaic` (a stable sort, the later
    overwrites) agrees."""
    store = _tie_archive(tmp_path, order)
    mas, _ = _server(store, tmp_path, ["truecolour"])
    bbox = _bbox("seam")
    env = {"mas": mas, "root": str(tmp_path / "s2")}
    pipe = TilePipeline(mas, executor=WarpExecutor())
    granules = pipe.index(_request(env, "truecolour", bbox))
    assert len({g.srs for g in granules}) == 2
    _, got = _staged(env, "truecolour", bbox, pipe.executor)
    assert pipe.executor.band_crs["sets_multi_crs"] == 2
    winner = 1000 if order[-1] == "z55" else 2000
    _, _, scale, _, _ = LAYERS["truecolour"]
    want = [int(np.floor(np.float32(winner + 300 * c) * np.float32(scale)))
            for c in range(3)]
    assert (got[..., :3] == np.array(want, np.uint8)).all(), \
        np.unique(got.reshape(-1, 4), axis=0)


# --- a one-CRS tile is the program it was ------------------------------------------

def _parent_mosaic_band_sets(granules, ctrl, params, prios, method,
                             out_hw, step, win, win0, grid_of=None,
                             crs_of=None):
    """`ops.warp._mosaic_band_sets` as it was before sets could lie in
    several CRSs, line for line (``crs_of`` is the later keyword, None
    here)."""
    assert crs_of is None
    h, w = out_hw
    C = len(granules[0])
    grid_of = grid_of or (0,) * C
    grids = [[c for c in range(C) if grid_of[c] == r]
             for r in range(max(grid_of) + 1)]
    one = len(grids) == 1
    sx = warp._bilerp_grid(ctrl[0], h, w, step)
    sy = warp._bilerp_grid(ctrl[1], h, w, step)
    wins = [win if one or win is None else win[r]
            for r in range(len(grids))]
    unfold = warp._unfolds(method, [
        (tuple(wins[r] or bands[cs[0]].shape) + (len(cs),),
         jnp.result_type(*[bands[c] for c in cs]))
        for bands in granules for r, cs in enumerate(grids)])
    data = [jnp.zeros((h, w, len(cs)), jnp.float32) for cs in grids]
    best = [jnp.full((h, w, len(cs)), -jnp.inf, jnp.float32)
            for cs in grids]
    for k, bands in enumerate(granules):
        for r, cs in enumerate(grids):
            d, o = warp._grid_taps([bands[c] for c in cs], params, win0,
                                   k if one else (k, r), sx, sy, method,
                                   wins[r], unfold)
            score = jnp.where(o, prios[k] if one
                              else prios[k, np.asarray(cs)], -jnp.inf)
            take = score > best[r]
            data[r] = jnp.where(take, d, data[r])
            best[r] = jnp.where(take, score, best[r])
    if one:
        return data[0], best[0]
    place = {c: (r, j) for r, cs in enumerate(grids)
             for j, c in enumerate(cs)}

    def in_order(planes):
        return jnp.stack([planes[place[c][0]][..., place[c][1]]
                          for c in range(C)], axis=-1)
    return in_order(data), in_order(best)


def _operands(G, C, grids, windowed, K=1, seed=9):
    """G sets of C scenes (a channel on the 2x coarser grid where
    ``grids`` is 2), NaN holes and a nodata block, and K control grids
    of a 64-px tile over them: (operands, kernel keywords)."""
    rng = np.random.default_rng(seed)
    grid_of = None if grids == 1 else (1,) + (0,) * (C - 1)
    bands = []
    for k in range(G):
        got = []
        for c in range(C):
            n = 48 if grid_of and grid_of[c] else 96
            a = rng.uniform(500, 4000, (n, n)).astype(np.float32)
            a[10 + k:20, 30 // (96 // n) + c:40] = np.nan
            a[30:40, :4 + k] = -999.0
            got.append(jnp.asarray(a))
        bands.append(tuple(got))
    row = [[-3.0 * k, 1.0, 0.0, 2.0 * k, 0.0, 1.0, 90, 92, -999.0, 0.0, 0.0]
           for k in range(G)]
    params = np.array(row, np.float32)
    win, win0 = ((64, 64), np.array([[4, 2 + k] for k in range(G)],
                                    np.int32)) if windowed else (None, None)
    if grid_of:
        half = params.copy()
        half[:, :6] /= 2.0
        half[:, 6:8] /= 2.0
        params = np.stack([params, half], axis=1)
        if windowed:
            win, win0 = ((64, 64), (32, 32)), np.stack([win0, win0 // 2], 1)
    gh = gw = (64 - 1 + 15) // 16 + 1
    ctrl = np.stack([np.stack(np.meshgrid(
        np.linspace(3.0 + j, 70.0 - j, gw), np.linspace(5.0 + j, 66.0, gh)))
        for j in range(K)]).astype(np.float32)
    prios = rng.permutation(G * C).reshape(G, C).astype(np.float32) + 1.0
    kw = dict(win=win, win0=None if win0 is None else jnp.asarray(win0),
              **({"grid_of": grid_of} if grid_of else {}))
    if K > 1:
        kw["crs_of"] = jnp.asarray(np.arange(G, dtype=np.int32) % K)
    return (tuple(bands), jnp.asarray(ctrl[0] if K == 1 else ctrl),
            jnp.asarray(params), jnp.asarray(prios)), kw


KERNELS = {"truecolour": (3, None), "ndvi": (2, "(a - b) / (a + b)")}


def _program(kernel, operands, kw):
    C, text = KERNELS[kernel]
    if text is None:
        fn = warp.render_rgba_ctrl.__wrapped__
        statics = dict(method="bilinear", out_hw=(64, 64), step=16,
                       auto=False, colour_scale=0)
        args = operands + (jnp.asarray(np.array(
            [0.0, 254.0 / 3000.0, 3000.0], np.float32)),)
    else:
        fp = fingerprint(compile_expr(text))
        fn = warp.render_expr_ctrl.__wrapped__
        statics = dict(fp=fp.key, method="bilinear", out_hw=(64, 64),
                       step=16, auto=False, colour_scale=0)
        args = operands + (jnp.asarray(np.array([1.0, 127.0, 2.0],
                                                np.float32)),
                           jnp.asarray(fp.const_array()))
    return lambda: (str(jax.make_jaxpr(
        lambda *a: fn(*a, **statics, **kw))(*args)),
        np.asarray(jax.jit(lambda *a: fn(*a, **statics, **kw))(*args)))


@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("grids", [1, 2])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_one_crs_is_the_parents_program(kernel, G, grids, windowed,
                                        monkeypatch):
    """The jaxpr and the bytes of a one-CRS call, on one pixel grid and
    on two, are those of the parent's `_mosaic_band_sets`: the three
    Sentinel-2 cells run the programs they ran."""
    operands, kw = _operands(G, KERNELS[kernel][0], grids, windowed)
    run = _program(kernel, operands, kw)
    now_jaxpr, now = run()
    monkeypatch.setattr(warp, "_mosaic_band_sets", _parent_mosaic_band_sets)
    then_jaxpr, then = run()
    assert now_jaxpr == then_jaxpr
    np.testing.assert_array_equal(now, then)
    assert (now != 255).any()


@pytest.mark.parametrize("grids", [1, 2])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_each_set_reads_its_own_grid(kernel, grids):
    """Two control grids, the sets alternating between them: the tile
    is what one-CRS calls over the sets of each grid give, mosaicked by
    priority, and no gather reads a control grid (it is picked by a
    dynamic slice)."""
    C = KERNELS[kernel][0]
    (bands, ctrl, params, prios), kw = _operands(4, C, grids, False, K=2)
    jaxpr, got = _program(kernel, (bands, ctrl, params, prios), kw)()
    one_kw = {k: v for k, v in kw.items() if k != "crs_of"}
    one = _program(kernel, (bands, ctrl[0], params, prios), one_kw)()[0]
    assert jaxpr.count(" gather[") == one.count(" gather[")
    # the same sets, each grid's sets alone with the other's priorities
    # at -inf, then the per-channel winner of the two: for a true-colour
    # tile the bytes, for an expression the mosaic before it
    parts = []
    for j in range(2):
        keep = (np.arange(4) % 2 == j)[:, None]
        p = jnp.where(keep, prios, -jnp.inf)
        parts.append(warp._mosaic_band_sets(
            bands, ctrl[j], params, p, "bilinear", (64, 64), 16,
            kw["win"], kw["win0"], kw.get("grid_of")))
    mixed = warp._mosaic_band_sets(
        bands, ctrl, params, prios, "bilinear", (64, 64), 16, kw["win"],
        kw["win0"], kw.get("grid_of"), kw["crs_of"])
    take = parts[1][1] > parts[0][1]
    np.testing.assert_array_equal(
        np.asarray(mixed[0]),
        np.asarray(jnp.where(take, parts[1][0], parts[0][0])))
    np.testing.assert_array_equal(
        np.asarray(mixed[1]), np.asarray(jnp.maximum(parts[0][1],
                                                     parts[1][1])))
    assert got.shape[:2] == (64, 64)


@pytest.mark.parametrize("kernel", list(KERNELS))
def test_the_map_is_an_operand_not_a_program(kernel):
    """Which set lies in which CRS is traced: two assignments of one (G,
    K) share one jaxpr."""
    C = KERNELS[kernel][0]
    operands, kw = _operands(4, C, 1, True, K=2)
    first = _program(kernel, operands, kw)()[0]
    kw["crs_of"] = jnp.asarray(np.array([1, 1, 0, 1], np.int32))
    assert _program(kernel, operands, kw)()[0] == first


# --- the grouping rules ------------------------------------------------------------

def _scene(x0, y0, res, n, crs):
    bucket = max(256, -(-n // 256) * 256)
    return SimpleNamespace(
        gt=GeoTransform(x0, res, 0.0, y0, 0.0, -res), crs=parse_crs(crs),
        height=n, width=n, nodata=-999.0, bucket=(bucket, bucket),
        dtype=np.dtype(np.float32))


def _rgb(x0, y0, crs):
    return [_scene(x0, y0, 10.0, 10980, crs) for _ in range(3)]


def test_sets_in_two_crss_form_with_a_grid_each():
    scenes = _rgb(699960.0, 6300040.0, "EPSG:32755") \
        + _rgb(199980.0, 6300040.0, "EPSG:32756") \
        + _rgb(699960.0, 6200020.0, "EPSG:32755")
    made = _grid_sets(scenes, [0, 1, 2] * 3, 3)
    assert made == ([[0, 1, 2], [3, 4, 5], [6, 7, 8]], None)
    origins, crs_of = _set_crs(scenes, made[0])
    assert [(o[0].epsg, o[1], o[2]) for o in origins] == [
        (32755, 699960.0, 6300040.0), (32756, 199980.0, 6300040.0)]
    assert crs_of == (0, 1, 0)
    # one CRS: no map, the kernels' one-CRS program
    assert _set_crs(scenes[:3] + scenes[6:], [[0, 1, 2], [3, 4, 5]])[1] \
        is None


@pytest.mark.parametrize("n_crs,declined", [(3, False), (4, True)])
def test_more_crss_than_grids_decline(n_crs, declined):
    scenes = [s for z in range(n_crs)
              for s in _rgb(100000.0 * z, 6300040.0, f"EPSG:3275{4 + z}")]
    made = _grid_sets(scenes, [0, 1, 2] * n_crs, 3)
    assert (made is None) == declined
    ex = WarpExecutor()
    ex._note_grids(made, scenes)
    assert ex.band_crs == {
        "sets_one_crs": 0, "sets_multi_crs": 0 if declined else n_crs,
        "multi_crs_declined": int(declined)}


def test_the_counters_count_sets_by_their_crss():
    ex = WarpExecutor()
    one = _rgb(699960.0, 6300040.0, "EPSG:32755")
    ex._note_grids(_grid_sets(one, [0, 1, 2], 3), one)
    two = one + _rgb(199980.0, 6300040.0, "EPSG:32756")
    ex._note_grids(_grid_sets(two, [0, 1, 2] * 2, 3), two)
    # a one-CRS list that forms no set is no multi-CRS decline; a
    # two-CRS list that forms none is
    ex._note_grids(None, one[:2])
    ex._note_grids(None, two[:5])
    assert ex.band_crs == {"sets_one_crs": 1, "sets_multi_crs": 2,
                           "multi_crs_declined": 1}
    assert ex.band_grids["sets_one_grid"] == 3


def test_control_grids_share_the_finest_step(monkeypatch):
    """Where one CRS's grid must refine, every CRS's grid of the tile is
    taken at that step (the kernel has one), from the same cache."""
    ex = WarpExecutor()
    real = WarpExecutor._ctrl_err_px
    z56 = parse_crs("EPSG:32756")

    def err(sx, sy, dst_gt, dst_crs, src_crs, step):
        return 1.0 if src_crs == z56 and step > 8 else real(
            sx, sy, dst_gt, dst_crs, src_crs, step)
    monkeypatch.setattr(WarpExecutor, "_ctrl_err_px", staticmethod(err))
    b = _bbox("seam")
    dst = GeoTransform(b[0], (b[2] - b[0]) / 256, 0.0, b[3], 0.0,
                       -(b[3] - b[1]) / 256)
    origins = [(parse_crs("EPSG:32755"), 772000.0, 6214000.0),
               (z56, 221000.0, 6214000.0)]
    cx, cy, dev, step = ex._ctrl_sets(dst, EPSG3857, 256, 256, origins)
    assert step == 8 and cx.shape == (2, 33, 33) and dev.shape == (2, 2, 33,
                                                                   33)
    sx, sy, _ = ex._ctrl_geo_coords(dst, EPSG3857, 256, 256, z56, 8)
    np.testing.assert_allclose(cx[1], sx - 221000.0)
    np.testing.assert_allclose(np.asarray(dev[1, 1]),
                               (sy - 6214000.0).astype(np.float32))
