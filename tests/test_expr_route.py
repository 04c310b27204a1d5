"""The band-expression route a TPU takes (`ops.warp.render_expr_ctrl`
through `executor.render_expr_byte`'s bucketed leg): the kernel held to
the plain reference (`benchmarks/reference_expr.py`) on seeded rasters,
for NDVI, EVI, a constant-bearing and a ternary expression over one, two
and four granule sets with nodata wedges and zero denominators; what the
benchmark's bound has to catch (bfloat16, a swapped variable, a dropped
granule, an expression evaluated before the mosaic); the fused leg
against the unfused `evaluate_expressions` leg, byte for byte; and a
GetMap on an expression layer: one index query, one dispatch, no stacked
copy of a raster, a row in `tile_stages`, `expr.paths.bucketed`."""

import asyncio
import datetime as dt
import json

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import reference, reference_expr
from benchmarks.archives import sentinel2_granules as s2
from gsky_tpu import obs
from gsky_tpu.geo.crs import EPSG3857
from gsky_tpu.geo.transform import BBox, GeoTransform
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.io.png import decode_png
from gsky_tpu.ops import paged
from gsky_tpu.ops.expr import compile_expr, fingerprint
from gsky_tpu.ops.scale import scale_to_byte
from gsky_tpu.ops.warp import render_expr_ctrl
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
from gsky_tpu.pipeline.executor import WarpExecutor, _inv_gt_params
from gsky_tpu.pipeline.scene_cache import default_scene_cache
from gsky_tpu.pipeline.tile_stages import render_staged
from gsky_tpu.server.config import ConfigWatcher
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

EXPRS = {
    "ndvi": "(nir - red) / (nir + red)",
    "evi": "2.5 * (nir - red) / (nir + 6 * red - 7.5 * blue + 10000)",
    "constants": "(nir - red + 0.5) / (nir + red) * 0.5 + 0.25",
    "ternary": "nir > red ? (nir - red) / (nir + red) : 0.1",
}
SCALE, CLIP = 254.0, 1.0
# the benchmark's bound (traffic/ndvi-pan-cold.json): 0.5 % of a tile's
# bytes, and no byte further than one level
BOUND = 0.005

# --- (a) the kernel against the plain reference ------------------------------

H = W = 80              # a set's rasters
PITCH = 60              # corner to corner: neighbours overlap by 20 px
RES = 10.0
NODATA = -999.0
OUT = 64                # the tile
# sets touched -> the tile's centre in source pixels of the 140-px layout
CENTRES = {1: (50.0, 30.0), 2: (76.0, 30.0), 4: (76.0, 76.0)}
BANDS = ("nir", "red", "blue")


def _rasters(seed=35):
    """{(set, band): (H, W) float32}: four sets in a 2 x 2 layout, each
    band a smooth field of its own (sets differ, so a wrong winner
    shows) with a nodata wedge per BAND (so the mosaic's winner can
    differ between the variables of one pixel) and a block where nir
    and red are both 0: NDVI's 0 / 0, the constant-bearing
    expression's 0.5 / 0, and the ternary's unselected branch.  (A
    denominator that only cancels, red - 500 or EVI's, is not zero
    after a bilinear tap in either precision, so none is made.)"""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float64)
    out = {}
    for k in range(4):
        for b, base in zip(BANDS, (3200.0, 1300.0, 900.0)):
            ph = rng.uniform(0, 2 * np.pi, 2)
            d = base + 700 * np.cos(yy / 9 + ph[0]) * np.sin(xx / 7 + ph[1]) \
                + rng.integers(-2, 3, (H, W))
            d[20:26, 34:40] = 0.0 if b != "blue" else 900.0
            # the wedge: nir lacks columns near its east edge, red rows
            # near its north edge (which a newer southern neighbour
            # lays over an older set's valid red), blue a corner
            if b == "nir":
                d[:, W - 12 + k:] = NODATA
            elif b == "red":
                d[:10 + k, :] = NODATA
            else:
                d[:14, :14] = NODATA
            out[k, b] = d.astype(np.float32)
    return out


def _corner(k):
    return ((k % 2) * PITCH * RES, -(k // 2) * PITCH * RES)


def _sources(rasters, sets, bands, spoil=lambda k, b, d: d):
    """{band: [reference.Source]} in EPSG:3857 (the tile's own CRS, so
    the reference's projection is the identity), set k newer than set
    k - 1."""
    return {b: [reference.Source(
        namespace=b, timestamp=float(k), crs="EPSG:3857",
        x0=_corner(k)[0], y0=_corner(k)[1], dx=RES, dy=-RES, shape=(H, W),
        nodata=NODATA, read=lambda k=k, b=b: spoil(k, b, rasters[k, b]))
        for k in sets] for b in bands}


def _bbox(n_sets, px=0.7, centre=None):
    cx, cy = centre or CENTRES[n_sets]
    half = OUT * px / 2 * RES
    return (cx * RES - half, -cy * RES - half,
            cx * RES + half, -cy * RES + half)


def _kernel(rasters, text, sets, bbox, method="bilinear"):
    """uint8 (OUT, OUT) from one `render_expr_ctrl` dispatch, its
    operands built as `executor._band_sets` builds them."""
    fp = fingerprint(compile_expr(text))
    step = 16
    n = (OUT - 1 + step - 1) // step + 1
    px = (bbox[2] - bbox[0]) / OUT
    # source-CRS coordinates of the centres of every 16th tile pixel,
    # relative to the first set's corner
    ox, oy = _corner(sets[0])
    cx = bbox[0] + (np.arange(n) * step + 0.5) * px - ox
    cy = bbox[3] - (np.arange(n) * step + 0.5) * px - oy
    ctrl = np.stack(np.meshgrid(cx, cy)).astype(np.float32)
    G = 1 << (len(sets) - 1).bit_length()
    params = np.zeros((G, 11), np.float32)
    params[:, 10] = -1.0
    prios = np.full((G, fp.n_slots), -np.inf, np.float32)
    bands = []
    for i, k in enumerate(sets):
        x0, y0 = _corner(k)
        params[i, :6] = _inv_gt_params(
            GeoTransform(x0, RES, 0.0, y0, 0.0, -RES), ox, oy)
        params[i, 6:11] = (H, W, NODATA, 0.0, 0.0)
        prios[i] = k + 1.0
        bands.append(tuple(jnp.asarray(rasters[k, b]) for b in fp.slots))
    bands += [bands[0]] * (G - len(sets))
    return np.asarray(render_expr_ctrl(
        tuple(bands), jnp.asarray(ctrl), jnp.asarray(params),
        jnp.asarray(prios),
        jnp.asarray(np.array([0.0, SCALE, CLIP], np.float32)),
        jnp.asarray(fp.const_array()), fp.key, method, (OUT, OUT), step,
        False, 0))


def _reference(rasters, text, sets, bbox, **kw):
    node = reference_expr.parse(text)
    return reference_expr.render_byte(
        text, _sources(rasters, sets, reference_expr.variables(node), **kw),
        bbox, "EPSG:3857", OUT, OUT, "bilinear", 0.0, SCALE, CLIP)


@pytest.fixture(scope="module")
def rasters():
    return _rasters()


@pytest.mark.parametrize("n_sets", [1, 2, 4])
@pytest.mark.parametrize("name", list(EXPRS))
def test_render_expr_ctrl_matches_reference(rasters, name, n_sets):
    sets = list(range(4))[:n_sets] if n_sets < 4 else [0, 1, 2, 3]
    bbox = _bbox(n_sets)
    got = _kernel(rasters, EXPRS[name], sets, bbox)
    want = _reference(rasters, EXPRS[name], sets, bbox)
    rec = reference_expr.compare(got, want)
    assert rec["mismatch"] <= BOUND and rec["max_byte_diff"] <= 1, rec
    # a tile of data; over one set with holes in it (its wedges, and
    # where the expression's denominator is zero), over several the
    # neighbours fill a set's wedges channel by channel
    assert 0.3 < np.mean(want != 255) <= 1.0
    assert n_sets > 1 or np.mean(want != 255) < 1.0
    assert len(np.unique(want)) > 20


def test_a_zero_denominator_is_no_data(rasters):
    """0 / 0 (NDVI over the block where both bands are 0) and 0.5 / 0
    (the constant-bearing expression there) are no data in kernel and
    reference alike; EVI's denominator is 10,000 there; the ternary's
    unselected branch may divide by zero and the pixel stays valid."""
    bbox = _bbox(1, px=0.25, centre=(37.0, 23.0))       # round the block
    sets = [0]
    for name, text in EXPRS.items():
        got = _kernel(rasters, text, sets, bbox)
        want = _reference(rasters, text, sets, bbox)
        holes = float(np.mean(want == 255))
        if name in ("ternary", "evi"):
            assert holes == 0.0
            assert name == "evi" or \
                (want == int(np.floor(np.float32(0.1) * SCALE))).any()
        else:
            assert 0.05 < holes < 0.3, (name, holes)
        assert np.mean((got == 255) != (want == 255)) <= BOUND, name


def _bf16(a):
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _before_the_mosaic(rasters, text, sets, bbox):
    """The fault of evaluating per granule set and mosaicking the
    results: a set whose red lacks data hides a neighbour's nir."""
    out = np.full((OUT, OUT), 255, np.uint8)
    for k in sets:                      # oldest first: the newer wins
        one = _reference(rasters, text, [k], bbox)
        out = np.where(one != 255, one, out)
    return out


FAULTS = {
    "bfloat16_rasters": lambda r, t, s, b: _reference(
        r, t, s, b, spoil=lambda k, band, d: np.where(
            d == NODATA, d, _bf16(d))),
    "swapped_variable": lambda r, t, s, b: _reference(
        r, t.replace("nir", "@").replace("red", "nir").replace("@", "red"),
        s, b),
    "dropped_granule": lambda r, t, s, b: _reference(r, t, s[:-1], b),
    "before_the_mosaic": _before_the_mosaic,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_bound_catches(rasters, fault):
    """Each fault, computed by the reference, is outside the bound the
    kernel is inside of."""
    sets, bbox = [0, 1, 2, 3], _bbox(4)
    for name in ("ndvi", "evi"):
        got = _kernel(rasters, EXPRS[name], sets, bbox)
        bad = FAULTS[fault](rasters, EXPRS[name], sets, bbox)
        assert reference_expr.compare(got, bad)["mismatch"] > 4 * BOUND, name


def test_one_program_a_structure(rasters):
    """The jit key holds the fingerprint, not the text: another
    constant and other variable names run the program of the first."""
    bbox = _bbox(1)
    _kernel(rasters, "(nir - red) / (red - 500) * 0.125 + 0.25", [0], bbox)
    n = render_expr_ctrl._cache_size()
    _kernel(rasters, "(red - blue) / (blue - 800) * 0.5 + 0.125", [0], bbox)
    assert render_expr_ctrl._cache_size() == n >= 1


# --- (b) the fused leg against the unfused one, on an archive ----------------

SEED = 35
X0, Y0 = 399960.0, 6200020.0
ARCHIVE = {
    "kind": "sentinel2_granules", "collection": "s2",
    "file_prefix": "S2A_T55H", "crs": "EPSG:32755", "origin": [X0, Y0],
    "res": 10.0, "granule_hw": [320, 320], "pitch_m": 2900.0,
    "grid": [2, 2], "date": "2020-01-10",
    "bands": [{"name": "nir", "namespace": "nbart_nir_1", "base": 3200},
              {"name": "red", "namespace": "nbart_red", "base": 1300},
              {"name": "blue", "namespace": "nbart_blue", "base": 900}],
    "nodata": -999, "wedge_px": 24, "compress": False}
TIME = s2.dates(ARCHIVE)[0]
STAMP = dt.datetime.fromisoformat(ARCHIVE["date"]).replace(
    tzinfo=dt.timezone.utc).timestamp()
LAYERS = {
    "ndvi": "ndvi = (nbart_nir_1 - nbart_red) / (nbart_nir_1 + nbart_red)",
    "evi": "evi = 2.5 * (nbart_nir_1 - nbart_red) / (nbart_nir_1 "
           "+ 6 * nbart_red - 7.5 * nbart_blue + 10000)",
}
PALETTE = {"interpolate": True, "colours": [
    {"R": 140, "G": 81, "B": 10, "A": 255},
    {"R": 254, "G": 224, "B": 100, "A": 255},
    {"R": 166, "G": 217, "B": 106, "A": 255},
    {"R": 26, "G": 150, "B": 65, "A": 255},
    {"R": 0, "G": 68, "B": 27, "A": 255}]}
# name -> (centre in UTM metres from the archive's corner, half-size in
# metres, granule sets the tile touches)
CASES = {
    "interior": ((1000.0, -1000.0), 320.0, 1),
    "overlap_strip": ((3050.0, -900.0), 320.0, 2),
    "four_corner": ((3050.0, -3050.0), 320.0, 4),
    "nodata_wedge": ((3080.0, -2600.0), 150.0, 2),
}


def _case_bbox(case):
    (cx, cy), half, _ = CASES[case]
    xs = np.array([X0 + cx - half, X0 + cx + half])
    ys = np.array([Y0 + cy - half, Y0 + cy + half])
    mx, my = reference.project(xs, ys, ARCHIVE["crs"], "EPSG:3857")
    return (float(mx[0]), float(my[0]), float(mx[1]), float(my[1]))


def _want(sources, layer, bbox):
    text = reference_expr.split_product(LAYERS[layer])[1]
    names = reference_expr.variables(reference_expr.parse(text))
    return reference_expr.render_byte(
        text, reference_expr.select_vars(sources, names, STAMP), bbox,
        "EPSG:3857", 256, 256, "bilinear", 0.0, SCALE, CLIP)


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("s2expr")
    store = MASStore()
    for rec in s2.build(ARCHIVE, SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    conf = root / "conf"
    conf.mkdir()
    (conf / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [{
            "name": name, "rgb_products": [product],
            "data_source": str(root / "s2"), "resample": "bilinear",
            "time_generator": "mas", "offset_value": 0.0,
            "clip_value": CLIP, "scale_value": SCALE, "palette": PALETTE}
            for name, product in LAYERS.items()]}))
    mas = MASClient(store)
    watcher = ConfigWatcher(str(conf), mas_factory=lambda addr: mas,
                            install_signal=False)
    server = OWSServer(watcher, mas_factory=lambda addr: mas,
                       metrics=MetricsLogger(), gateway=None)
    return {"server": server, "mas": mas, "root": str(root / "s2"),
            "sources": s2.sources(ARCHIVE, SEED)}


def _request(env, layer, bbox):
    return GeoTileRequest(
        collection=env["root"], bands=[LAYERS[layer]], bbox=BBox(*bbox),
        crs=EPSG3857, width=256, height=256, start_time=STAMP,
        end_time=None, resample="bilinear")


@pytest.mark.parametrize("window", ["whole_scene", "gather_window"])
@pytest.mark.parametrize("layer", list(LAYERS))
@pytest.mark.parametrize("case", list(CASES))
def test_fused_leg_is_the_unfused_leg_byte_for_byte(env, case, layer,
                                                    window, monkeypatch):
    """`render_staged` -> `render_expr_ctrl` (whole scenes as a CPU
    serves, gather windows as the chip does) against the modular route
    (`warp_mosaic_scenes` + `evaluate_expressions` + `scale_to_byte`),
    and both within the bound of the reference."""
    monkeypatch.setenv("GSKY_WARP_WINDOW",
                       "1" if window == "gather_window" else "0")
    bbox = _case_bbox(case)
    req = _request(env, layer, bbox)
    pipe = TilePipeline(env["mas"], executor=WarpExecutor())
    kind, got = render_staged(pipe, req, 1, 0.0, SCALE, CLIP, 0, False)
    assert kind == "composite" and got.shape == (256, 256)
    (leg,) = pipe.executor.bucket_stats
    slots = 2 if layer == "ndvi" else 3
    assert leg.startswith(f"render_expr:(({CASES[case][2]}, 512, 512, "
                          f"{slots}), ")
    assert leg.endswith("None)") == (window == "whole_scene"), leg
    res = TilePipeline(env["mas"], executor=WarpExecutor()).process(req)
    unfused = np.asarray(scale_to_byte(
        jnp.asarray(res.data[layer]), jnp.asarray(res.valid[layer]),
        offset=0.0, scale=SCALE, clip=CLIP, colour_scale=0, auto=False))
    np.testing.assert_array_equal(got, unfused)
    rec = reference_expr.compare(got, _want(env["sources"], layer, bbox))
    assert rec["mismatch"] <= BOUND and rec["max_byte_diff"] <= 1, rec
    assert np.mean(got != 255) == 1.0       # the neighbour fills the wedge


def test_a_grid_that_lacks_a_band_declines(env, monkeypatch):
    """Sets are complete or the fused leg declines: with one granule's
    red raster gone from the index's answer the executor returns None
    and counts nothing, and the caller takes the unfused leg."""
    pipe = TilePipeline(env["mas"], executor=WarpExecutor())
    req = _request(env, "ndvi", _case_bbox("overlap_strip"))
    made = pipe.composite_prep(req)
    granules, ns_ids, prio, n_slots, fp = made
    drop = next(i for i, g in enumerate(granules)
                if g.namespace == "nbart_red")
    keep = [i for i in range(len(granules)) if i != drop]
    out = pipe.executor.render_expr_byte(
        [granules[i] for i in keep], [ns_ids[i] for i in keep],
        [prio[i] for i in keep], req.dst_gt(), req.crs, 256, 256, n_slots,
        fp, "bilinear", 0.0, SCALE, CLIP, 0, False)
    assert out is None and not pipe.executor.bucket_stats


# --- (c) a GetMap on an expression layer --------------------------------------

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


def _getmap(env, layer, bbox, monkeypatch):
    """(body, what the request moved: index queries, dispatch legs,
    stacks in the scene cache, tiles in `tile_stages`, `expr.paths`)."""
    from gsky_tpu.pipeline.executor import default_executor
    server = env["server"]
    import threading
    queries = []
    real = env["mas"].intersects

    def spy(*a, **kw):
        # the prefetch planner asks the index about tiles it predicts,
        # on a thread of its own: not this request's queries
        if threading.current_thread().name != "gsky-prefetch":
            queries.append(a)
        return real(*a, **kw)
    monkeypatch.setattr(env["mas"], "intersects", spy)

    def state():
        return {"legs": dict(default_executor.bucket_stats),
                "stacks": default_scene_cache.stats()["stacks"],
                "tiles": server.metrics.summary().get(
                    "tile_stages", {}).get("tiles", 0),
                "paths": paged.expr_fused_stats()["paths"]}
    s0 = state()
    status, body = _get(server, (
        f"/ows?service=WMS&request=GetMap&version=1.3.0&layers={layer}"
        f"&crs=EPSG:3857&bbox={bbox[0]!r},{bbox[1]!r},{bbox[2]!r},"
        f"{bbox[3]!r}&width=256&height=256&format=image/png&time={TIME}"))
    assert status == 200, body[:300]
    s1 = state()

    def moved(key):
        return {k: v - s0[key].get(k, 0) for k, v in s1[key].items()
                if v != s0[key].get(k, 0)}
    return body, {"queries": len(queries), "legs": moved("legs"),
                  "stacks": s1["stacks"] - s0["stacks"],
                  "tiles": s1["tiles"] - s0["tiles"],
                  "paths": moved("paths")}


def _indices(body):
    from PIL import Image
    import io
    img = Image.open(io.BytesIO(body))
    assert img.mode == "P"
    return img, np.asarray(img)


@pytest.mark.parametrize("layer", list(LAYERS))
@pytest.mark.parametrize("case", list(CASES))
def test_getmap_is_one_index_query_and_one_dispatch(env, case, layer,
                                                    monkeypatch):
    default_scene_cache.clear()
    bbox = _case_bbox(case)
    body, moved = _getmap(env, layer, bbox, monkeypatch)
    assert moved["queries"] == 1
    (leg, n), = moved["legs"].items()
    assert leg.startswith("render_expr:((") and n == 1
    assert moved["stacks"] == 0 and moved["tiles"] == 1
    assert moved["paths"] == {"bucketed": 1}
    img, got = _indices(body)
    rec = reference_expr.compare(got, _want(env["sources"], layer, bbox))
    assert rec["mismatch"] <= BOUND and rec["max_byte_diff"] <= 1, rec
    # the colour table is the reference's ramp, entry 255 transparent
    table = np.array(img.getpalette("RGB")).reshape(-1, 3)
    ramp = reference_expr.palette(PALETTE["colours"])
    assert (table[:255] == ramp[:255, :3]).all()
    assert decode_png(body)[..., 3].min() == 255    # a tile full of data


def test_expr_fuse_off_takes_the_old_leg(env, monkeypatch):
    """`GSKY_EXPR_FUSE=0`: the staged path declines, the modular route
    indexes again and mosaics, evaluates and scales in separate
    dispatches over a stacked copy; the answer is the same tile."""
    default_scene_cache.clear()
    bbox = _case_bbox("overlap_strip")
    fused, _ = _getmap(env, "ndvi", bbox, monkeypatch)
    default_scene_cache.clear()
    monkeypatch.setenv("GSKY_EXPR_FUSE", "0")
    body, moved = _getmap(env, "ndvi", bbox, monkeypatch)
    assert moved["paths"] == {"unfused": 1}
    assert [k.split(":")[0] for k in moved["legs"]] == ["scene_mosaic"]
    assert moved["stacks"] == 1 and moved["tiles"] == 0
    np.testing.assert_array_equal(_indices(body)[1], _indices(fused)[1])


def test_spans_name_the_expression_and_the_leg(env):
    """`tile.plan` carries the fingerprint's hash and slot count,
    `tile.dispatch` the leg that served."""
    pipe = TilePipeline(env["mas"], executor=WarpExecutor())
    req = _request(env, "evi", _case_bbox("interior"))
    with obs.start_trace("test") as trace:
        assert render_staged(pipe, req, 1, 0.0, SCALE, CLIP, 0, False)
    spans = {s["name"]: s for s in trace.span_dicts()}
    fp = fingerprint(compile_expr(
        reference_expr.split_product(LAYERS["evi"])[1]))
    assert spans["tile.plan"]["attrs"]["expr"] == fp.hash
    assert spans["tile.plan"]["attrs"]["slots"] == 3
    assert spans["tile.dispatch"]["attrs"]["leg"] == "render_expr"


def test_newer_set_wins_per_channel(rasters):
    """Newest-wins is per channel: where the newest set lacks red but
    holds nir, the pixel is its nir over the older set's red."""
    bbox = _bbox(2)
    spoiled = dict(rasters)
    red1 = rasters[1, "red"].copy()
    red1[:, :30] = NODATA               # all of the overlap, in the newer set
    spoiled[1, "red"] = red1
    got = _kernel(spoiled, EXPRS["ndvi"], [0, 1], bbox)
    want = _reference(spoiled, EXPRS["ndvi"], [0, 1], bbox)
    rec = reference_expr.compare(got, want)
    assert rec["mismatch"] <= BOUND, rec
    mixed = _before_the_mosaic(spoiled, EXPRS["ndvi"], [0, 1], bbox)
    assert reference_expr.compare(got, mixed)["mismatch"] > BOUND
