"""The WCS export route (`serve_wcs` -> `_getcoverage` -> the staged
export engine, or the per-tile leg for a single tile -> `write_geotiff`)
held to the plain reference (`benchmarks/reference_export.py`) by the
benchmark cell's own rule (`benchmarks/generators/wcs_exports.py::held`
with `traffic/coverage-2k-cubic.json`'s tolerance and bound): a
UTM -> EPSG:4326 cubic export over a scene's edge, over its nodata
corner, at 0.7 and 1.4 source pixels a pixel, across tile seams; what
that rule refuses (rasters held in bfloat16, bilinear or nearest in
place of cubic, a tile displaced by one pixel, a dropped tile, a wrong
tie point); and what an engine export leaves in `/debug`
`export_pipeline` and in its trace."""

import asyncio
import json
import os

import numpy as np
import pytest

from benchmarks import reference_export, spec
from benchmarks.archives import geotiff_scenes
from benchmarks.generators import wcs_exports
from gsky_tpu import obs
from gsky_tpu.geo.crs import parse_crs
from gsky_tpu.geo.transform import BBox, GeoTransform
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.io import write_geotiff
from gsky_tpu.server.config import ConfigWatcher
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

SEED = 37
ARCHIVE = {
    "kind": "geotiff_scenes", "collection": "landsat", "file_prefix": "LC08",
    "crs": "EPSG:32755", "origin": [590000.0, 6105000.0], "res": 30.0,
    "scene_hw": [360, 380], "scenes": 2, "shift_m": [1800.0, 1200.0],
    "first_date": "2020-01-10", "step_days": 1, "namespace": "nbar",
    "nodata": -999, "nodata_corner": 0.125, "compress": False}
SIZE = 128              # an export: 2 x 2 of the `scene` layer's tiles
TILE = 64
LAYER = {"title": "one day's scene, cubic", "collection": "landsat",
         "rgb_products": ["nbar"], "time_generator": "mas",
         "resample": "cubic"}
CONFIG = {"archive": ARCHIVE, "layers": [
    dict(LAYER, name="scene", wcs_max_tile_width=TILE,
         wcs_max_tile_height=TILE),
    dict(LAYER, name="scene_one")]}          # upstream's 1024: one tile
# the cell's rule, from the cell's own file
TRAFFIC = spec.sized(spec.load_json(os.path.join(
    spec.HERE, "traffic", "coverage-2k-cubic.json")), False)
TRAFFIC["size"] = [SIZE, SIZE]

# name -> (scene, centre in source pixels (col, row), source px a px)
CASES = {
    "inside_at_0.7": (0, (200.0, 200.0), 0.7),
    "inside_at_1.4": (1, (190.0, 200.0), 1.4),
    "over_the_east_edge": (0, (340.0, 150.0), 1.0),
    "over_the_south_edge": (1, (200.0, 330.0), 0.9),
    "over_the_nodata_corner": (0, (60.0, 60.0), 1.0),
}


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("export_route")
    store = MASStore()
    for rec in geotiff_scenes.build(ARCHIVE, SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    conf = root / "conf"
    conf.mkdir()
    (conf / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": [dict({k: v for k, v in lay.items() if k != "collection"},
                        data_source=str(root / lay["collection"]))
                   for lay in CONFIG["layers"]]}))
    mas = MASClient(store)
    watcher = ConfigWatcher(str(conf), mas_factory=lambda addr: mas,
                            install_signal=False)
    metrics = MetricsLogger()
    server = OWSServer(watcher, mas_factory=lambda addr: mas,
                       metrics=metrics, gateway=None,
                       temp_dir=str(root / "not" / "made" / "yet"))
    return {"server": server, "metrics": metrics, "root": root,
            "gen": _generator("scene")}


def _generator(layer):
    return wcs_exports.Generator(dict(TRAFFIC, layer=layer), CONFIG,
                                 geotiff_scenes, SEED)


def _request(gen, case, size=SIZE):
    ti, (col, row), ratio = CASES[case]
    src = gen.sources[ti]
    half = size * ratio * src.dx / 2
    bbox = gen.bbox_at(src, src.x0 + col * src.dx, src.y0 + row * src.dy,
                       (half, half))
    return gen._req(ti, bbox, (size, size), (case,))


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


def _export_stats(env):
    return dict(env["metrics"].summary().get("export_pipeline") or {})


# --- the served export against the reference ------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_engine_export_is_the_reference(env, case):
    """Four tiles through the staged engine: every pixel, then the rows
    and columns beside the seams by themselves, and the tags."""
    gen = env["gen"]
    req = _request(gen, case)
    before = _export_stats(env).get("exports", 0)
    status, body = _get(env["server"], req.path)
    assert status == 200 and wcs_exports.tiff_ok(status, body), body[:300]
    assert _export_stats(env)["exports"] == before + 1
    problems, rec = gen.held(req, body)
    assert problems == [], (problems, rec)
    assert rec["pixels_checked"] == SIZE * SIZE
    assert rec["max_abs_err"] < TRAFFIC["check"]["tol_dn"]
    if case.startswith("over"):
        assert 0.3 < rec["data_fraction"] < 0.95, rec
    else:
        assert rec["data_fraction"] == 1.0
    seams = gen.seam_sets((SIZE, SIZE), 2)
    assert [s[0].size * s[1].size for s in seams] == [4 * SIZE, 4 * SIZE]
    problems, rec = gen.held(req, body, seams)
    assert problems == [] and rec["pixels_checked"] == 8 * SIZE


@pytest.mark.parametrize("case", ["inside_at_0.7", "over_the_nodata_corner"])
def test_single_tile_export_is_the_reference(env, case):
    """A coverage of one tile never reaches the engine
    (`len(local_tiles) > 1`): the per-tile leg serves it, to the same
    rule, and `export_pipeline.exports` does not count it."""
    gen = _generator("scene_one")
    req = _request(gen, case)
    before = _export_stats(env).get("exports", 0)
    status, body = _get(env["server"], req.path)
    assert status == 200, body[:300]
    assert _export_stats(env).get("exports", 0) == before
    problems, rec = gen.held(req, body)
    assert problems == [], (problems, rec)


def test_the_servers_temp_dir_is_made(env):
    """`-temp_dir` may name a directory nobody has made (the benchmark's
    `serve.py` does): the server makes it, or every export is a 500."""
    assert os.path.isdir(env["server"].temp_dir)
    assert env["server"].temp_dir.endswith(os.path.join("not", "made", "yet"))
    assert os.listdir(env["server"].temp_dir) == []     # nothing left behind


# --- what the engine leaves behind ---------------------------------------------

def test_debug_counts_tiles_bytes_and_stages(env):
    before = _export_stats(env)
    req = _request(env["gen"], "inside_at_1.4")
    # 1/64 px on: nothing the first request left is the answer
    b = req.meta["bbox"]
    d = (b[2] - b[0]) / SIZE / 64
    req = env["gen"]._req(req.meta["ti"], (b[0] + d, b[1], b[2] + d, b[3]),
                          (SIZE, SIZE), ("debug",))
    status, _ = _get(env["server"], req.path)
    assert status == 200
    after = _export_stats(env)

    def moved(key):
        return after.get(key, 0) - before.get(key, 0)
    assert moved("exports") == 1 and moved("tiles") == 4
    assert moved("tiles_resident") == 4 and moved("tiles_fallback") == 0
    # the layer is cubic: every resident tile's taps came as
    # neighbourhoods (`ops.warp._tap_pairs`)
    forms = {k: after["tap_form"].get(k, 0)
             - before.get("tap_form", {}).get(k, 0)
             for k in ("neighbourhood", "per_tap")}
    assert forms == {"neighbourhood": 4, "per_tap": 0}
    # a float32 and a validity byte a pixel came off the device
    assert moved("readback_bytes") == SIZE * SIZE * 5
    for key in ("plan_s", "warp_s", "encode_s", "write_s", "wall_s"):
        assert moved(key) > 0, key
    last = after["last"]
    assert last["plan_s"] < last["wall_s"]
    assert last["tiles_resident"] == 4 and last["index_queries"] == 1


def test_the_tap_form_is_the_kernels_not_the_layers(env, monkeypatch):
    """`tap_form` counts what the executor handed the kernel, not the
    layer's method: where the unfolded copies would not fit the bound
    (`ops.warp._UNFOLD_BYTES`), a cubic tile gathers a tap at a time
    and is counted and traced so."""
    import sys

    import jax

    import gsky_tpu.ops.warp  # noqa: F401
    warp = sys.modules["gsky_tpu.ops.warp"]
    monkeypatch.setattr(warp, "_UNFOLD_BYTES", 0)
    jax.clear_caches()          # no program traced under the real bound
    obs.reset_recorder()
    before = _export_stats(env)
    req = _request(env["gen"], "inside_at_0.7")
    b = req.meta["bbox"]
    d = (b[2] - b[0]) / SIZE / 32
    req = env["gen"]._req(req.meta["ti"], (b[0] + d, b[1], b[2] + d, b[3]),
                          (SIZE, SIZE), ("per_tap",))
    try:
        status, _ = _get(env["server"], req.path)
    finally:
        jax.clear_caches()
    assert status == 200
    after = _export_stats(env)
    assert {k: after["tap_form"].get(k, 0)
            - before.get("tap_form", {}).get(k, 0)
            for k in ("neighbourhood", "per_tap")} \
        == {"neighbourhood": 0, "per_tap": 4}
    tiles = [sp for t in obs.default_recorder().traces()
             for sp in t.get("spans", []) if sp["name"] == "export.tile"]
    assert [sp["attrs"]["tap_form"] for sp in tiles] == ["per_tap"] * 4


def test_trace_holds_a_span_a_tile_and_the_write(env):
    obs.reset_recorder()
    req = _request(env["gen"], "over_the_east_edge")
    b = req.meta["bbox"]
    d = (b[3] - b[1]) / SIZE / 64
    req = env["gen"]._req(req.meta["ti"], (b[0], b[1] + d, b[2], b[3] + d),
                          (SIZE, SIZE), ("trace",))
    status, _ = _get(env["server"], req.path)
    assert status == 200
    spans = [sp for t in obs.default_recorder().traces()
             for sp in t.get("spans", [])]
    tiles = [sp for sp in spans if sp["name"] == "export.tile"]
    assert len(tiles) == 4
    assert {sp["attrs"]["route"] for sp in tiles} == {"resident"}
    assert {sp["attrs"]["tap_form"] for sp in tiles} == {"neighbourhood"}
    write, = [sp for sp in spans if sp["name"] == "export.write"]
    assert write["attrs"]["format"] == "geotiff" and write["dur_s"] > 0
    names = {sp["name"] for sp in spans}
    assert {"export.plan", "export.decode_stage", "export.warp_stage",
            "export.encode_stage"} <= names
    # the write comes after the engine's last stage
    warp, = [sp for sp in spans if sp["name"] == "export.warp_stage"]
    assert write["t0"] >= warp["t0"] + warp["dur_s"] - 1e-3


def test_the_write_deflates_its_blocks_on_the_pool(env):
    """An export of 2 x 2 GeoTIFF blocks (256 + 64 a side): every block
    is deflated on the shared pool, in `/debug` and on the write's span."""
    size = 320
    obs.reset_recorder()
    before = _export_stats(env).get("deflate", {})
    req = _request(env["gen"], "inside_at_0.7", size)
    status, body = _get(env["server"], req.path)
    assert status == 200 and wcs_exports.tiff_ok(status, body), body[:300]
    after = _export_stats(env)["deflate"]

    def moved(key):
        return after[key] - before.get(key, 0)
    assert moved("blocks_pooled") == moved("blocks") == 4
    assert moved("writes") == 1 and moved("busy_s") > 0
    write, = [sp for t in obs.default_recorder().traces()
              for sp in t.get("spans", []) if sp["name"] == "export.write"]
    assert write["attrs"]["blocks"] == 4
    assert write["attrs"]["deflate_workers"] == after["workers"] >= 1


# --- what the rule refuses ----------------------------------------------------

def _body(tmp_path, plane, bbox, shift_px=0.0):
    """A GeoTIFF of `plane` as the server writes one, its tie point
    `shift_px` pixels east of the bbox's corner."""
    h, w = plane.shape
    gt = GeoTransform.from_bbox(BBox(*bbox), w, h)
    gt = GeoTransform(gt.x0 + shift_px * gt.dx, gt.dx, 0.0, gt.y0, 0.0, gt.dy)
    path = str(tmp_path / "spoiled.tif")
    write_geotiff(path, plane[None].astype(np.float32), gt,
                  parse_crs("EPSG:4326"), wcs_exports.NODATA)
    with open(path, "rb") as fp:
        return fp.read()


def _as_served(values, valid):
    return np.where(valid, values, wcs_exports.NODATA).astype(np.float32)


def _bfloat16(a):
    """float32 -> bfloat16 -> float32, round to nearest even."""
    bits = np.asarray(a, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def _spoil_bfloat16(gen, req):
    src = gen.sources[req.meta["ti"]]
    raw = src.read()
    held16 = np.where(raw == src.nodata, raw, _bfloat16(raw))
    return _as_served(*reference_export.render(
        src, req.meta["bbox"], "EPSG:4326", SIZE, SIZE, "cubic",
        data=held16))


def _spoil_method(method):
    def spoil(gen, req):
        return _as_served(*reference_export.render(
            gen.sources[req.meta["ti"]], req.meta["bbox"], "EPSG:4326",
            SIZE, SIZE, method))
    return spoil


def _right(gen, req):
    return _spoil_method("cubic")(gen, req)


def _spoil_shift(gen, req):
    plane = _right(gen, req)
    plane[:TILE, TILE:] = np.roll(plane[:TILE, TILE:], 1, axis=1)
    return plane


def _spoil_drop(gen, req):
    plane = _right(gen, req)
    plane[TILE:, :TILE] = wcs_exports.NODATA
    return plane


SPOILED = {
    "rasters_held_in_bfloat16": (_spoil_bfloat16, 0.0, 0.5),
    "bilinear_for_cubic": (_spoil_method("bilinear"), 0.0, 0.5),
    "nearest_for_cubic": (_spoil_method("near"), 0.0, 0.5),
    "a_tile_one_pixel_on": (_spoil_shift, 0.0, 0.2),
    "a_tile_dropped": (_spoil_drop, 0.0, 0.24),
    "tie_point_one_pixel_on": (_right, 1.0, None),
}


def test_the_rule_passes_the_reference_itself(env, tmp_path):
    gen = env["gen"]
    req = _request(gen, "over_the_nodata_corner")
    problems, rec = gen.held(
        req, _body(tmp_path, _right(gen, req), req.meta["bbox"]))
    assert problems == [] and rec["mismatch"] == 0.0


@pytest.mark.parametrize("fault", list(SPOILED))
def test_the_rule_refuses(env, tmp_path, fault):
    """Each fault, written as the server writes an export, is refused by
    the cell's check: by the share of pixels past `tol_dn` (far over the
    bound, so the bound has room on both sides) or by the tags."""
    gen = env["gen"]
    spoil, shift_px, least = SPOILED[fault]
    req = _request(gen, "inside_at_0.7")
    problems, rec = gen.held(
        req, _body(tmp_path, spoil(gen, req), req.meta["bbox"], shift_px))
    assert problems, rec
    if least is None:
        assert "tie point" in problems[0] and rec["mismatch"] == 0.0
    else:
        assert rec["mismatch"] > least > 10 * TRAFFIC["check"]["bound_mismatch"]
