"""`landsat8-export.coverage-2k-cubic`: its entries and the shapes the
configuration keeps, the generator (what a seed draws, the draw's rules,
the twin rule, the prefill, the refusal of a program that cannot serve
the cell), the reference's reader against the program's writer and its
cubic tap against values computed by hand, the new readers on recorded
`/debug` pairs, the roofline's counts, and one rehearsal of the whole
cell on the CPU."""

import hashlib
import itertools
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reference, reference_export, roofline_export, spec  # noqa: E402
from benchmarks.archives import geotiff_scenes           # noqa: E402
from benchmarks.ctx import Ctx                           # noqa: E402
from benchmarks.generators import wcs_exports            # noqa: E402

CELL = "landsat8-export.coverage-2k-cubic"
SEED = 2900000037
NEW = ["export.plan_ms_per_export", "export.warp_stage_ms_per_export",
       "export.encode_stage_ms_per_export",
       "frontend.wcs_write_ms_per_export", "export.resident_tile_share",
       "kernels.export_warp_ms_per_tile",
       "warp_scenes_ctrl_scored_roofline"]


@pytest.fixture(scope="module")
def full():
    return spec.load_cell(CELL)


def _generator(cell, seed=SEED):
    return wcs_exports.Generator(cell.traffic, cell.config, geotiff_scenes,
                                 seed)


# --- the entries and the configuration ----------------------------------------

def test_its_entries(full):
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "coverage-2k-cubic"
    config, = [c for c in bench["configs"] if c["name"] == "landsat8-export"]
    assert config["reduced"] == ["archive_extent", "export_extent",
                                 "wcs_timeout"]
    assert sorted(full.config["reduced"]) == sorted(config["reduced"])
    assert [m["name"] for m in full.end_to_end] == [
        "latency_p50_ms", "throughput_rps", "setup_s"]
    reported = {m["name"] for m in full.per_layer}
    assert set(NEW) <= reported
    assert {"index.row_decode_hit_share", "index.sql_statements_per_query",
            "index.footprint_prepared_hit_share", "device.idle_share",
            "device.compiles_in_window", "device.programs_warmed"} <= reported
    # its reader knows `tile.*`, `drill.*`, `wps.*` and `encode` spans and
    # finds none of them in an export, so it lists the cells it reads
    outside, = [m for m in bench["per_layer"]
                if m["name"] == "device.idle_outside_stages_share"]
    assert CELL not in outside["workloads"] and len(outside["workloads"]) == 4
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    assert entry["why"].count("20-50") == 1 and "device" in entry["why"]


def test_published_shapes_are_uncut(full):
    a = full.config["archive"]
    mosaic = spec.load_cell("landsat8-mosaic.pan-cold").config["archive"]
    for key in ("kind", "crs", "origin", "res", "scene_hw", "namespace",
                "nodata", "nodata_corner", "compress", "first_date",
                "step_days"):
        assert a[key] == mosaic[key], key
    assert a["scenes"] == 8
    assert a["shift_m"] == [s / 2 for s in mosaic["shift_m"]]
    lay, = full.config["layers"]
    assert lay["resample"] == "cubic" and not lay.get("accum")
    assert (lay["wcs_max_tile_width"], lay["wcs_max_tile_height"]) \
        == (1024, 1024)
    t = full.traffic
    assert t["size"] == [2048, 2048] and t["crs"] == "EPSG:4326"
    assert t["src_px_per_px"] == [0.7, 1.4] and t["on_scene_min"] == 0.6
    assert t["loop"] == {"kind": "closed", "connections": 2}
    assert t["check"]["full"]["size"] == [4096, 4096]
    assert t["demand_still"] == ["cache.scene.misses",
                                 "export_pipeline.tiles_fallback"]


# --- the generator ----------------------------------------------------------------

# sha256 over the paths of a seed's first 200 exports at the size the
# chip runs, as PR 37 drew them
DRAWN = "d19c4312677b9fcef90cb0d93569ff770156bb3dac59bd512b60d39ab8db4f22"


def test_the_same_seed_draws_the_same_requests(full):
    h = hashlib.sha256()
    reqs = list(itertools.islice(_generator(full, 2147483659).window().reqs,
                                 200))
    for r in reqs:
        h.update(r.path.encode() + b"\n")
    assert len({r.path for r in reqs}) == 200           # every export new
    assert h.hexdigest() == DRAWN


def test_a_draw_keeps_the_traffic_files_rules(full):
    gen = _generator(full)
    reqs = list(itertools.islice(gen.window().reqs, 400))
    ratios = np.array([r.meta["src_px_per_px"] for r in reqs])
    assert 0.7 <= ratios.min() < 0.75 and 1.3 < ratios.max() <= 1.4
    # log-uniform: as many below the geometric mean as above it
    assert 0.4 < np.mean(ratios < (0.7 * 1.4) ** 0.5) < 0.6
    assert min(r.meta["on_scene"] for r in reqs) >= 0.6
    assert np.mean([r.meta["on_scene"] < 1.0 for r in reqs]) > 0.2
    assert np.mean([r.meta["nodata_corner"] > 0 for r in reqs]) > 0.02
    assert {r.meta["ti"] for r in reqs} == set(range(8))
    for r in reqs[:20]:
        # the same size in metres on both axes, through the export's
        # centre (a parallel's length changes by 0.3 % over its height);
        # the scenes' grid metres are up to 0.4 % longer than the
        # ground's (UTM's scale factor 590 km from the central meridian)
        b, src = r.meta["bbox"], gen.sources[r.meta["ti"]]
        mx, my = (b[0] + b[2]) / 2, (b[1] + b[3]) / 2
        x, y = reference.project(
            np.array([b[0], b[2], mx, mx]), np.array([my, my, b[1], b[3]]),
            "EPSG:4326", src.crs)
        across = np.hypot(x[1] - x[0], y[1] - y[0]) / 2048
        up = np.hypot(x[3] - x[2], y[3] - y[2]) / 2048
        assert across == pytest.approx(up, rel=2e-4)
        assert across == pytest.approx(r.meta["src_px_per_px"] * 30.0,
                                       rel=5e-3)
        assert "&width=2048&height=2048&format=GeoTIFF" in r.path
        assert "coverage=scene&crs=EPSG:4326" in r.path


def test_a_twin_lies_a_128th_of_a_pixel_on(full):
    gen = _generator(full)
    reqs = list(itertools.islice(gen.window().reqs, 5))
    for r, t in zip(reqs, gen.twins(reqs)):
        b, tb = r.meta["bbox"], t.meta["bbox"]
        px = ((b[2] - b[0]) / 2048, (b[3] - b[1]) / 2048)
        assert (tb[0] - b[0]) / px[0] == pytest.approx(1 / 128, rel=1e-6)
        assert (tb[3] - b[3]) / px[1] == pytest.approx(1 / 128, rel=1e-6)
        assert tb[2] - tb[0] == pytest.approx(b[2] - b[0], rel=1e-12)
        assert t.meta["ti"] == r.meta["ti"] and t.path != r.path
        assert t.key == r.key + ("twin",)


def test_prefill_is_one_native_export_a_date(full):
    fill = _generator(full).prefill()
    assert [r.meta["ti"] for r in fill] == list(range(8))
    assert {r.meta["src_px_per_px"] for r in fill} == {1.0}


def test_the_published_size_is_checked_on_blocks_and_seams(full):
    gen = _generator(full)
    req, sets = gen.full_export()
    assert req.meta["size"] == (4096, 4096)
    assert req.meta["src_px_per_px"] == 1.0 and req.meta["on_scene"] >= 0.6
    sizes = [r.size * c.size for r, c in sets]
    assert sizes == [512 * 512] * 4 + [12 * 4096, 12 * 4096]
    rows = sets[4][0].ravel().tolist()
    assert rows == [1022, 1023, 1024, 1025, 2046, 2047, 2048, 2049,
                    3070, 3071, 3072, 3073]
    again, _ = _generator(full).full_export()
    assert again.path == req.path


def test_a_program_that_cannot_serve_the_cell_is_refused_at_once(
        full, monkeypatch):
    """PR 37's parent answers every export with a 500 (its server does
    not make the temp directory `serve.py` names); the generator
    refuses such a program before the server starts."""
    from gsky_tpu.server import ows
    monkeypatch.delattr(ows, "export_temp_dir")
    with pytest.raises(SystemExit) as refused:
        _generator(full)
    assert "export_temp_dir" in str(refused.value.code)


# --- the reference -----------------------------------------------------------------

def test_cubic_weights_and_tap_by_hand():
    w = reference_export.cubic_weights(np.array([0.0, 0.5]))
    assert [float(x[0]) for x in w] == [0.0, 1.0, 0.0, 0.0]
    assert [float(x[1]) for x in w] == [-0.0625, 0.5625, 0.5625, -0.0625]
    ramp = np.add.outer(10.0 * np.arange(8), np.arange(8.0))
    # Catmull-Rom reproduces a plane: at (col 3.25, row 2.5) of the
    # centres' grid, i.e. corner-based (3.75, 3.0)
    v, ok = reference_export.tap_cubic(ramp, -999.0, np.array([3.75]),
                                       np.array([3.0]))
    assert ok[0] and v[0] == pytest.approx(10 * 2.5 + 3.25)
    # a nodata tap drops out of both sums: the plane survives only by
    # renormalising, so the value moves, but stays a weighted mean
    holed = ramp.copy()
    holed[2, 3] = -999.0
    v2, ok2 = reference_export.tap_cubic(holed, -999.0, np.array([3.75]),
                                         np.array([3.0]))
    assert ok2[0] and v2[0] != pytest.approx(v[0], abs=1e-9)
    assert ramp.min() <= v2[0] <= ramp.max()
    # off the raster's outer edge: no data, whatever the taps hold
    _, off = reference_export.tap_cubic(ramp, -999.0, np.array([-0.01, 8.01]),
                                        np.array([3.0, 3.0]))
    assert not off.any()
    # all-nodata neighbourhood: the weights left sum to nothing
    _, none = reference_export.tap_cubic(np.full((8, 8), -999.0), -999.0,
                                         np.array([4.0]), np.array([4.0]))
    assert not none[0]


@pytest.mark.parametrize("shape, compress", [
    ((1, 300, 520), True), ((1, 256, 256), False), ((2, 70, 33), True)])
def test_reader_against_the_programs_writer(tmp_path, shape, compress):
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import BBox, GeoTransform
    from gsky_tpu.io import write_geotiff
    rng = np.random.default_rng(7)
    data = rng.normal(1600, 300, shape).astype(np.float32)
    data[0, :9, :11] = -9999.0
    bbox = (148.0, -35.5, 148.0 + 0.0003 * shape[2], -35.5 + 0.00027 * shape[1])
    path = str(tmp_path / "w.tif")
    write_geotiff(path, data, GeoTransform.from_bbox(
        BBox(*bbox), shape[2], shape[1]), parse_crs("EPSG:4326"), -9999.0,
        compress=compress)
    with open(path, "rb") as fp:
        got, tags = reference_export.read_geotiff(fp.read())
    assert got.shape == shape and (got == data).all()
    assert tags["tile"] == (256, 256)
    assert tags["compression"] == (8 if compress else 1)
    assert reference_export.georeferencing_problems(
        tags, bbox, shape[2], shape[1], 4326, -9999.0) == []
    moved = (bbox[0] + 0.0003, bbox[1], bbox[2] + 0.0003, bbox[3])
    assert any("tie point" in p for p in
               reference_export.georeferencing_problems(
                   tags, moved, shape[2], shape[1], 4326, -9999.0))
    assert reference_export.georeferencing_problems(
        tags, bbox, shape[2], shape[1], 32755, -999.0) != []


def test_render_in_blocks_is_render_at_once():
    cell = spec.load_cell(CELL, rehearsal=True)
    gen = _generator(cell, 5)
    src = gen.sources[0]
    # round a point 100 px inside the north-west corner: off the scene,
    # in its nodata corner and on its data
    gen_bbox = gen.bbox_at(src, src.x0 + 100 * src.dx, src.y0 + 100 * src.dy,
                           (150 * src.dx, 145 * src.dx))
    want, valid = reference_export.render(src, gen_bbox, "EPSG:4326", 300, 290)
    rows, cols = np.arange(290)[:, None], np.arange(300)[None, :]
    X, Y = reference_export.centres(gen_bbox, 300, 290, rows, cols)
    once, ok = reference_export.resample_at(src, X, Y, "EPSG:4326")
    assert (valid == ok).all() and valid.any() and not valid.all()
    assert np.array_equal(want[valid], once[ok])
    cmp = reference_export.compare(
        np.where(valid, want, -9999.0).astype(np.float32), -9999.0, want,
        valid, 0.05)
    assert cmp["mismatch"] == 0.0 and cmp["max_abs_err"] < 1e-3


# --- the readers ---------------------------------------------------------------------

def _ctx(debug0, debug1, module=None, cell=None):
    c = Ctx(cell=cell or SimpleNamespace(config={"layers": []}, traffic={}),
            results=[], t0=0.0, window_s=20.0, setup_s=1.0, warmup=[],
            warmed=None, debug0=debug0, debug1=debug1,
            compiles_in_window=(0, 0), device_kind="TPU v5 lite",
            hbm_peak_bytes=None)
    if module is not None:
        c.module = lambda name: module.get(name)
    return c


def _debug(exports, legs=None, **sums):
    doc = {"executor": {"dispatches": legs or {}}}
    if exports is not None:
        doc["export_pipeline"] = dict(sums, exports=exports)
    return doc


def _read(name, ctx):
    return spec.reader("layer_metrics", name).read(ctx)


def test_stage_readers_on_a_recorded_pair():
    d0 = _debug(10, plan_s=0.02, warp_s=1.0, encode_s=2.0, write_s=5.0,
                tiles_resident=40, tiles_fallback=0)
    d1 = _debug(50, plan_s=0.10, warp_s=3.0, encode_s=30.0, write_s=25.0,
                tiles_resident=196, tiles_fallback=4)
    ctx = _ctx(d0, d1)
    assert _read(NEW[0], ctx) == pytest.approx(2.0)
    assert _read(NEW[1], ctx) == pytest.approx(50.0)
    assert _read(NEW[2], ctx) == pytest.approx(700.0)
    assert _read(NEW[3], ctx) == pytest.approx(500.0)
    assert _read(NEW[4], ctx) == pytest.approx(97.5)
    # the parent: stage seconds it always kept, none of the new keys
    p0, p1 = _debug(10, warp_s=1.0, encode_s=2.0), \
        _debug(50, warp_s=3.0, encode_s=30.0)
    parent = _ctx(p0, p1)
    assert [_read(n, parent) for n in NEW[:5]] == [
        None, pytest.approx(50.0), pytest.approx(700.0), None, None]
    # no export in the window, no `export_pipeline` at all: nothing
    assert [_read(n, _ctx(d1, d1)) for n in NEW[:5]] == [None] * 5
    assert [_read(n, _ctx({}, {})) for n in NEW[:5]] == [None] * 5


def test_kernel_readers_on_a_recorded_pair(full):
    legs0 = {"scene_mosaic:((1, 7680, 7936), (1536, 1536))": 100}
    legs1 = {"scene_mosaic:((1, 7680, 7936), (1536, 1536))": 180,
             "scene_mosaic:((1, 7680, 7936), (1024, 768))": 20,
             "render_byte:((4, 7680, 7936), (512, 512))": 9}
    ctx = _ctx(_debug(1, legs0), _debug(2, legs1),
               {"warp_scenes_ctrl_scored": (4.0, 25)}, full)
    assert _read(NEW[5], ctx) == pytest.approx(160.0)
    one = roofline_export.warp_scenes_ctrl_scored(1)[1] / 819e9
    share = _read(NEW[6], ctx)
    assert share == pytest.approx(100 * one / 0.160, rel=1e-6)
    assert 0.0 < share < 1.0
    bare = _ctx(_debug(1, {}), _debug(2, {"render_byte": 9}), {}, full)
    assert _read(NEW[5], bare) is None and _read(NEW[6], bare) is None


def test_roofline_counts():
    o1, b1 = roofline_export.warp_scenes_ctrl_scored(1)
    px = 1024 * 1024
    assert b1 == px * 16 * 4 + 2 * 65 * 65 * 4 + 11 * 4 + px * 5
    o2, b2 = roofline_export.warp_scenes_ctrl_scored(2)
    assert b2 - b1 == px * 16 * 4 + 11 * 4 and o2 > o1
    assert b1 / 819e9 > 10 * o1 / 197e12       # memory-bound
    # a bilinear tile of 256 x 256 reads what roofline.py says a tile reads,
    # and writes a float and a validity byte where that writes a byte
    from benchmarks import roofline
    ob, bb = roofline_export.warp_scenes_ctrl_scored(4, (256, 256), 4)
    assert bb - roofline.render_scenes_ctrl(4, (256, 256), 4)[1] \
        == 256 * 256 * 4


# --- the cell ---------------------------------------------------------------------------

def test_rehearsal_runs_the_cell(tmp_path):
    def run(trace):
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
             "--workload", CELL, "--seed", str(SEED), "--seconds", "4",
             "--trace", str(trace), "--rehearsal", "--out", str(tmp_path)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    traced = run(1)
    assert traced["correct"] and traced["failed"] == 0
    m = traced["metrics"]
    assert m["export.resident_tile_share"]["value"] == 100.0
    for name in NEW[:4]:
        assert m[name]["value"] > 0, name
    assert NEW[5] not in m and NEW[6] not in m      # no device trace here
    report = json.load(open(tmp_path / f"{CELL}.json"))
    assert all(leg.startswith("scene_mosaic:") for leg in report["legs"])
    whats = [c["what"] for c in report["records"]]
    assert whats == ["full", "window", "window", "window"]
    assert all(c["mismatch"] <= 0.005 for c in report["records"])
    assert report["records"][0]["export_4k_s"] > 0
    assert report["records"][0]["host_rss_peak_bytes"] > 0
    assert traced["checks"]["demand_moved"] == 0
    assert traced["checks"]["answers_checked"] == 4
    untraced = run(0)
    assert untraced["correct"]
    assert set(untraced["metrics"]) == {"latency_p50_ms", "throughput_rps",
                                        "setup_s"}
