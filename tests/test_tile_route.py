"""The one route from a tile request to its kernel
(`pipeline/executor.py`): which leg `WarpExecutor._choose_leg` picks for
each entry point under each thing it can observe; the leg a TPU runs
(`render_scenes_ctrl` through `render_composite_byte`,
`render_rgba_ctrl` through `render_rgba_byte`) held to the plain
reference (`benchmarks/reference.py`, `reference_rgb.py`) within the
benchmark's own bounds, and to the modular route for `cubic`, which the
reference does not have; and the named record `_scene_groups` hands
every leg."""

import datetime as dt

import numpy as np
import pytest

import jax.numpy as jnp

import test_paged
import test_rgb_granules as RG
from benchmarks import reference, reference_rgb
from benchmarks.archives import geotiff_scenes
from benchmarks.archives import sentinel2_granules as s2
from gsky_tpu.geo.crs import EPSG3857
from gsky_tpu.geo.transform import BBox
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.ops.expr import compile_expr, fingerprint
from gsky_tpu.ops.scale import scale_to_byte
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline, pages
from gsky_tpu.pipeline import waves as W
from gsky_tpu.pipeline.executor import SceneGroup, WarpExecutor


@pytest.fixture(autouse=True)
def _hermetic(tmp_path, monkeypatch):
    """A race ledger, page pool and wave scheduler of the test's own
    (the rule of tests/test_paged.py and test_waves.py)."""
    monkeypatch.setenv("GSKY_KERNEL_LEDGER", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setenv("GSKY_PAGE_SIZE", "64x128")
    monkeypatch.setenv("GSKY_PAGE_POOL_MB", "8")
    pages.reset_default_pool()
    W.reset_waves()
    yield
    W.reset_waves()
    pages.reset_default_pool()


# --- (a) which leg serves --------------------------------------------------

# what the executor can observe -> the environment that shows it
OBSERVED = {
    "plain_cpu": {},                    # the chip's choice too
    "interpret": {"GSKY_PALLAS": "interpret"},
    "interpret_waves_off": {"GSKY_PALLAS": "interpret", "GSKY_WAVES": "0"},
    "interpret_paged_off": {"GSKY_PALLAS": "interpret", "GSKY_PAGED": "0"},
}
# entry point -> the `bucket_stats` name that counts up (None: the
# entry declines and counts nothing)
LEGS = {
    "render_byte_scenes": {
        "plain_cpu": "render_byte", "interpret": "render_byte_wave",
        "interpret_waves_off": "render_byte_paged",
        "interpret_paged_off": "render_byte"},
    "warp_mosaic_scenes": {
        "plain_cpu": "scene_mosaic", "interpret": "scene_mosaic_wave",
        "interpret_waves_off": "scene_mosaic_paged",
        "interpret_paged_off": "scene_mosaic"},
    "render_expr_byte": {
        "plain_cpu": None, "interpret": "render_expr_wave",
        "interpret_waves_off": "render_expr_paged",
        "interpret_paged_off": None},
}


@pytest.mark.parametrize("observed", list(OBSERVED))
@pytest.mark.parametrize("entry", list(LEGS))
def test_one_leg_serves(entry, observed, monkeypatch):
    for name in ("GSKY_PALLAS", "GSKY_WAVES", "GSKY_PAGED", "GSKY_SPMD"):
        monkeypatch.delenv(name, raising=False)
    for name, value in OBSERVED[observed].items():
        monkeypatch.setenv(name, value)
    group = test_paged._fake_group(B=2, shift=False)
    monkeypatch.setattr(WarpExecutor, "_scene_groups",
                        lambda self, *a, **kw: [group])
    ex = WarpExecutor()
    where = (None, [0, 1], [2.0, 1.0], None, None, 96, 96)
    if entry == "render_expr_byte":
        out = ex.render_expr_byte(
            *where, 2, fingerprint(compile_expr("(a - b) / (a + b)")))
    else:
        out = getattr(ex, entry)(*where, 2)
    want = LEGS[entry][observed]
    assert (out is None) == (want is None)
    assert [k.split(":")[0] for k in ex.bucket_stats] == \
        ([] if want is None else [want])
    assert sum(ex.bucket_stats.values()) == (0 if want is None else 1)
    if pages._default is not None:
        assert pages._default.stats()["pinned"] == 0


# --- (b) the chip's leg against the plain reference ------------------------

SEED = 33
LANDSAT = {
    "kind": "geotiff_scenes", "collection": "landsat", "file_prefix": "LC08",
    "crs": "EPSG:32755", "origin": [590000.0, 6105000.0], "res": 30.0,
    "scene_hw": [600, 620], "scenes": 3, "shift_m": [6200.0, 3600.0],
    "first_date": "2020-01-10", "step_days": 1, "namespace": "nbar",
    "nodata": -999, "nodata_corner": 0.125, "compress": False}
CLIP, SCALE = 3000.0, 254.0 / 3000.0
# the benchmark's bound for Landsat tiles (traffic/pan-cold.json
# `check.bound_mismatch`; PERF.md section 2): 0.2 % of bytes, one level
BOUND_SHARE, BOUND_LEVELS = 0.002, 1
# a 256 x 256 tile at one source pixel a pixel, over ground all three
# scenes cover; the newest's nodata corner lies inside it, so the
# mosaic's winner changes within the tile
CENTRE, HALF = (604500.0, 6093000.0), 3840.0


def _stamp(day):
    return (dt.datetime.fromisoformat(LANDSAT["first_date"]).replace(
        tzinfo=dt.timezone.utc) + dt.timedelta(days=day)).timestamp()


# scenes -> (start, end) of the request, and the scenes it selects
SELECT = {"one_scene": ((_stamp(0), None), [0]),
          "mosaic": ((_stamp(0), _stamp(3)), [0, 1, 2])}


@pytest.fixture(scope="module")
def landsat(tmp_path_factory):
    root = tmp_path_factory.mktemp("route_landsat")
    store = MASStore()
    for rec in geotiff_scenes.build(LANDSAT, SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    xs = np.array([CENTRE[0] - HALF, CENTRE[0] + HALF])
    ys = np.array([CENTRE[1] - HALF, CENTRE[1] + HALF])
    mx, my = reference.project(xs, ys, LANDSAT["crs"], "EPSG:3857")
    return {"mas": MASClient(store), "root": str(root / "landsat"),
            "sources": geotiff_scenes.sources(LANDSAT, SEED),
            "bbox": (float(mx[0]), float(my[0]), float(mx[1]),
                     float(my[1]))}


def _request(landsat, scenes, method):
    (start, end), _ = SELECT[scenes]
    return GeoTileRequest(
        collection=landsat["root"], bands=[LANDSAT["namespace"]],
        bbox=BBox(*landsat["bbox"]), crs=EPSG3857, width=256, height=256,
        start_time=start, end_time=end, resample=method)


def _render(landsat, scenes, method, window, monkeypatch):
    """(pipe, uint8 (256, 256)) through `render_composite_byte`, the
    window on as on a TPU or off as on a CPU."""
    monkeypatch.setenv("GSKY_WARP_WINDOW", window)
    pipe = TilePipeline(landsat["mas"], executor=WarpExecutor())
    got = pipe.render_composite_byte(
        _request(landsat, scenes, method), 0.0, SCALE, CLIP, 0, False)
    assert got is not None
    ex = pipe.executor
    assert [k.split(":")[0] for k in ex.bucket_stats] == ["render_byte"]
    assert (ex.win_engaged, ex.win_declined) == \
        ((1, 0) if window == "1" else (0, 0))
    return pipe, np.asarray(got)


@pytest.mark.parametrize("window", ["1", "0"])
@pytest.mark.parametrize("scenes", list(SELECT))
@pytest.mark.parametrize("method", ["near", "bilinear"])
def test_render_scenes_ctrl_matches_reference(landsat, method, scenes,
                                              window, monkeypatch):
    _, got = _render(landsat, scenes, method, window, monkeypatch)
    want = reference.render_tile(
        [landsat["sources"][k] for k in SELECT[scenes][1]],
        landsat["bbox"], "EPSG:3857", 256, 256, method, 0.0, SCALE, CLIP)
    assert 0.5 < np.mean(want != 255) <= 1.0        # a tile of data
    if scenes == "mosaic":
        # scene 2 wins except in its nodata corner, where scene 1 does
        alone = reference.render_tile(
            landsat["sources"][2:], landsat["bbox"], "EPSG:3857", 256,
            256, method, 0.0, SCALE, CLIP)
        assert 0.01 < np.mean(alone == 255) < 0.5 and (want != 255).all()
    assert np.mean(got != want) <= BOUND_SHARE
    both = (got != 255) & (want != 255)
    assert np.abs(got[both].astype(int)
                  - want[both].astype(int)).max() <= BOUND_LEVELS


@pytest.mark.parametrize("window", ["1", "0"])
@pytest.mark.parametrize("scenes", list(SELECT))
def test_cubic_matches_modular_route(landsat, scenes, window, monkeypatch):
    """`cubic` is not in the reference: the fused kernel is held to the
    modular route (`TilePipeline.process` + `scale_to_byte`), the same
    arithmetic in another program, to the byte."""
    pipe, got = _render(landsat, scenes, "cubic", window, monkeypatch)
    res = pipe.process(_request(landsat, scenes, "cubic"))
    ns = LANDSAT["namespace"]
    want = np.asarray(scale_to_byte(
        jnp.asarray(res.data[ns]), jnp.asarray(res.valid[ns]),
        offset=0.0, scale=SCALE, clip=CLIP, colour_scale=0, auto=False))
    assert 0.5 < np.mean(want != 255) <= 1.0
    np.testing.assert_array_equal(got, want)


@pytest.fixture(scope="module")
def sentinel2(tmp_path_factory):
    root = tmp_path_factory.mktemp("route_s2")
    store = MASStore()
    for rec in s2.build(RG.ARCHIVE, RG.SEED, str(root)):
        assert not rec.get("error"), rec
        store.ingest(rec)
    return {"mas": MASClient(store), "root": str(root / "s2"),
            "sources": s2.sources(RG.ARCHIVE, RG.SEED)}


@pytest.mark.parametrize("case,granules", [("interior", 1),
                                           ("overlap_strip", 2)])
def test_render_rgba_ctrl_matches_reference(sentinel2, case, granules):
    bbox = RG._bbox(case)
    stamp = dt.datetime.fromisoformat(RG.ARCHIVE["date"]).replace(
        tzinfo=dt.timezone.utc).timestamp()
    pipe = TilePipeline(sentinel2["mas"], executor=WarpExecutor())
    got = pipe.render_rgba_byte(GeoTileRequest(
        collection=sentinel2["root"], bands=RG.NAMESPACES,
        bbox=BBox(*bbox), crs=EPSG3857, width=256, height=256,
        start_time=stamp, end_time=None, resample="bilinear"),
        0.0, RG.SCALE, RG.CLIP, 0, False)
    assert got is not None
    (leg,) = pipe.executor.bucket_stats
    # G granule sets (a power of two) of three bands in one dispatch
    assert leg.startswith(f"render_rgba:(({granules}, ")
    rec = reference_rgb.compare(np.asarray(got),
                                RG._want(sentinel2["sources"], bbox))
    assert rec["mismatch"] <= RG.BOUND and rec["max_byte_diff"] <= 1, rec


# --- (c) the record --------------------------------------------------------

@pytest.mark.parametrize("stacked", [True, False])
def test_scene_group_is_read_by_field(landsat, stacked, monkeypatch):
    monkeypatch.setenv("GSKY_WARP_WINDOW", "1")
    monkeypatch.setenv("GSKY_PALLAS", "interpret")
    # pages a 256-px footprint fits into the default eight slots of
    monkeypatch.setenv("GSKY_PAGE_SIZE", "128x512")
    monkeypatch.setenv("GSKY_PAGE_POOL_MB", "16")
    pages.reset_default_pool()
    pipe = TilePipeline(landsat["mas"], executor=WarpExecutor())
    req = _request(landsat, "mosaic", "near")
    granules = pipe.index(req)
    assert len(granules) == 3
    groups = pipe.executor._scene_groups(
        granules, [0, 0, 0], [1.0, 2.0, 3.0], req.dst_gt(), req.crs, 256,
        256, stacked=stacked)
    (g,) = groups
    assert isinstance(g, SceneGroup)
    B = 4                               # three scenes, padded to a power of two
    if stacked:
        assert g.stack.shape[0] == B and g.win0.shape == (2,)
    else:
        assert len(g.stack) == B and g.stack[3] is g.stack[0]
        assert g.win0.shape == (B, 2)   # an origin a scene
    assert g.ctrl.shape == g.ctrl_dev.shape == (2, 17, 17)
    assert g.params.dtype == np.float32 and g.params64.dtype == np.float64
    assert g.params.shape == g.params64.shape == (B, 11)
    assert g.step == 16 and g.win is not None
    assert g.skey == tuple(s.serial for s in g.scenes) + (B,)
    assert len(g.scenes) == 3
    # the paged inputs come from the record's fields alone
    pool, tables, params16 = pipe.executor._paged_from_group(g, 1)
    try:
        assert tables.shape[0] == B and params16.shape == (B, 16)
        np.testing.assert_array_equal(params16[:, :11], g.params)
        assert (tables[:3] != 0).any(axis=1).all() and not tables[3].any()
    finally:
        pool.unpin(tables)
    assert pool.stats()["pinned"] == 0
