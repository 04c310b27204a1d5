"""`sentinel2-seam.truecolour-seam-cold`: the archive kind's geometry
(the strip both zones cover, each granule in its zone's EPSG, the
wedges, one ground in both zones), the generator (its walk stays in the
strip, most of its tiles read both zones, what it refuses), the bound's
readings (a zone's sets warped on the other zone's grid, a zone
dropped, bfloat16 rasters), the two new readers on a recorded `/debug`
pair, and one rehearsal of the whole cell on the CPU."""

import dataclasses
import io
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

from benchmarks import reference, reference_rgb, roofline_multicrs  # noqa: E402
from benchmarks import roofline_multigrid, roofline_rgb, spec  # noqa: E402
from benchmarks.archives import sentinel2_zone_seam as seam  # noqa: E402
from benchmarks.ctx import Ctx                          # noqa: E402
from benchmarks.plan import Result                      # noqa: E402

CELL = "sentinel2-seam.truecolour-seam-cold"
SEED = 2900000045
NEW = ["executor.multicrs_set_share", "render_rgba_ctrl_multicrs_roofline"]


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, rehearsal=True)


def _generator(cell, seed=SEED):
    kind = spec.load_kind("generators", cell.traffic["generator"])
    return kind.Generator(cell.traffic, cell.config, seam, seed)


@pytest.fixture(scope="module")
def gen(cell):
    return _generator(cell)


# --- the configuration and the archive -----------------------------------------

def test_the_products_shapes_are_uncut():
    config = spec.load_cell(CELL).config
    a, pub = config["archive"], config["published"]
    assert (a["res"], a["granule_hw"]) == (pub["res_m"], pub["granule_hw"]) \
        == (10.0, [10980, 10980])
    assert a["granule_hw"][0] * a["res"] == pub["granule_m"] == 109800.0
    assert [z["crs"] for z in a["zones"]] == pub["crs"] == [
        "EPSG:32755", "EPSG:32756"]
    # MGRS columns G of zone 55 and K of zone 56, two rows 100 km apart
    assert [z["x0"] for z in a["zones"]] == [699960.0, 199980.0]
    assert a["rows_y0"] == [6300040.0, 6200020.0]
    assert [z["wedge_edge"] for z in a["zones"]] == ["east", "west"]
    assert [(b["namespace"], b["base"]) for b in a["bands"]] == [
        ("nbart_red", 1600), ("nbart_green", 1450), ("nbart_blue", 1300)]
    assert a["nodata"] == -999 and a["wedge_px"] == 700
    assert set(config["reduced"]) == {"archive_extent", "wms_timeout"}
    lay, = config["layers"]
    assert lay["rgb_products"] == ["nbart_red", "nbart_green", "nbart_blue"]
    assert (lay["resample"], lay["clip_value"]) == ("bilinear", 3000.0)


def test_the_strip_is_55_km_wide_at_full_size():
    """Zone 55's column G reaches east to 150.33-150.41 E, zone 56's
    column K west to 149.70-149.77 E: every tile between reads both."""
    p = spec.load_cell(CELL).config["archive"]
    w, s, e, n = seam.strip(p)
    assert 149.77 < w < 149.78 and 150.33 < e < 150.34
    assert 50.0 < (e - w) * seam.M_PER_DEG_LON / 1e3 < 56.0
    assert -35.29 < s < -35.28 and -33.43 < n < -33.42


def test_its_entries():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "seam-pan-cold"
    config, = [c for c in bench["configs"] if c["name"] == "sentinel2-seam"]
    assert config["reduced"] == ["archive_extent", "wms_timeout"]
    assert [c["source"] for c in bench["configs"]].count(
        config["source"]) == 1
    full = spec.load_cell(CELL)
    assert [m["name"] for m in full.end_to_end] == [
        "latency_p50_ms", "throughput_rps", "setup_s"]
    assert set(NEW) <= {m["name"] for m in full.per_layer}
    # every tile-path metric of the two-grid cell lists this cell, but
    # those that read other legs or counters
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        elif "sentinel2-swir.falsecolour-cold" in m.get("workloads", []):
            assert (CELL in m["workloads"]) == (m["name"] not in (
                "executor.multigrid_set_share",
                "render_rgba_ctrl_multigrid_roofline")), m["name"]


@pytest.fixture(scope="module")
def sources(cell):
    return seam.sources(cell.config["archive"], SEED)


def test_each_granule_lies_in_its_zone(cell, sources):
    p = cell.config["archive"]
    by_crs = {}
    for s in sources:
        by_crs.setdefault(s.crs, set()).add((s.x0, s.y0, s.shape))
    assert sorted(by_crs) == ["EPSG:32755", "EPSG:32756"]
    for z, zone in enumerate(p["zones"]):
        assert by_crs[zone["crs"]] == {
            (zone["x0"], y0, tuple(p["granule_hw"])) for y0 in p["rows_y0"]}


def test_the_wedges_face_the_other_zone(cell, sources):
    """Zone 55's granules lack a triangle along their east edge, zone
    56's along their west edge, each widening southwards; the other
    zone covers every pixel of each wedge that lies in the strip."""
    p = cell.config["archive"]
    H, W = p["granule_hw"]
    w, s_, e, n = seam.strip(p)
    for s in sources[::3]:
        d = s.read()
        gone = d == p["nodata"]
        east = s.crs == "EPSG:32755"
        edge = gone[:, -p["wedge_px"]:] if east else gone[:, :p["wedge_px"]]
        assert gone.sum() == edge.sum() > 0
        assert gone[-1].sum() > gone[0].sum()
        rows, cols = np.nonzero(gone)
        x = s.x0 + (cols + 0.5) * s.dx
        y = s.y0 + (rows + 0.5) * s.dy
        lon, lat = reference.project(x, y, s.crs, "EPSG:4326")
        keep = (lon >= w) & (lon <= e) & (lat >= s_) & (lat <= n)
        assert keep.any()
        x, y = x[keep], y[keep]
        other = [o for o in sources[::3] if o.crs != s.crs]
        ox, oy = reference.project(x, y, s.crs, other[0].crs)
        covered = np.zeros(len(x), bool)
        for o in other:
            col = (ox - o.x0) / o.dx
            row = (oy - o.y0) / o.dy
            covered |= (col >= 1) & (col <= W - 1) & (row >= 1) \
                & (row <= H - 1)
        assert covered.all()


def test_both_zones_show_one_ground(cell, sources):
    """At the same longitude and latitude in the strip the two zones'
    rasters differ by their noise (2 DN each) and the field's
    interpolation: a few DN, where warping with the other zone's grid
    reads kilometres off."""
    w, s_, e, n = seam.strip(cell.config["archive"])
    rng = np.random.default_rng(1)
    lon = rng.uniform(w, e, 400)
    lat = rng.uniform(s_, n, 400)
    got = {}
    for src in sources:
        if src.namespace != "nbart_red":
            continue
        x, y = reference.project(lon, lat, "EPSG:4326", src.crs)
        col = np.floor((x - src.x0) / src.dx).astype(int)
        row = np.floor((y - src.y0) / src.dy).astype(int)
        inside = (col >= 0) & (col < src.shape[1]) & (row >= 0) \
            & (row < src.shape[0])
        v = np.full(len(lon), np.nan)
        d = src.read()
        v[inside] = d[row[inside], col[inside]]
        v[v == src.nodata] = np.nan
        got[src.crs] = np.where(np.isnan(got.get(src.crs, v)), v,
                                got.get(src.crs, v))
    a, b = got["EPSG:32755"], got["EPSG:32756"]
    both = ~np.isnan(a) & ~np.isnan(b)
    assert both.mean() > 0.8
    # a 10 m pixel apart and the field's 15 km period: ~4 DN of slope
    assert np.abs(a[both] - b[both]).max() <= 12
    assert np.abs(a[both] - b[both]).mean() <= 3


# --- the generator -------------------------------------------------------------

def _window(gen, n=240):
    reqs = itertools.islice(gen.window().reqs, n)
    return [Result(r, 0.0, 0.0, 200, True, 0, 1000, b"same") for r in reqs]


@pytest.mark.parametrize("rehearsal", [True, False])
def test_its_walk_stays_in_the_strip(rehearsal):
    """Every view's centre lies in the strip's tiles of its level (a
    viewport may reach a tile or two past it), no tile twice, and most
    tiles read granules of both zones."""
    full = spec.load_cell(CELL, rehearsal=rehearsal)
    gen = _generator(full, 2147483659)
    reqs = list(itertools.islice(gen.window().reqs, 400))
    assert len({r.key for r in reqs}) == 400
    for r in reqs:
        z, x, y = r.meta["z"], r.key[2], r.key[3]
        x0, y0, x1, y1 = gen.ranges[z]
        assert x0 - 3 <= x <= x1 + 3 and y0 - 2 <= y <= y1 + 2, (z, x, y)
    if rehearsal:
        both = [gen.zones_touched(r.meta["layer"], r.meta["time"],
                                  r.meta["bbox"]) > 1 for r in reqs[:200]]
        assert np.mean(both) > 0.7


def test_its_walk_is_the_rgb_cells():
    rgb = spec.load_cell("sentinel2-rgb.pan-cold").traffic
    mine = spec.load_cell(CELL).traffic
    for key in ("loop", "zoom_shares", "viewport", "views", "step",
                "pan_tiles"):
        assert mine[key] == rgb[key], key
    assert mine["layers"] == {"truecolour": 1.0}
    assert mine["check"] == {"tiles": 8, "bound_mismatch": 0.005}
    assert mine["demand_still"] == ["cache.scene.misses",
                                    "rgb_routes.fallback",
                                    "band_crs.multi_crs_declined"]


def test_a_program_without_the_path_is_refused_at_once(cell, monkeypatch):
    """The parent forms no band set in several CRSs: every tile of the
    cell would take the modular route and stack each zone's rasters.
    The generator refuses it before the server starts."""
    import importlib
    executor = importlib.import_module("gsky_tpu.pipeline.executor")
    monkeypatch.delattr(executor, "_set_crs")
    with pytest.raises(SystemExit) as refused:
        _generator(cell)
    assert "_set_crs" in str(refused.value.code)


def test_prefill_touches_every_granule(gen):
    """One tile in the middle of each granule, sent as a twin; at
    rehearsal size the zones overlap so far that each also touches the
    other zone's granule."""
    fill = gen.prefill()
    assert len(fill) == 4 and all(r.key[-1] == "twin" for r in fill)
    srcs = gen._channels("truecolour", gen.dates[0])[0]
    for s in srcs:
        assert sum(gen._overlaps(s, r.meta["bbox"]) for r in fill) >= 1


# --- what the check flags -------------------------------------------------------

def _png_rgba(rgba):
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(rgba, "RGBA").save(buf, "PNG")
    return buf.getvalue()


def _render(gen, req, spoil=lambda srcs: srcs):
    lay = gen.layers[req.meta["layer"]]
    return reference_rgb.render_rgba(
        [spoil(c) for c in gen._channels(lay["name"], req.meta["time"])],
        req.meta["bbox"], "EPSG:3857", 256, 256, lay["resample"],
        lay["offset_value"], lay["scale_value"], lay["clip_value"])


def _serve(gen, spoil):
    def fetch(req):
        body = _png_rgba(_render(gen, req, spoil))
        return Result(req, 0.0, 0.0, 200, True, 0, len(body), b"same", body)
    return fetch


def _on_the_other_grid(srcs):
    """Zone 56's granules warped on zone 55's control grid, as the
    program would place them: each set's rows are taken on its own
    zone's origin (the first granule's corner) and read against the
    other zone's coordinates, so a zone 56 granule lands where the zone
    55 granule of its row lies, ~2 km (rehearsal) to ~50 km off and
    turned by the zones' 3.4 degrees of grid convergence."""
    x55 = min(s.x0 for s in srcs if s.crs == "EPSG:32755")
    return [dataclasses.replace(s, crs="EPSG:32755", x0=x55)
            if s.crs == "EPSG:32756" else s for s in srcs]


def _dropped(srcs):
    return [s for s in srcs if s.crs != "EPSG:32756"]


def _bf16(a):
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _in_bf16(srcs):
    return [dataclasses.replace(s, nodata=float(_bf16(s.nodata)),
                                read=lambda s=s: _bf16(s.read()))
            for s in srcs]


@pytest.mark.parametrize("fault,spoil", [
    ("none", lambda srcs: srcs),
    ("on_the_other_grid", _on_the_other_grid),
    ("in_bf16", _in_bf16),
])
def test_verify_holds_the_tiles(cell, fault, spoil):
    gen = _generator(cell)
    results = _window(gen)
    problems, records = gen.verify(results, _serve(gen, spoil))
    assert len(records) == 8
    assert sum(r["zones"] > 1 for r in records) >= 6
    if fault == "none":
        assert not problems and all(r["mismatch"] == 0 for r in records)
    else:
        assert len(problems) == 8, problems


def test_a_sample_with_too_few_seam_tiles_is_flagged(cell):
    gen = _generator(cell)
    one = [r for r in _window(gen) if not gen._both(r)]
    problems, _ = gen.verify(one, _serve(gen, lambda srcs: srcs))
    assert any("span both zones" in p for p in problems), problems


def test_the_bound_lies_between_the_readings(cell):
    """The upper readings at rehearsal size, on tiles of the window
    over both zones: a zone's sets on the other zone's grid, the zone
    dropped (a tile across its neighbour's wedge), and the rasters in
    bfloat16, the precision below the float32 the configuration keeps
    on the device, each at least ten times the bound on every tile;
    the lower one, the program's, is the rehearsal's and the chip's
    (`test_rehearsal_runs_the_cell`, PERF.md)."""
    gen = _generator(cell)
    bound = cell.traffic["check"]["bound_mismatch"]
    reqs = [r.req for r in _window(gen, 120) if gen._both(r)][::10]
    assert len(reqs) >= 6
    for req in reqs:
        want = _render(gen, req)
        for spoil in (_on_the_other_grid, _in_bf16):
            share = reference_rgb.compare(_render(gen, req, spoil),
                                          want)["mismatch"]
            assert share > 10 * bound, (req.key, spoil.__name__, share)
    # a zone dropped shows where only it holds data: across the other
    # zone's wedge
    p = cell.config["archive"]
    x = p["zones"][0]["x0"] + p["granule_hw"][1] * p["res"] \
        - p["wedge_px"] * p["res"] * 0.6
    y = p["rows_y0"][-1] - p["granule_hw"][0] * p["res"] * 0.9
    mx, my = reference.project(np.array([x - 150.0, x + 150.0]),
                               np.array([y - 150.0, y + 150.0]),
                               "EPSG:32755", "EPSG:3857")
    req = gen._req("truecolour", 17, 0, 0, gen.dates[0],
                   bbox=(mx[0], my[0], mx[1], my[1]))
    share = reference_rgb.compare(_render(gen, req, _dropped),
                                  _render(gen, req))["mismatch"]
    assert share > 10 * bound, share


# --- the readers ------------------------------------------------------------------

def _ctx(debug0, debug1, module=None):
    c = Ctx(cell=SimpleNamespace(config={"layers": []}), results=[], t0=0.0,
            window_s=20.0, setup_s=1.0, warmup=[], warmed=None,
            debug0=debug0, debug1=debug1, compiles_in_window=(0, 0),
            device_kind="TPU v5 lite", hbm_peak_bytes=None)
    if module is not None:
        c.module = lambda name: module.get(name)
    return c


def _debug(one, multi, legs=None):
    doc = {"executor": {"dispatches": legs or {}}}
    if one is not None:
        doc["band_crs"] = {"sets_one_crs": one, "sets_multi_crs": multi,
                           "multi_crs_declined": 0}
    return doc


@pytest.mark.parametrize("debug0, debug1, want", [
    (_debug(0, 300), _debug(0, 2300), 100.0),
    (_debug(10, 10), _debug(40, 100), 75.0),
    (_debug(7, 7), _debug(7, 7), None),             # no set in the window
    (_debug(None, None), _debug(None, None), None),  # the parent: no key
])
def test_multicrs_share_reads_the_windows_sets(debug0, debug1, want):
    got = spec.reader("layer_metrics", NEW[0]).read(_ctx(debug0, debug1))
    assert got == want


def test_roofline_reader_on_a_recorded_pair():
    mc2 = "render_rgba_mc:((2, 2, 3), (512, 512))"
    mc4 = "render_rgba_mc:((4, 2, 3), (512, 512))"
    one = "render_rgba:((1, 11008, 11008, 3), (512, 512))"
    legs0 = {mc2: 100, one: 10}
    legs1 = {mc2: 1100, mc4: 80, one: 130, "render_byte:((1, 2), None)": 4}
    ctx = _ctx(_debug(0, 0, legs0), _debug(0, 0, legs1),
               {"render_rgba_ctrl": (3.0, 1200)})
    share = spec.reader("layer_metrics", NEW[1]).read(ctx)
    bw = 819e9
    least = (1000 * roofline_multicrs.render_rgba_ctrl(2, 2)[1]
             + 80 * roofline_multicrs.render_rgba_ctrl(4, 2)[1]
             + 120 * roofline_rgb.render_rgba_ctrl(1)[1]) / bw / 1200
    assert share == pytest.approx(100 * least / 2.5e-3, rel=1e-6)
    assert 0.0 < share < 1.0
    # a program with no such dispatch (the parent): nothing, no error
    bare = _ctx(_debug(None, None, {}), _debug(None, None, {one: 9}),
                {"render_rgba_ctrl": (1.0, 9)})
    assert spec.reader("layer_metrics", NEW[1]).read(bare) is None


def test_roofline_counts():
    assert roofline_multicrs.leg_shape(
        "render_rgba_mc:((4, 2, 3), (256, 256))") == (4, 2, 1, 3)
    assert roofline_multicrs.leg_shape(
        "render_rgba_mc:((2, 2, 2, 3), ((512, 512), (384, 384)))") \
        == (2, 2, 2, 3)
    assert roofline_multicrs.leg_shape(
        "render_rgba_mg:((2, 2, 3), None)") is None
    o1, b1 = roofline_multicrs.render_rgba_ctrl(2, 1)
    o2, b2 = roofline_multicrs.render_rgba_ctrl(2, 2)
    mo, mb = roofline_multigrid.render_rgba_ctrl(2, 1, 3)
    grid = 2 * 17 * 17 * 4
    assert (o1, b1 - 8) == (mo, mb)
    assert b2 - b1 == grid and o2 - o1 == 16 * 256 * 256
    assert b2 / 819e9 > o2 / 197e12        # memory-bound


# --- the cell ------------------------------------------------------------------------

def test_rehearsal_runs_the_cell(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "4",
         "--trace", "1", "--rehearsal", "--out", str(tmp_path)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    m = line["metrics"]
    assert m["executor.multicrs_set_share"]["value"] > 70.0
    assert m["executor.rgb_packed_share"]["value"] == 100.0
    assert m["scene_cache.upload_mb_per_tile"]["value"] == 0.0
    assert NEW[1] not in m                  # no device trace here
    assert line["checks"]["answers_checked"] == 8
    report = json.load(open(tmp_path / f"{CELL}.json"))
    assert any(leg.startswith("render_rgba_mc:((") for leg in report["legs"])
    assert all(c["mismatch"] <= 0.005 for c in report["records"])
    assert sum(c["zones"] > 1 for c in report["records"]) >= 6
