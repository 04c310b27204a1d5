"""`sentinel2-rgb.pan-cold`: the archive kind (granules on one pixel
grid whose overlaps are identical, one timestamp), the generator (what
its check flags) and one rehearsal of the whole cell on the CPU."""

import io
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reference_rgb, spec             # noqa: E402
from benchmarks.archives import sentinel2_granules as s2     # noqa: E402
from benchmarks.plan import Result                      # noqa: E402

CELL = "sentinel2-rgb.pan-cold"
SEED = 2900000011


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, rehearsal=True)


@pytest.fixture(scope="module")
def gen(cell):
    kind = spec.load_kind("generators", cell.traffic["generator"])
    return kind.Generator(cell.traffic, cell.config, s2, SEED)


def test_published_shapes_are_uncut():
    config = spec.load_cell(CELL).config
    a = config["archive"]
    assert a["granule_hw"] == config["published"]["granule_hw"] \
        == [10980, 10980]
    assert a["res"] == 10.0 and a["pitch_m"] == 100000.0
    assert a["grid"] == [2, 2] and len(a["bands"]) == 3
    lay, = config["layers"]
    assert lay["resample"] == "bilinear" and len(lay["rgb_products"]) == 3
    assert set(config["reduced"]) == {"archive_extent", "wms_timeout"}


def test_overlapping_pixels_are_identical(cell):
    p = cell.config["archive"]
    H, W = p["granule_hw"]
    ov = W - int(round(p["pitch_m"] / p["res"]))
    assert 0 < ov < W // 4
    for b in range(3):
        nw, ne, sw = (s2.band(p, SEED, i, j, b)
                      for i, j in ((0, 0), (0, 1), (1, 0)))
        east, west = nw[:, -ov:], ne[:, :ov]
        both = (east != p["nodata"]) & (west != p["nodata"])
        assert both.mean() > 0.2
        assert (east[both] == west[both]).all()
        # each lacks a wedge of its own, and the other fills it
        assert ((east == p["nodata"]) | (west == p["nodata"])).any()
        assert not ((east == p["nodata"]) & (west == p["nodata"])).any()
        south, north = nw[-ov:], sw[:ov]
        both = (south != p["nodata"]) & (north != p["nodata"])
        assert both.mean() > 0.9 and (south[both] == north[both]).all()
    assert (s2.band(p, SEED, 0, 0, 0) != s2.band(p, SEED, 0, 0, 1)).any()
    assert (s2.band(p, SEED, 0, 0, 0)
            != s2.band(p, SEED + 1, 0, 0, 0)).any()


def test_one_timestamp_extent_and_dates(cell):
    p = cell.config["archive"]
    srcs = s2.sources(p, SEED)
    assert len(srcs) == 12
    assert len({s.timestamp for s in srcs}) == 1
    assert sorted({s.namespace for s in srcs}) == sorted(
        b["namespace"] for b in p["bands"])
    assert s2.dates(p) == ["2020-01-10T00:00:00.000Z"]
    crs, xmin, ymin, xmax, ymax = s2.extent(p)
    side = p["pitch_m"] + p["granule_hw"][0] * p["res"]
    assert crs == p["crs"] and (xmin, ymax) == tuple(p["origin"])
    assert xmax - xmin == ymax - ymin == side
    assert (srcs[0].read() == s2.band(p, SEED, 0, 0, 0)).all()


def test_same_seed_same_traffic_and_no_tile_twice(cell):
    kind = spec.load_kind("generators", cell.traffic["generator"])
    a, b = ([r.path for r in itertools.islice(
        kind.Generator(cell.traffic, cell.config, s2, SEED).window().reqs,
        300)] for _ in range(2))
    assert a == b and len(set(a)) == len(a)
    assert all("layers=truecolour" in p for p in a)


def test_prefill_touches_every_granule(gen):
    fill = gen.prefill()
    assert len(fill) == 4
    touched = [gen.granules_touched("truecolour", r.meta["time"],
                                    r.meta["bbox"]) for r in fill]
    assert touched == [1, 1, 1, 1]
    assert all(r.key[-1] == "twin" for r in fill)


def _png(rgba):
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(rgba, "RGBA").save(buf, "PNG")
    return buf.getvalue()


def _serve(gen, spoil):
    """A `fetch` that answers from the reference, spoiled by
    `spoil(channels, render, bbox)` -> rgba."""
    lay = gen.layers["truecolour"]

    def fetch(req):
        chans = gen._channels("truecolour", req.meta["time"])
        rgba = spoil(chans, lambda c: reference_rgb.render_rgba(
            c, req.meta["bbox"], "EPSG:3857", 256, 256, lay["resample"],
            lay["offset_value"], lay["scale_value"], lay["clip_value"]),
            req.meta["bbox"])
        body = _png(rgba)
        return Result(req, 0.0, 0.0, 200, True, 0, len(body), b"same", body)
    return fetch


def _window(gen, n=120):
    reqs = itertools.islice(gen.window().reqs, n)
    return [Result(r, 0.0, 0.0, 200, True, 0, 1000, b"same") for r in reqs]


def _without_the_granule_under(chans, render, bbox):
    """The tile as a mosaic renders it that has lost the first granule
    under the tile's centre (a centre off the data loses none)."""
    from benchmarks import reference
    cx, cy = reference.project(np.array([(bbox[0] + bbox[2]) / 2]),
                               np.array([(bbox[1] + bbox[3]) / 2]),
                               "EPSG:3857", chans[0][0].crs)
    under = [(s.x0, s.y0) for s in chans[0]
             if s.x0 <= cx[0] <= s.x0 + s.dx * s.shape[1]
             and s.y0 + s.dy * s.shape[0] <= cy[0] <= s.y0]
    return render([[s for s in c if (s.x0, s.y0) != under[0]]
                   for c in chans] if under else chans)


@pytest.mark.parametrize("fault,spoil,flagged", [
    ("none", lambda chans, render, bbox: render(chans), False),
    ("swapped_channel",
     lambda chans, render, bbox: render(chans)[..., [2, 1, 0, 3]], True),
    ("dropped_granule", _without_the_granule_under, True),
])
def test_verify_flags(gen, fault, spoil, flagged):
    results = _window(gen)
    problems, records = gen.verify(results, _serve(gen, spoil))
    assert len(records) == 8
    assert {r["granules"] > 1 for r in records} == {False, True}
    if not flagged:
        assert not problems and all(r["mismatch"] == 0 for r in records)
    elif fault == "swapped_channel":
        assert len(problems) == 8
    else:
        # a tile that one granule alone covers goes transparent, and
        # on an overlap the neighbour's wedge shows
        assert problems
        assert any(r["alpha_mismatch"] > 0 for r in records)


def _bf16(a):
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def test_a_bf16_tap_fails_the_bound(cell):
    """The bound's upper reading: the reference with its rasters held in
    bfloat16, the nearest precision below the float32 the configuration
    keeps on the device (8 to 16 DN steps at 1,000 to 3,000 DN against
    11.8 DN a byte), is far outside the bound on every checked tile."""
    import dataclasses
    # a generator of its own: the module's has drawn what the tests
    # before this one asked of it
    gen = spec.load_kind("generators", cell.traffic["generator"]).Generator(
        cell.traffic, cell.config, s2, SEED)
    lay = gen.layers["truecolour"]
    bound = cell.traffic["check"]["bound_mismatch"]
    coarse = [[dataclasses.replace(
        s, nodata=float(_bf16(s.nodata)),
        read=lambda s=s: _bf16(s.read())) for s in c]
        for c in gen._channels("truecolour", gen.dates[0])]
    shares = []
    for r in _window(gen, 40)[::10]:
        args = (r.req.meta["bbox"], "EPSG:3857", 256, 256, lay["resample"],
                lay["offset_value"], lay["scale_value"], lay["clip_value"])
        want = reference_rgb.render_rgba(
            gen._channels("truecolour", gen.dates[0]), *args)
        shares.append(reference_rgb.compare(
            reference_rgb.render_rgba(coarse, *args), want)["mismatch"])
    assert min(shares) > 10 * bound, shares


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
    assert m["scene_cache.upload_mb_per_tile"]["value"] == 0.0
    assert m["executor.rgb_packed_share"]["value"] == 100.0
    assert "render_rgba_ctrl_roofline" not in m     # no device trace here
    report = json.load(open(tmp_path / f"{CELL}.json"))
    legs = "".join(report["legs"])       # one granule, and several
    assert "render_rgba:((1," in legs
    assert "render_rgba:((2," in legs or "render_rgba:((4," in legs
    assert all(c["mismatch"] <= 0.005 for c in report["records"])
    assert report["checks"] == traced["checks"]
    assert traced["checks"]["mismatch_max"] == \
        max(c["mismatch"] for c in report["records"])
