"""`sentinel2-swir.falsecolour-cold`: the configuration keeps the
product's shapes, the generator (what a seed draws, what it prefills,
what it refuses, what its check flags), the bound's upper readings (the
reference with bfloat16 rasters; the 20 m band read as if it lay on the
10 m grid), the two new readers on a recorded `/debug` pair, and one
rehearsal of the whole cell on the CPU."""

import dataclasses
import hashlib
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

from benchmarks import (reference_expr, reference_rgb,  # noqa: E402
                        roofline_multigrid, spec)
from benchmarks.archives import sentinel2_bands_by_res as s2r  # noqa: E402
from benchmarks.ctx import Ctx                          # noqa: E402
from benchmarks.plan import Result                      # noqa: E402

CELL = "sentinel2-swir.falsecolour-cold"
SEED = 2900000043
NEW = ["executor.multigrid_set_share",
       "render_rgba_ctrl_multigrid_roofline"]


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, rehearsal=True)


def _generator(cell, seed=SEED):
    kind = spec.load_kind("generators", cell.traffic["generator"])
    return kind.Generator(cell.traffic, cell.config, s2r, seed)


@pytest.fixture(scope="module")
def gen(cell):
    return _generator(cell)


# --- the configuration -------------------------------------------------------

def test_the_products_shapes_are_uncut():
    config = spec.load_cell(CELL).config
    a, pub = config["archive"], config["published"]
    res = a["resolutions"]
    assert (res["r10m"]["res"], res["r10m"]["granule_hw"]) == \
        (10.0, pub["granule_hw_10m"]) == (10.0, [10980, 10980])
    assert (res["r20m"]["res"], res["r20m"]["granule_hw"]) == \
        (20.0, pub["granule_hw_20m"]) == (20.0, [5490, 5490])
    # one footprint at either resolution, the wedge one triangle
    assert 10980 * 10.0 == 5490 * 20.0 == 109800.0
    assert res["r10m"]["wedge_px"] * 10.0 == res["r20m"]["wedge_px"] * 20.0
    assert a["pitch_m"] == 100000.0 and a["grid"] == [2, 2]
    assert a["nodata"] == pub["nodata"] == -999
    assert [(b["namespace"], b["resolution"]) for b in a["bands"]] == [
        ("nbart_green", "r10m"), ("nbart_nir_1", "r10m"),
        ("nbart_swir_2", "r20m"), ("nbart_swir_3", "r20m")]
    assert set(config["reduced"]) == {"archive_extent", "wms_timeout"}
    fc, nbr = config["layers"]
    assert fc["rgb_products"] == ["nbart_swir_2", "nbart_nir_1",
                                  "nbart_green"]
    text = reference_expr.split_product(nbr["rgb_products"][0])[1]
    assert reference_expr.variables(reference_expr.parse(text)) == \
        ["nbart_nir_1", "nbart_swir_3"]
    assert (nbr["offset_value"], nbr["clip_value"], nbr["scale_value"]) \
        == (1.0, 2.0, 127.0)            # NBR -1..1 spans 0..254
    for lay in (fc, nbr):
        assert lay["resample"] == "bilinear"


def test_its_entries():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "swir-pan-cold"
    config, = [c for c in bench["configs"] if c["name"] == "sentinel2-swir"]
    assert config["reduced"] == ["archive_extent", "wms_timeout"]
    full = spec.load_cell(CELL)
    assert [m["name"] for m in full.end_to_end] == [
        "latency_p50_ms", "throughput_rps", "setup_s"]
    reported = {m["name"] for m in full.per_layer}
    assert set(NEW) <= reported
    # every metric that lists the one-grid control lists this cell, but
    # the one-grid roofline, which reads no multi-grid dispatch
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        elif "sentinel2-rgb.pan-cold" in m.get("workloads", []):
            assert (CELL in m["workloads"]) == \
                (m["name"] != "render_rgba_ctrl_roofline"), m["name"]


# --- the generator -------------------------------------------------------------

# sha256 over path, NUL, body, newline of a seed's first 500 requests at
# the size the chip runs, as this cell first drew them
DRAWN = "0f5b57e0796b19cb8a49ad687bfadc7b1031db4f26ad1d9caff0bd63add1b366"


def test_the_same_seed_draws_the_same_requests():
    full = spec.load_cell(CELL)
    h = hashlib.sha256()
    paths = []
    for r in itertools.islice(_generator(full, 2147483659).window().reqs,
                              500):
        h.update(r.path.encode() + b"\0" + (r.body or b"") + b"\n")
        paths.append(r.path)
    assert len(set(paths)) == 500           # no tile twice
    assert all("layers=falsecolour" in p for p in paths)
    assert h.hexdigest() == DRAWN


def test_its_walk_is_the_rgb_cells():
    rgb = spec.load_cell("sentinel2-rgb.pan-cold").traffic
    mine = spec.load_cell(CELL).traffic
    for key in ("loop", "zoom_shares", "viewport", "views", "step",
                "pan_tiles"):
        assert mine[key] == rgb[key], key
    assert mine["layers"] == {"falsecolour": 1.0}
    assert mine["check"] == {"tiles": 8, "nbr_tiles": 4,
                             "bound_mismatch": 0.005}
    assert mine["demand_still"] == ["cache.scene.misses",
                                    "rgb_routes.fallback",
                                    "band_grids.multi_grid_declined"]


def test_a_program_without_the_path_is_refused_at_once(cell, monkeypatch):
    """The parent's program has no band set over several grids: every
    tile of the cell would take the modular route.  The generator
    refuses it before the server starts, and the run ends with an exit
    code."""
    import importlib
    executor = importlib.import_module("gsky_tpu.pipeline.executor")
    monkeypatch.delattr(executor, "_grid_sets")
    with pytest.raises(SystemExit) as refused:
        _generator(cell)
    assert "_grid_sets" in str(refused.value.code)


def test_prefill_touches_every_granule_for_every_layer(gen):
    fill = gen.prefill()
    assert len(fill) == 8
    assert sorted({r.meta["layer"] for r in fill}) == ["falsecolour", "nbr"]
    assert [gen.granules_touched(r.meta["layer"], r.meta["time"],
                                 r.meta["bbox"]) for r in fill] == [1] * 8
    assert all(r.key[-1] == "twin" for r in fill)


def test_the_sources_carry_each_bands_grid(cell):
    srcs = s2r.sources(cell.config["archive"], SEED)
    by_ns = {}
    for s in srcs:
        by_ns.setdefault(s.namespace, set()).add((s.dx, s.shape))
    assert by_ns == {"nbart_green": {(10.0, (700, 700))},
                     "nbart_nir_1": {(10.0, (700, 700))},
                     "nbart_swir_2": {(20.0, (350, 350))},
                     "nbart_swir_3": {(20.0, (350, 350))}}
    # overlapping pixels are identical in both granules of a row where
    # both hold data (each lacks its wedge there)
    a, b = [s for s in srcs if s.namespace == "nbart_swir_2"][:2]
    shift = int(round((b.x0 - a.x0) / a.dx))
    left, right = a.read()[:, shift:], b.read()[:, :a.shape[1] - shift]
    both = (left != -999) & (right != -999)
    assert left.shape == (350, 31) and both.mean() > 0.2
    np.testing.assert_array_equal(left[both], right[both])


# --- what the check flags --------------------------------------------------------

def _png_rgba(rgba):
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(rgba, "RGBA").save(buf, "PNG")
    return buf.getvalue()


def _png_p(indices, colours):
    from PIL import Image
    img = Image.fromarray(indices, "P")
    ramp = reference_expr.palette(colours)
    img.putpalette(ramp[:, :3].tobytes())
    buf = io.BytesIO()
    img.save(buf, "PNG", transparency=bytes(ramp[:, 3]))
    return buf.getvalue()


def _render(gen, req, spoil=lambda srcs: srcs):
    """The reference's tile of ``req``, from sources ``spoil`` may
    change."""
    lay = gen.layers[req.meta["layer"]]
    args = (req.meta["bbox"], "EPSG:3857", 256, 256, lay["resample"],
            lay["offset_value"], lay["scale_value"], lay["clip_value"])
    if gen._is_expression(lay["name"]):
        per_var = gen._per_var(lay["name"], req.meta["time"])
        return reference_expr.render_byte(
            gen._expression(lay["name"]),
            {k: spoil(v) for k, v in per_var.items()}, *args)
    return reference_rgb.render_rgba(
        [spoil(c) for c in gen._channels(lay["name"], req.meta["time"])],
        *args)


def _serve(gen, spoil):
    def fetch(req):
        lay = gen.layers[req.meta["layer"]]
        got = _render(gen, req, spoil)
        body = _png_p(got, lay["palette"]["colours"]) \
            if gen._is_expression(lay["name"]) else _png_rgba(got)
        return Result(req, 0.0, 0.0, 200, True, 0, len(body), b"same", body)
    return fetch


def _window(gen, n=160):
    reqs = itertools.islice(gen.window().reqs, n)
    return [Result(r, 0.0, 0.0, 200, True, 0, 1000, b"same") for r in reqs]


def _on_the_fine_grid(srcs):
    """The 20 m band read as if it lay on the 10 m grid."""
    return [dataclasses.replace(s, dx=10.0, dy=-10.0) if s.dx == 20.0
            else s for s in srcs]


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
    ("on_the_fine_grid", _on_the_fine_grid),
    ("in_bf16", _in_bf16),
])
def test_verify_holds_both_layers(cell, fault, spoil):
    gen = _generator(cell)
    results = _window(gen)
    problems, records = gen.verify(results, _serve(gen, spoil))
    assert len(records) == 12
    assert [r["layer"] for r in records] == ["falsecolour"] * 8 + ["nbr"] * 4
    for layer, n in (("falsecolour", 4), ("nbr", 2)):
        assert sum(r["granules"] > 1 for r in records
                   if r["layer"] == layer) == n
    if fault == "none":
        assert not problems and all(r["mismatch"] == 0 for r in records)
    else:
        # every tile, of both layers, is outside the bound
        assert len(problems) == 12, problems


# --- the bound's upper readings ---------------------------------------------------

def test_a_lower_precision_or_a_wrong_grid_fails_by_far(cell):
    """The two readings the bound lies between, at its upper end: the
    reference with its rasters held in bfloat16, the nearest precision
    below the float32 the configuration keeps on the device, and with
    the 20 m band read on the 10 m grid: each at least ten times the
    bound on every checked tile of both layers."""
    gen = _generator(cell)
    bound = cell.traffic["check"]["bound_mismatch"]
    reqs = [r.req for r in _window(gen, 60)[::6]]
    layer = next(n for n in gen.layers if gen._is_expression(n))
    reqs += [gen._req(layer, r.meta["z"], r.key[2], r.key[3],
                      r.meta["time"]) for r in reqs[:4]]
    for req in reqs:
        want = _render(gen, req)
        compare = reference_expr.compare if want.ndim == 2 \
            else reference_rgb.compare
        for spoil in (_in_bf16, _on_the_fine_grid):
            share = compare(_render(gen, req, spoil), want)["mismatch"]
            assert share > 10 * bound, (req.key, spoil.__name__, share)


# --- the readers ----------------------------------------------------------------------

def _ctx(debug0, debug1, module=None):
    c = Ctx(cell=SimpleNamespace(config={"layers": []}), results=[], t0=0.0,
            window_s=20.0, setup_s=1.0, warmup=[], warmed=None,
            debug0=debug0, debug1=debug1, compiles_in_window=(0, 0),
            device_kind="TPU v5 lite", hbm_peak_bytes=None)
    if module is not None:
        c.module = lambda name: module.get(name)
    return c


def _debug(one, multi, legs=None):
    grids = None if one is None else {
        "sets_one_grid": one, "sets_multi_grid": multi,
        "multi_grid_declined": 0}
    doc = {"executor": {"dispatches": legs or {}}}
    if grids is not None:
        doc["band_grids"] = grids
    return doc


@pytest.mark.parametrize("debug0, debug1, want", [
    (_debug(0, 300), _debug(0, 2300), 100.0),
    (_debug(10, 10), _debug(40, 40), 50.0),
    (_debug(7, 7), _debug(7, 7), None),             # no set in the window
    (_debug(None, None), _debug(None, None), None),  # the parent: no key
])
def test_multigrid_share_reads_the_windows_sets(debug0, debug1, want):
    got = spec.reader("layer_metrics", NEW[0]).read(_ctx(debug0, debug1))
    assert got == want


def test_roofline_reader_on_a_recorded_pair():
    legs0 = {"render_rgba_mg:((1, 2, 3), ((512, 512), (384, 384)))": 100}
    legs1 = {"render_rgba_mg:((1, 2, 3), ((512, 512), (384, 384)))": 1100,
             "render_rgba_mg:((2, 2, 3), ((512, 512), (384, 384)))": 50,
             "render_rgba:((1, 11008, 11008, 3), (512, 512))": 7}
    ctx = _ctx(_debug(0, 0, legs0), _debug(0, 0, legs1),
               {"render_rgba_ctrl": (2.0, 1000)})
    share = spec.reader("layer_metrics", NEW[1]).read(ctx)
    one = roofline_multigrid.render_rgba_ctrl(1, 2, 3)[1] / 819e9
    two = roofline_multigrid.render_rgba_ctrl(2, 2, 3)[1] / 819e9
    assert share == pytest.approx(
        100 * (1000 * one + 50 * two) / 1050 / 2e-3, rel=1e-6)
    assert 0.0 < share < 1.0
    # a program with no such dispatch (the parent): nothing, no error
    bare = _ctx(_debug(None, None, {}),
                _debug(None, None, {"render_rgba:((1, 768, 768, 3), None)": 9}),
                {"render_rgba_ctrl": (1.0, 9)})
    assert spec.reader("layer_metrics", NEW[1]).read(bare) is None


def test_roofline_counts():
    assert roofline_multigrid.leg_shape(
        "render_rgba_mg:((4, 2, 3), ((256, 256), (192, 192)))") == (4, 2, 3)
    assert roofline_multigrid.leg_shape(
        "render_rgba_mg:((2, 2, 3), None)") == (2, 2, 3)
    assert roofline_multigrid.leg_shape(
        "render_rgba:((1, 768, 768, 3), None)") is None
    o1, b1 = roofline_multigrid.render_rgba_ctrl(1, 2, 3)
    o4, b4 = roofline_multigrid.render_rgba_ctrl(4, 2, 3)
    px = 256 * 256
    assert b1 == px * 3 * 4 * 4 + 2 * 17 * 17 * 4 + (22 + 3) * 4 + px * 4
    assert b4 - b1 == 3 * (px * 3 * 4 * 4 + (22 + 3) * 4) and o4 > o1
    assert b1 / 819e9 > o1 / 197e12        # memory-bound


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
    assert m["executor.multigrid_set_share"]["value"] == 100.0
    assert m["executor.rgb_packed_share"]["value"] == 100.0
    assert m["scene_cache.upload_mb_per_tile"]["value"] == 0.0
    assert NEW[1] not in m                  # no device trace here
    assert line["checks"]["answers_checked"] == 12
    report = json.load(open(tmp_path / f"{CELL}.json"))
    assert all(leg.startswith("render_rgba_mg:((")
               for leg in report["legs"])
    assert all(c["mismatch"] <= 0.005 for c in report["records"])
