"""`sentinel2-algebra.ndvi-cold`: the configuration keeps the published
shapes, the generator (what a seed draws, what it prefills, what its
check flags), the reference's own evaluator against values computed by
hand, the bound's upper reading (bfloat16 rasters, a bfloat16 quotient),
the three new readers on a recorded `/debug` pair, and one rehearsal of
the whole cell on the CPU."""

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

from benchmarks import reference, reference_expr, roofline_expr, spec  # noqa: E402
from benchmarks.archives import sentinel2_granules as s2     # noqa: E402
from benchmarks.ctx import Ctx                          # noqa: E402
from benchmarks.plan import Result                      # noqa: E402

CELL = "sentinel2-algebra.ndvi-cold"
SEED = 2900000011
NEW = ["kernels.expr_render_ms_per_tile", "render_expr_ctrl_roofline",
       "executor.expr_fused_share"]


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, rehearsal=True)


def _generator(cell, seed=SEED):
    kind = spec.load_kind("generators", cell.traffic["generator"])
    return kind.Generator(cell.traffic, cell.config, s2, seed)


@pytest.fixture(scope="module")
def gen(cell):
    return _generator(cell)


# --- the configuration -------------------------------------------------------

def test_published_shapes_are_uncut():
    config = spec.load_cell(CELL).config
    a = config["archive"]
    assert a["granule_hw"] == config["published"]["granule_hw"] \
        == [10980, 10980]
    assert a["res"] == 10.0 and a["pitch_m"] == 100000.0
    assert a["nodata"] == config["published"]["nodata"] == -999
    assert a["grid"] == [2, 2] and a["kind"] == "sentinel2_granules"
    assert [(b["namespace"], b["base"]) for b in a["bands"]] == [
        ("nbart_nir_1", 3200), ("nbart_red", 1300), ("nbart_blue", 900)]
    assert set(config["reduced"]) == {"archive_extent", "wms_timeout"}
    ndvi, evi = config["layers"]
    for lay in (ndvi, evi):
        assert lay["resample"] == "bilinear" and len(lay["rgb_products"]) == 1
        assert (lay["offset_value"], lay["clip_value"],
                lay["scale_value"]) == (0.0, 1.0, 254.0)
        assert len(lay["palette"]["colours"]) == 5
    text = reference_expr.split_product(ndvi["rgb_products"][0])[1]
    assert reference_expr.variables(reference_expr.parse(text)) == \
        ["nbart_nir_1", "nbart_red"]
    text = reference_expr.split_product(evi["rgb_products"][0])[1]
    assert reference_expr.variables(reference_expr.parse(text)) == \
        ["nbart_nir_1", "nbart_red", "nbart_blue"]


def test_its_entries():
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and entry["traffic"] == "ndvi-pan-cold"
    config, = [c for c in bench["configs"]
               if c["name"] == "sentinel2-algebra"]
    assert config["reduced"] == ["archive_extent", "wms_timeout"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    full = spec.load_cell(CELL)
    assert [m["name"] for m in full.end_to_end] == [
        "latency_p50_ms", "throughput_rps", "setup_s"]
    reported = [m["name"] for m in full.per_layer]
    assert set(NEW) <= set(reported)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]


# --- the generator -------------------------------------------------------------

# sha256 over path, NUL, body, newline of a seed's first 500 requests at
# the size the chip runs, as PR 35 drew them
DRAWN = "a2e7bf0d5b5a0efd9440ac677c62cabbf872e5ac51b2324f40ed39ae9587544a"


def test_the_same_seed_draws_the_same_requests():
    full = spec.load_cell(CELL)
    h = hashlib.sha256()
    paths = []
    for r in itertools.islice(_generator(full, 2147483659).window().reqs,
                              500):
        h.update(r.path.encode() + b"\0" + (r.body or b"") + b"\n")
        paths.append(r.path)
    assert len(set(paths)) == 500           # no tile twice
    share = sum("layers=ndvi" in p for p in paths) / 500
    assert 0.5 < share < 0.9                # 70 % of sessions open NDVI
    assert h.hexdigest() == DRAWN


def test_its_walk_is_the_rgb_cells(cell):
    rgb = spec.load_cell("sentinel2-rgb.pan-cold").traffic
    mine = spec.load_cell(CELL).traffic
    for key in ("loop", "zoom_shares", "viewport", "views", "step",
                "pan_tiles"):
        assert mine[key] == rgb[key], key
    assert mine["layers"] == {"ndvi": 0.7, "evi": 0.3}
    assert mine["check"] == {"tiles": 8, "bound_mismatch": 0.005}
    assert mine["demand_still"] == ["cache.scene.misses",
                                    "expr.paths.unfused"]


def test_a_program_without_the_kernel_is_refused_at_once(cell, monkeypatch):
    """The parent of PR 35 ran out of the device's memory and then of
    the host's under this cell; the generator refuses such a program
    before the server starts, and the run ends with an exit code."""
    import importlib
    warp = importlib.import_module("gsky_tpu.ops.warp")
    monkeypatch.delattr(warp, "render_expr_ctrl")
    with pytest.raises(SystemExit) as refused:
        _generator(cell)
    assert "render_expr_ctrl" in str(refused.value.code)


def test_prefill_touches_every_granule_for_every_layer(gen):
    fill = gen.prefill()
    assert len(fill) == 8
    assert sorted({r.meta["layer"] for r in fill}) == ["evi", "ndvi"]
    assert [gen.granules_touched(r.meta["layer"], r.meta["time"],
                                 r.meta["bbox"]) for r in fill] == [1] * 8
    assert all(r.key[-1] == "twin" for r in fill)


# --- the reference's evaluator --------------------------------------------------

@pytest.mark.parametrize("text, env, want", [
    ("(a - b) / (a + b)", {"a": 3200.0, "b": 1300.0}, 1900.0 / 4500.0),
    ("2.5 * (a - b) / (a + 6 * b - 7.5 * c + 10000)",
     {"a": 3200.0, "b": 1300.0, "c": 900.0}, 2.5 * 1900.0 / 14250.0),
    ("1 + 2 * 3 - 4 / 8", {}, 6.5),
    ("-(a - 2) * -3", {"a": 5.0}, 9.0),
    ("- - a", {"a": 2.0}, 2.0),
    ("2 - 3 - 4", {}, -5.0),                # left to right
    ("8 / 4 / 2", {}, 1.0),
    ("a > b ? a - b : b - a", {"a": 1.0, "b": 4.0}, 3.0),
    ("a >= 1 ? 10 : a < 0 ? 20 : 30", {"a": 0.5}, 30.0),
    ("(a == 2) + (a != 2) + (a <= 2)", {"a": 2.0}, 2.0),
    ("1.5e3 + .5", {}, 1500.5),
])
def test_evaluator_against_hand_computed_values(text, env, want):
    got = reference_expr.evaluate(reference_expr.parse(text), env)
    assert float(got) == pytest.approx(want, rel=1e-15)


def test_evaluator_on_arrays_and_zero_denominators():
    node = reference_expr.parse("(nir - red) / (nir + red)")
    assert reference_expr.variables(node) == ["nir", "red"]
    nir = np.array([3.0, 0.0, 5.0])
    red = np.array([1.0, 0.0, -5.0])
    got = reference_expr.evaluate(node, {"nir": nir, "red": red})
    assert got[0] == 0.5 and np.isnan(got[1]) and np.isinf(got[2])
    with pytest.raises(ValueError):
        reference_expr.parse("(a + b")
    with pytest.raises(ValueError):
        reference_expr.parse("a b")
    assert reference_expr.split_product("ndvi = (a - b) / (a + b)") == \
        ("ndvi", "(a - b) / (a + b)")


def test_palette_is_the_described_ramp(cell):
    colours = cell.config["layers"][0]["palette"]["colours"]
    ramp = reference_expr.palette(colours)
    assert ramp.shape == (256, 4) and ramp.dtype == np.uint8
    # four sections of 64: each starts on its stop, entry 255 is no data
    for s in range(4):
        assert list(ramp[64 * s, :3]) == [colours[s][k] for k in "RGB"]
    assert list(ramp[255]) == [0, 0, 0, 0] and (ramp[:255, 3] == 255).all()
    # integer steps truncated toward zero: brown (140) -> yellow (254) in
    # red rises by 114 * i // 64, yellow (224) -> light green (217) in
    # green falls by -(7 * i // 64)
    assert ramp[32, 0] == 140 + 114 * 32 // 64
    assert ramp[64 + 63, 1] == 224 - 7 * 63 // 64
    # within one level of the float ramp the other tile cells check
    assert np.abs(ramp[:255, :3].astype(float)
                  - reference.palette_ramp(colours)[:255, :3]).max() <= 1.0


# --- what the check flags --------------------------------------------------------

def _png(indices, colours):
    from PIL import Image
    img = Image.fromarray(indices, "P")
    ramp = reference_expr.palette(colours)
    img.putpalette(ramp[:, :3].tobytes())
    buf = io.BytesIO()
    img.save(buf, "PNG", transparency=bytes(ramp[:, 3]))
    return buf.getvalue()


def _serve(gen, spoil):
    """A `fetch` that answers from the reference, spoiled by
    `spoil(gen, req)` -> indices."""
    def fetch(req):
        lay = gen.layers[req.meta["layer"]]
        body = _png(spoil(gen, req), lay["palette"]["colours"])
        return Result(req, 0.0, 0.0, 200, True, 0, len(body), b"same", body)
    return fetch


def _window(gen, n=160):
    reqs = itertools.islice(gen.window().reqs, n)
    return [Result(r, 0.0, 0.0, 200, True, 0, 1000, b"same") for r in reqs]


def _render(gen, req, per_var=None, text=None):
    lay = gen.layers[req.meta["layer"]]
    return reference_expr.render_byte(
        text or gen._expression(lay["name"]),
        per_var or gen._per_var(lay["name"], req.meta["time"]),
        req.meta["bbox"], "EPSG:3857", 256, 256, lay["resample"],
        lay["offset_value"], lay["scale_value"], lay["clip_value"])


def _swapped(gen, req):
    per_var = gen._per_var(req.meta["layer"], req.meta["time"])
    per_var["nbart_nir_1"], per_var["nbart_red"] = \
        per_var["nbart_red"], per_var["nbart_nir_1"]
    return _render(gen, req, per_var)


def _without_the_granule_under(gen, req):
    """The tile as a mosaic renders it that has lost the first granule
    under the tile's centre."""
    bbox = req.meta["bbox"]
    per_var = gen._per_var(req.meta["layer"], req.meta["time"])
    first = next(iter(per_var.values()))
    cx, cy = reference.project(np.array([(bbox[0] + bbox[2]) / 2]),
                               np.array([(bbox[1] + bbox[3]) / 2]),
                               "EPSG:3857", first[0].crs)
    under = [(s.x0, s.y0) for s in first
             if s.x0 <= cx[0] <= s.x0 + s.dx * s.shape[1]
             and s.y0 + s.dy * s.shape[0] <= cy[0] <= s.y0]
    if not under:
        return _render(gen, req)
    return _render(gen, req, {k: [s for s in v if (s.x0, s.y0) != under[0]]
                              for k, v in per_var.items()})


@pytest.mark.parametrize("fault,spoil,flagged", [
    ("none", _render, False),
    ("swapped_variable", _swapped, True),
    ("dropped_granule", _without_the_granule_under, True),
])
def test_verify_flags(cell, fault, spoil, flagged):
    gen = _generator(cell)
    results = _window(gen)
    problems, records = gen.verify(results, _serve(gen, spoil))
    assert len(records) == 8
    assert {r["granules"] > 1 for r in records} == {False, True}
    assert {r["layer"] for r in records} == {"ndvi", "evi"}
    if not flagged:
        assert not problems and all(r["mismatch"] == 0 for r in records)
    elif fault == "swapped_variable":
        assert len(problems) == 8
    else:
        assert problems
        assert any(r["validity_mismatch"] > 0 for r in records)


def test_verify_flags_another_palette(cell):
    gen = _generator(cell)

    def fetch(req):
        body = _png(_render(gen, req), [{"R": 0, "G": 0, "B": 0},
                                        {"R": 255, "G": 255, "B": 255}])
        return Result(req, 0.0, 0.0, 200, True, 0, len(body), b"same", body)
    problems, _ = gen.verify(_window(gen), fetch)
    assert len(problems) == 8 and "ramp" in problems[0]


# --- the bound's upper reading ------------------------------------------------------

def _bf16(a):
    """float32 values rounded to bfloat16 (nearest even), as float32."""
    u = np.asarray(a, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def test_bf16_rasters_or_a_bf16_quotient_fail_the_bound(cell):
    """The bound's upper reading: the reference with its rasters held in
    bfloat16, the nearest precision below the float32 the configuration
    keeps on the device (16 DN steps at 3,200 DN, 8 at 1,300: an NDVI
    step of ~0.3 of a byte level), or with the expression's value held in
    bfloat16 (steps of 1 / 512 near 0.4: half a level), is far outside
    the bound on every checked tile."""
    import dataclasses
    gen = _generator(cell)
    bound = cell.traffic["check"]["bound_mismatch"]
    for r in _window(gen, 40)[::10]:
        req = r.req
        lay = gen.layers[req.meta["layer"]]
        want = _render(gen, req)
        coarse = {k: [dataclasses.replace(
            s, nodata=float(_bf16(s.nodata)),
            read=lambda s=s: _bf16(s.read())) for s in v]
            for k, v in gen._per_var(lay["name"], req.meta["time"]).items()}
        share = reference_expr.compare(_render(gen, req, coarse),
                                       want)["mismatch"]
        assert share > 10 * bound, (req.key, share)
        value, valid = reference_expr.render_plane(
            gen._expression(lay["name"]),
            gen._per_var(lay["name"], req.meta["time"]), req.meta["bbox"],
            "EPSG:3857", 256, 256, lay["resample"])
        quotient = reference.scale_byte(
            _bf16(value), valid, lay["offset_value"], lay["scale_value"],
            lay["clip_value"])
        share = reference_expr.compare(quotient, want)["mismatch"]
        assert share > 10 * bound, (req.key, share)


def test_an_expression_evaluated_before_the_mosaic_fails_the_bound():
    """The archive's bands share one wedge, so on it a mosaic of
    per-granule expressions is the expression of per-band mosaics; the
    product's bands do not (detector footprints differ by band).  On two
    overlapping granules whose red lacks a strip the nir holds, the two
    differ wherever the newer granule's red is missing."""
    rng = np.random.default_rng(35)
    rasters = {(k, b): (base + 600 * rng.random((64, 64))).astype(np.float32)
               for k in range(2) for b, base in (("nir", 3000), ("red", 1200))}
    rasters[1, "red"][:, :24] = -999.0

    def srcs(k):
        return {b: [reference.Source(
            namespace=b, timestamp=float(j), crs="EPSG:3857",
            x0=400.0 * j, y0=0.0, dx=10.0, dy=-10.0, shape=(64, 64),
            nodata=-999.0, read=lambda j=j, b=b: rasters[j, b]) for j in k]
            for b in ("nir", "red")}
    text, bbox = "(nir - red) / (nir + red)", (300.0, -500.0, 700.0, -100.0)
    args = (bbox, "EPSG:3857", 64, 64, "bilinear", 0.0, 254.0, 1.0)
    want = reference_expr.render_byte(text, srcs([0, 1]), *args)
    before = np.full((64, 64), 255, np.uint8)
    for k in (0, 1):
        one = reference_expr.render_byte(text, srcs([k]), *args)
        before = np.where(one != 255, one, before)
    assert reference_expr.compare(before, want)["mismatch"] > 0.1


# --- the readers ----------------------------------------------------------------------

def _ctx(debug0, debug1, module=None, config=None):
    c = Ctx(cell=SimpleNamespace(config=config or {"layers": []}),
            results=[], t0=0.0, window_s=20.0, setup_s=1.0, warmup=[],
            warmed=None, debug0=debug0, debug1=debug1,
            compiles_in_window=(0, 0), device_kind="TPU v5 lite",
            hbm_peak_bytes=None)
    if module is not None:
        c.module = lambda name: module.get(name)
    return c


def _debug(bucketed, unfused, legs=None):
    paths = {k: v for k, v in (("bucketed", bucketed), ("unfused", unfused))
             if v is not None}
    return {"expr": {"fuse": True, "programs": 0, "paths": paths},
            "executor": {"dispatches": legs or {}}}


@pytest.mark.parametrize("debug0, debug1, want", [
    (_debug(300, 0), _debug(2300, 0), 100.0),
    (_debug(10, 10), _debug(40, 20), 75.0),
    (_debug(None, 8), _debug(None, 500), 0.0),      # the parent: unfused only
    (_debug(5, 5), _debug(5, 5), None),             # no expression tile in it
    ({}, {}, None),                                 # no `expr` in /debug
])
def test_fused_share_reads_the_windows_paths(debug0, debug1, want):
    got = spec.reader("layer_metrics", NEW[2]).read(_ctx(debug0, debug1))
    assert got == want


def test_kernel_readers_on_a_recorded_pair():
    config = spec.load_cell(CELL).config
    legs0 = {"render_expr:((1, 11008, 11008, 2), (512, 512))": 100}
    legs1 = {"render_expr:((1, 11008, 11008, 2), (512, 512))": 1100,
             "render_expr:((2, 11008, 11008, 3), (512, 512))": 50,
             "render_rgba:((1, 11008, 11008, 3), (512, 512))": 7}
    ctx = _ctx(_debug(0, 0, legs0), _debug(0, 0, legs1),
               {"render_expr_ctrl": (2.0, 400)}, config)
    assert spec.reader("layer_metrics", NEW[0]).read(ctx) == 5.0
    share = spec.reader("layer_metrics", NEW[1]).read(ctx)
    # (1000 x 2.6 us + 50 x 7.7 us) / 1050 over 5 ms, in %
    one = roofline_expr.render_expr_ctrl(1, 2, 3)[1] / 819e9
    two = roofline_expr.render_expr_ctrl(2, 3, 8)[1] / 819e9
    assert share == pytest.approx(
        100 * (1000 * one + 50 * two) / 1050 / 5e-3, rel=1e-6)
    assert 0.0 < share < 0.2
    # a program with no such kernel (the parent): nothing, and no error
    bare = _ctx(_debug(None, 0, {}), _debug(None, 9, {"scene_mosaic": 9}),
                {}, config)
    assert spec.reader("layer_metrics", NEW[0]).read(bare) is None
    assert spec.reader("layer_metrics", NEW[1]).read(bare) is None


def test_roofline_counts():
    assert roofline_expr.leg_shape(
        "render_expr:((4, 11008, 11008, 3), (512, 512))") == (4, 3)
    assert roofline_expr.leg_shape("render_rgba:((1, 768, 768), None)") \
        is None
    ops = roofline_expr.ops_by_bands(spec.load_cell(CELL).config["layers"])
    assert ops == {2: 3, 3: 8}      # NDVI: - + /; EVI: 8 arithmetic nodes
    o1, b1 = roofline_expr.render_expr_ctrl(1, 2, 3)
    o4, b4 = roofline_expr.render_expr_ctrl(4, 2, 3)
    px = 256 * 256
    assert b1 == px * 2 * 4 * 4 + 2 * 17 * 17 * 4 + 14 * 4 + px
    assert b4 - b1 == 3 * (px * 2 * 4 * 4 + 14 * 4) and o4 > o1
    assert b1 / 819e9 > o1 / 197e12        # memory-bound


# --- the cell ------------------------------------------------------------------------

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
    assert m["executor.expr_fused_share"]["value"] == 100.0
    assert "render_expr_ctrl_roofline" not in m     # no device trace here
    assert "kernels.expr_render_ms_per_tile" not in m
    report = json.load(open(tmp_path / f"{CELL}.json"))
    legs = "".join(report["legs"])      # both layers, one set and several
    assert "render_expr:((1," in legs and ", 2), " in legs \
        and ", 3), " in legs
    assert "render_expr:((2," in legs or "render_expr:((4," in legs
    assert all(leg.startswith("render_expr:") for leg in report["legs"])
    assert all(c["mismatch"] <= 0.005 for c in report["records"])
    assert {c["layer"] for c in report["records"]} == {"ndvi", "evi"}
    assert traced["checks"]["demand_moved"] == 0
    untraced = run(0)
    assert untraced["correct"]
    assert set(untraced["metrics"]) == {"latency_p50_ms", "throughput_rps",
                                        "setup_s"}
