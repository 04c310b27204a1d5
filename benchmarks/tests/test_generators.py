"""The generators: the same seed gives the same traffic, another seed
other traffic, and each mix keeps the property its cell rests on."""

import itertools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import spec     # noqa: E402

CELLS = {"landsat8-mosaic.pan-cold": ("landsat8-mosaic", "pan-cold"),
         "modis-fc-drill.polygons-warm": ("modis-fc-drill", "polygons-warm")}


def make(cell_name, seed):
    config, mix = CELLS[cell_name]
    cell = spec.Cell(
        cell_name, 1,
        spec.sized(spec.load_json(os.path.join(
            spec.HERE, "configs", config + ".json")), True),
        spec.sized(spec.load_json(os.path.join(
            spec.HERE, "traffic", mix + ".json")), True), [], [])
    archive = spec.load_kind("archives", cell.config["archive"]["kind"])
    gen = spec.load_kind("generators", cell.traffic["generator"])
    return cell, gen.Generator(cell.traffic, cell.config, archive, seed)


def first(plan, n=300):
    return list(itertools.islice(plan.reqs, n))


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_same_seed_same_traffic(cell_name):
    a = first(make(cell_name, 5)[1].window())
    b = first(make(cell_name, 5)[1].window())
    c = first(make(cell_name, 6)[1].window())
    assert [(r.path, r.body) for r in a] == [(r.path, r.body) for r in b]
    assert [(r.path, r.body) for r in a] != [(r.path, r.body) for r in c]


def test_the_drills_prefill_covers_the_lattice_whatever_the_seed():
    cell, gen = make("modis-fc-drill.polygons-warm", 5)
    a = gen.prefill()
    b = make("modis-fc-drill.polygons-warm", 9)[1].prefill()
    assert [r.body for r in a] == [r.body for r in b]
    edges = cell.traffic["warmup"]["lattice_px"]
    assert sorted(r.meta["window_px"] for r in a) == sorted(
        (h + 1, w + 1) for h in edges for w in edges)


def test_pan_cold_never_repeats():
    """Nothing is asked for twice in one process, twins included: the
    response cache can answer nothing."""
    _, gen = make("landsat8-mosaic.pan-cold", 5)
    plan = gen.window()
    head = first(plan, 500)
    twins = gen.twins(head)
    more = first(plan, 1000)        # drawn after the twins, as run.py does
    keys = [r.key for r in head + twins + more]
    assert len(head) == 500 and len(more) == 1000
    assert len(set(keys)) == len(keys)
    assert len({r.path for r in head + twins + more}) == len(keys)
    assert gen.prefill() == []


def test_a_twin_is_the_same_tile_a_sliver_of_a_pixel_on():
    """Near enough to touch the same scenes and fall into the same
    bucket; far enough to be another tile to the program's response
    cache, which keys a bbox by 1/256ths of a pixel: two of those
    steps, at every level."""
    _, gen = make("landsat8-mosaic.pan-cold", 5)
    reqs = first(gen.window(), 300)
    assert len({r.meta["z"] for r in reqs}) >= 3
    for req, twin in zip(reqs, gen.twins(reqs)):
        a, b = np.array(req.meta["bbox"]), np.array(twin.meta["bbox"])
        pixel = (a[2] - a[0]) / 256
        assert np.allclose(b - a, pixel / 128, rtol=1e-6)
        # the response cache's own rule (serving/response_cache.py)
        quantum = pixel / 256
        assert (np.round(b / quantum) != np.round(a / quantum)).all()
        assert twin.path != req.path
        assert (twin.meta["layer"], twin.meta["time"]) == \
            (req.meta["layer"], req.meta["time"])


def test_the_mix_holds_the_shares_the_file_states():
    cell, gen = make("landsat8-mosaic.pan-cold", 5)
    reqs = first(gen.window(), 3000)
    by_layer = {n: sum(r.meta["layer"] == n for r in reqs) / len(reqs)
                for n in cell.traffic["layers"]}
    for n, share in cell.traffic["layers"].items():
        assert abs(by_layer[n] - share) < 0.12
    assert {str(r.meta["z"]) for r in reqs} == set(cell.traffic["zoom_shares"])
    assert len({r.meta["time"] for r in reqs}) == len(gen.dates)


def test_views_ask_only_for_what_they_newly_show():
    _, gen = make("landsat8-mosaic.pan-cold", 5)
    rng = np.random.default_rng(3)
    for _ in range(20):
        had = set()
        views = list(gen.session(rng))
        assert len(views) >= gen.t["views"][0]
        for view in views:
            keys = {r.key for r in view}
            assert len(keys) == len(view)
            assert not keys & had           # a browser keeps its tiles
            had |= keys


def test_polygons_lie_inside_the_stack_and_differ():
    cell, gen = make("modis-fc-drill.polygons-warm", 5)
    p = cell.config["archive"]
    lon0, lat0 = p["origin"]
    h, w = p["hw"]
    reqs = first(gen.window(), 200)
    assert len({r.body for r in reqs}) == len(reqs)
    import json
    import re
    lo, hi = cell.traffic["side_px"]
    for r in reqs:
        gj = json.loads(re.search(
            rb"<wps:ComplexData[^>]*>(.*)</wps:ComplexData>", r.body).group(1))
        ring = np.array(gj["features"][0]["geometry"]["coordinates"][0])
        assert (ring[0] == ring[-1]).all()
        assert cell.traffic["vertices"][0] <= len(ring) - 1 \
            <= cell.traffic["vertices"][1]
        assert ring[:, 0].min() >= lon0 and \
            ring[:, 0].max() <= lon0 + w * p["res"]
        assert ring[:, 1].max() <= lat0 and \
            ring[:, 1].min() >= lat0 - h * p["res"]
        wh, ww = r.meta["window_px"]
        assert 1 <= wh <= h and 1 <= ww <= w
    sides = [r.meta["side_px"] for r in reqs]
    assert lo <= min(sides) and max(sides) <= hi
    assert max(sides) > 4 * min(sides)      # paddock to catchment


# sha256 over path, NUL, body, newline of a seed's first 500 requests at
# the size the chip runs, recorded from the tree before PR 31 (34f48ce):
# a repair of `correct` or of the warm-up sends the window what it was sent
DRAWN = {
    "pan-cold":
        "2e83520bccdcb649ff6ce84ff1fcaa6195eeacb9f56494a792ab25ef410980bb",
    "polygons-warm":
        "16689db2a116430cd2c296ab9cb385780c9e035ca49bb457102721b20e5f39a2",
    "rgb-pan-cold":
        "a34f09c9f5a93217ab43235b5a4a4f8cb2fec4bde045b808a115d929cd8d18b5",
}


@pytest.mark.parametrize("mix", sorted(DRAWN))
def test_the_same_seed_still_draws_the_same_requests(mix):
    import hashlib
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    name = next(w["name"] for w in bench["workloads"] if w["traffic"] == mix)
    cell = spec.load_cell(name)
    archive = spec.load_kind("archives", cell.config["archive"]["kind"])
    gen = spec.load_kind("generators", cell.traffic["generator"]).Generator(
        cell.traffic, cell.config, archive, 2147483659)
    h = hashlib.sha256()
    for r in first(gen.window(), 500):
        h.update(r.path.encode() + b"\0" + (r.body or b"") + b"\n")
    assert h.hexdigest() == DRAWN[mix]
