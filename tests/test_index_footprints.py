"""A dataset row's footprint is prepared once per store generation
(`index/store.py`: `_Footprint`, `MASStore._refine`) and every
`intersects` answers the rows it always answered, in their order.

The oracle below is the contract written plainly: parse the WKT,
reproject to EPSG:4326 (point transforms only), split at the
antimeridian, and call two geometries intersecting when their bboxes
overlap and a vertex of either lies in the other or two exterior edges
cross.  It is pure Python and imports nothing from `index/store.py`."""

import itertools
import json
import math
import os
import re
import sys
import threading

import numpy as np
import pytest

from benchmarks import spec
from gsky_tpu.geo import geometry as geom
from gsky_tpu.geo.crs import EPSG3857, EPSG4326, parse_crs
from gsky_tpu.geo.transform import BBox
from gsky_tpu.index import MASClient
from gsky_tpu.index.store import MASStore, _Footprint, parse_time
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
from gsky_tpu.pipeline.drill import DrillPipeline
from gsky_tpu.pipeline.types import GeoDrillRequest

# -- the oracle -----------------------------------------------------------------

_NUM = r"[-+]?(?:\d+\.?\d*|\.\d+)(?:[eE][-+]?\d+)?"


def _ring(text):
    return [(float(x), float(y))
            for x, y in re.findall(rf"({_NUM})\s+({_NUM})", text)]


def parse(wkt):
    """("point", [(x, y)]) or ("polygon", [[exterior, hole, ...], ...])."""
    kind, body = re.match(r"\s*(\w+)\s*(\(.*\))\s*$", wkt, re.S).groups()
    kind = kind.upper()
    if kind == "POINT":
        return "point", _ring(body)
    if kind == "POLYGON":
        return "polygon", [[_ring(r) for r in re.findall(r"\(([^()]*)\)", body)]]
    if kind == "MULTIPOLYGON":
        return "polygon", [
            [_ring(r) for r in re.findall(r"\(([^()]*)\)", poly)]
            for poly in re.findall(r"\((\([^()]*\)(?:\s*,\s*\([^()]*\))*)\)",
                                   body)]
    raise ValueError(kind)


def _segmentized(ring, max_len):
    out = [ring[0]]
    for (x0, y0), (x1, y1) in zip(ring, ring[1:]):
        n = max(1, math.ceil(math.hypot(x1 - x0, y1 - y0) / max_len))
        out += [(x0 + (x1 - x0) * k / n, y0 + (y1 - y0) * k / n)
                for k in range(1, n + 1)]
    return out


def _to_4326(ring, crs):
    x, y = crs.transform_to(EPSG4326, np.array([p[0] for p in ring]),
                            np.array([p[1] for p in ring]))
    return list(zip(np.asarray(x, float).tolist(),
                    np.asarray(y, float).tolist()))


def _clip(ring, bound, keep_le):
    """Sutherland-Hodgman against the meridian x = bound."""
    def inside(p):
        return p[0] <= bound if keep_le else p[0] >= bound
    pts = ring[:-1] if ring[0] == ring[-1] else ring
    out = []
    for a, b in zip(pts, pts[1:] + pts[:1]):
        if inside(a) != inside(b):
            t = (bound - a[0]) / (b[0] - a[0])
            cut = (bound, a[1] + t * (b[1] - a[1]))
            out += [cut, b] if inside(b) else [cut]
        elif inside(b):
            out.append(b)
    return out + out[:1] if len(out) >= 3 else []


def _split(polys):
    out = []
    for poly in polys:
        lons = [p[0] for p in poly[0]]
        if max(lons) - min(lons) <= 180.0:
            out.append(poly)
            continue
        east = [[(x + 360.0 if x < 0 else x, y) for x, y in r] for r in poly]
        for keep_le, back in ((True, 0.0), (False, 360.0)):
            part = [[(x - back, y) for x, y in c]
                    for c in (_clip(r, 180.0, keep_le) for r in east) if c]
            if part:
                out.append(part)
    return out


def geometry(wkt, srs, nseg=0):
    """The WKT as the index reads it: in EPSG:4326, split."""
    kind, parts = parse(wkt)
    crs = parse_crs(srs) if srs else EPSG4326
    if kind == "point":
        return kind, parts if crs == EPSG4326 else _to_4326(parts, crs)
    if crs != EPSG4326:
        if nseg > 1:
            xs = [p[0] for poly in parts for r in poly for p in r]
            ys = [p[1] for poly in parts for r in poly for p in r]
            seg = max((max(xs) - min(xs) + max(ys) - min(ys)) / (2 * nseg),
                      1e-9)
            parts = [[_segmentized(r, seg) for r in poly] for poly in parts]
        parts = [[_to_4326(r, crs) for r in poly] for poly in parts]
    return kind, _split(parts)


def _in_ring(ring, px, py):
    odd = False
    pts = ring[:-1] if ring[0] == ring[-1] else ring
    for (x0, y0), (x1, y1) in zip(pts, pts[1:] + pts[:1]):
        if (y0 > py) != (y1 > py) \
                and px < x0 + (py - y0) * (x1 - x0) / (y1 - y0):
            odd = not odd
    return odd


def _in_polys(polys, p):
    return any(_in_ring(poly[0], *p)
               and not any(_in_ring(h, *p) for h in poly[1:])
               for poly in polys)


def _cross(r1, r2):
    def closed(r):
        return r if r[0] == r[-1] else r + r[:1]
    for (ax, ay), (bx, by) in zip(closed(r1), closed(r1)[1:]):
        for (cx, cy), (dx, dy) in zip(closed(r2), closed(r2)[1:]):
            rxs = (bx - ax) * (dy - cy) - (by - ay) * (dx - cx)
            if rxs == 0:
                continue
            t = ((cx - ax) * (dy - cy) - (cy - ay) * (dx - cx)) / rxs
            u = ((cx - ax) * (by - ay) - (cy - ay) * (bx - ax)) / rxs
            if 0 <= t <= 1 and 0 <= u <= 1:
                return True
    return False


def _bbox(kind, parts):
    pts = parts if kind == "point" else \
        [p for poly in parts for r in poly for p in r]
    xs, ys = [p[0] for p in pts], [p[1] for p in pts]
    return min(xs), min(ys), max(xs), max(ys)


def oracle(row, query):
    """Does the footprint (kind, parts) meet the query (kind, parts)?"""
    a, b = _bbox(*row), _bbox(*query)
    if a[2] <= b[0] or b[2] <= a[0] or a[3] <= b[1] or b[3] <= a[1]:
        return False
    if query[0] == "point":
        return row[0] == "polygon" and any(
            _in_polys(row[1], p) for p in query[1])
    if row[0] == "point":
        return any(_in_polys(query[1], p) for p in row[1])
    return (any(_in_polys(query[1], p) for poly in row[1] for p in poly[0])
            or any(_in_polys(row[1], p) for poly in query[1] for p in poly[0])
            or any(_cross(pa[0], pb[0]) for pa in row[1] for pb in query[1]))


# -- a store that shows what it selected ---------------------------------------

class Watched:
    """A store whose `intersects` calls are written down with the
    candidate rows their candidate step selected (the statement's rows,
    or a store in memory's from its arrays), per thread: the refinement
    is held to the oracle over exactly those rows, in their order."""

    def __init__(self, store):
        self.store = store
        self.calls = []
        self._local = threading.local()
        candidates = store._candidates

        def watched(*args):
            rows = self._local.rows = candidates(*args)
            return rows
        store._candidates = watched

    def intersects(self, gpath, **kw):
        self._local.rows = None
        answer = self.store.intersects(gpath, **kw)
        self.calls.append((kw, self._local.rows, answer))
        return answer

    def __getattr__(self, name):
        return getattr(self.store, name)


def expected(store, kw, candidates):
    """The oracle's choice among the candidate SQL rows, in their order."""
    query = geometry(kw["wkt"], kw.get("srs", ""), kw.get("nseg", 2))
    col = {c: i for i, c in enumerate(store._columns)}
    out = []
    for row in candidates:
        if oracle(geometry(row[col["polygon"]], row[col["srs"]]), query):
            out.append(row)
            if kw.get("limit") and len(out) >= kw["limit"]:
                break
    return [(r[col["path"]], r[col["namespace"]]) for r in out]


def answered(answer):
    return [(r["file_path"], r["namespace"]) for r in answer["gdal"]]


# -- (a) the benchmark's traffic over the rehearsal archives ---------------------

CELLS = {"landsat8-mosaic.pan-cold": ("landsat8-mosaic", "pan-cold"),
         "sentinel2-rgb.pan-cold": ("sentinel2-rgb", "rgb-pan-cold"),
         "modis-fc-drill.polygons-warm": ("modis-fc-drill", "polygons-warm")}
SEEDS = (3, 2147485020, 77)


@pytest.fixture(scope="module")
def archives(tmp_path_factory):
    """{cell: (cell files, archive module, crawl records)} at rehearsal
    size; the footprints do not depend on the seed."""
    out = {}
    for name, (config, mix) in CELLS.items():
        cell = spec.Cell(
            name, 1,
            spec.sized(spec.load_json(os.path.join(
                spec.HERE, "configs", config + ".json")), True),
            spec.sized(spec.load_json(os.path.join(
                spec.HERE, "traffic", mix + ".json")), True), [], [])
        archive = spec.load_kind("archives", cell.config["archive"]["kind"])
        root = tmp_path_factory.mktemp(config)
        out[name] = (cell, archive,
                     archive.build(cell.config["archive"], 3, str(root)),
                     str(root))
    return out


def _requests(cell, archive, seed, n=500):
    gen = spec.load_kind("generators", cell.traffic["generator"]).Generator(
        cell.traffic, cell.config, archive, seed)
    return gen, list(itertools.islice(gen.window().reqs, n))


def _ask(cell, root, mas, gen, req):
    """The index query the served path makes for this request."""
    if req.kind == "GetMap":
        lay = gen.layers[req.meta["layer"]]
        t = parse_time(req.meta["time"])
        start, end = (parse_time(gen.dates[0]), t) if lay.get("accum") \
            else (t, None)
        TilePipeline(mas).index(GeoTileRequest(
            collection=os.path.join(root, lay["collection"]),
            bands=lay["rgb_products"], bbox=BBox(*req.meta["bbox"]),
            crs=EPSG3857, start_time=start, end_time=end,
            resample=lay.get("resample", "near")))
    else:
        src = cell.config["processes"][0]["data_sources"][0]
        gj = json.loads(re.search(rb"(\{.*\})", req.body).group(1))
        DrillPipeline(mas).index(GeoDrillRequest(
            collection=os.path.join(root, src["collection"]),
            bands=src["rgb_products"],
            geometry_wkt=geom.from_geojson(gj).to_wkt()))


def _over_a_wedge(p, bbox):
    """Does the tile hold a point of some granule's nodata wedge?"""
    H, W = p["granule_hw"]
    t = np.linspace(0.0, 1.0, 17)
    mx, my = np.meshgrid(bbox[0] + t * (bbox[2] - bbox[0]),
                         bbox[1] + t * (bbox[3] - bbox[1]))
    ux, uy = EPSG3857.transform_to(parse_crs(p["crs"]), mx.ravel(),
                                   my.ravel())
    rows, cols = p["grid"]
    for i in range(rows):
        for j in range(cols):
            c = (ux - (p["origin"][0] + j * p["pitch_m"])) / p["res"]
            r = ((p["origin"][1] - i * p["pitch_m"]) - uy) / p["res"]
            inside = (c >= 0) & (c < W) & (r >= 0) & (r < H)
            frac = (np.floor(r) + 0.5) / H
            k = np.ceil(p["wedge_px"] * (frac if j % 2 == 0 else 1 - frac))
            wedge = (c >= W - k) if j % 2 == 0 else (c < k)
            if (inside & wedge).any():
                return True
    return False


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", list(CELLS))
def test_the_benchmarks_queries_get_the_oracles_rows(archives, name, seed):
    cell, archive, records, root = archives[name]
    store = MASStore()
    store.ingest_many(records)
    watched = Watched(store)
    gen, reqs = _requests(cell, archive, seed)
    assert len(reqs) == 500
    mas = MASClient(watched)
    for req in reqs:
        _ask(cell, root, mas, gen, req)
    assert len(watched.calls) >= 500
    granules = set()
    for kw, candidates, answer in watched.calls:
        assert candidates is not None       # no answer from the cache
        assert answered(answer) == expected(store, kw, candidates), kw
        granules.add(len({path.rsplit("_", 1)[0]
                          for path, _ in answered(answer)}))
    # a row is prepared by the first query that meets it, and only then
    assert 1 <= store.footprint_misses <= len(
        store._fetchall("SELECT id FROM datasets"))
    assert store.footprint_hits + store.footprint_misses == sum(
        len(candidates) for _, candidates, _ in watched.calls)
    if name == "sentinel2-rgb.pan-cold":
        # tiles on an overlap take rows of 2 or 4 granules, and tiles
        # over a granule's nodata wedge are among them: where a dropped
        # neighbour would leave a hole
        assert {1, 2, 4} <= granules
        assert any(_over_a_wedge(cell.config["archive"], r.meta["bbox"])
                   for r in reqs)
    if name != "modis-fc-drill.polygons-warm":
        # the refinement turns candidates away: not the prefilter's echo
        assert any(len(answer["gdal"]) < len(candidates)
                   for _, candidates, answer in watched.calls)


# -- (b) the shapes a footprint and a query come in ------------------------------

SQ = "POLYGON((10 10,20 10,20 20,10 20,10 10))"
HOLED = ("POLYGON((0 0,30 0,30 30,0 30,0 0),"
         "(10 10,20 10,20 20,10 20,10 10))")
TWO = ("MULTIPOLYGON(((0 0,5 0,5 5,0 5,0 0)),"
       "((40 40,50 40,50 50,40 50,40 40),(42 42,48 42,48 48,42 48,42 42)))")
# a footprint given in EPSG:4326 with vertices either side of 180: the
# form that is split into an eastern and a western part
ACROSS = ("EPSG:4326", "POLYGON((179 -36,-179 -36,-179 -35,179 -35,179 -36))")
# UTM footprints of zones 60 and 1 that reach over the antimeridian (x
# runs from 100 km to 260 km off the central meridian, 6 degrees wide at
# 60 N).  The inverse projection does not wrap, so they come out as one
# part reaching past +-180 (to 181.7 E, to 181.7 W) and answer on their
# zone's side of the line
ZONE60 = ("EPSG:32660",
          "POLYGON((600000 6600000,760000 6600000,760000 6700000,"
          "600000 6700000,600000 6600000))")
ZONE1 = ("EPSG:32601",
         "POLYGON((240000 6600000,400000 6600000,400000 6700000,"
         "240000 6700000,240000 6600000))")


def _box(x0, y0, x1, y1):
    return f"POLYGON(({x0} {y0},{x1} {y0},{x1} {y1},{x0} {y1},{x0} {y0}))"


SHAPES = [
    # id, (row srs, row polygon), (query srs, query wkt), intersects
    ("inside-a-footprint", ("EPSG:4326", SQ), ("", _box(12, 12, 13, 13)), True),
    ("footprint-inside-a-query", ("EPSG:4326", SQ), ("", _box(0, 0, 40, 40)),
     True),
    ("edges-cross-no-vertex-inside", ("EPSG:4326", SQ),
     ("", _box(12, 0, 18, 40)), True),
    ("apart", ("EPSG:4326", SQ), ("", _box(21, 21, 25, 25)), False),
    ("bboxes-meet-shapes-do-not", ("EPSG:4326",
                                   "POLYGON((0 0,10 0,0 10,0 0))"),
     ("", _box(6, 6, 9, 9)), False),
    ("touching-edges", ("EPSG:4326", SQ), ("", _box(20, 10, 30, 20)), False),
    ("touching-corners", ("EPSG:4326", SQ), ("", _box(20, 20, 30, 30)), False),
    ("collinear-overlap", ("EPSG:4326", SQ), ("", _box(15, 10, 25, 20)), True),
    ("in-the-hole", ("EPSG:4326", HOLED), ("", _box(12, 12, 18, 18)), False),
    ("over-the-holes-edge", ("EPSG:4326", HOLED), ("", _box(5, 12, 15, 18)),
     True),
    ("second-part", ("EPSG:4326", TWO), ("", _box(39, 39, 41, 41)), True),
    ("second-parts-hole", ("EPSG:4326", TWO), ("", _box(44, 44, 46, 46)),
     False),
    ("between-the-parts", ("EPSG:4326", TWO), ("", _box(10, 10, 30, 30)),
     False),
    ("multipart-query", ("EPSG:4326", SQ), ("", TWO.replace("40", "12")),
     True),
    ("point-inside", ("EPSG:4326", SQ), ("", "POINT(15 15)"), True),
    ("point-outside", ("EPSG:4326", SQ), ("", "POINT(25 15)"), False),
    ("point-in-the-hole", ("EPSG:4326", HOLED), ("", "POINT(15 15)"), False),
    ("near-point-inside", ("EPSG:4326", SQ),
     ("", _box(15, 15, 15 + 1e-9, 15 + 1e-9)), True),
    ("near-point-outside", ("EPSG:4326", SQ),
     ("", _box(25, 15, 25 + 1e-9, 15 + 1e-9)), False),
    ("a-3857-tile", ("EPSG:4326", SQ),
     ("EPSG:3857", _box(1600000, 1600000, 1700000, 1700000)), True),
    ("across-east-side", ACROSS, ("", _box(179.2, -35.8, 179.6, -35.2)), True),
    ("across-west-side", ACROSS, ("", _box(-179.6, -35.8, -179.2, -35.2)),
     True),
    ("across-away", ACROSS, ("", _box(0, -35.8, 1, -35.2)), False),
    ("across-query-across", ACROSS,
     ("", "POLYGON((179.8 -35.8,-179.8 -35.8,-179.8 -35.2,179.8 -35.2,"
          "179.8 -35.8))"), True),
    ("query-across-a-plain-footprint",
     ("EPSG:4326", _box(-179.9, -35.9, -179.1, -35.1)),
     ("", "POLYGON((179.8 -35.8,-179.8 -35.8,-179.8 -35.2,179.8 -35.2,"
          "179.8 -35.8))"), True),
    ("zone60-its-side", ZONE60, ("", _box(179.0, 59.6, 179.2, 59.8)), True),
    ("zone60-up-to-the-line", ZONE60, ("", _box(179.5, 59.6, 180.0, 59.8)),
     True),
    ("zone60-far", ZONE60, ("", _box(0, 59.6, 1, 59.8)), False),
    ("zone1-its-side", ZONE1, ("", _box(-179.0, 59.6, -178.8, 59.8)), True),
    ("zone1-far", ZONE1, ("", _box(0, 59.6, 1, 59.8)), False),
]


def _row(path, polygon, srs="EPSG:4326", ns="b1"):
    return {"filename": path, "file_type": "GTiff", "geo_metadata": [{
        "ds_name": path, "namespace": ns, "array_type": "Int16",
        "srs": srs, "geotransform": [0, 1, 0, 0, 0, -1],
        "polygon": polygon, "timestamps": ["2020-01-10T00:00:00.000Z"]}]}


@pytest.mark.parametrize("case", SHAPES, ids=[c[0] for c in SHAPES])
def test_shapes(case):
    _, (row_srs, polygon), (srs, wkt), want = case
    store = MASStore()
    store.ingest(_row("/d/a.tif", polygon, row_srs))
    assert oracle(geometry(polygon, row_srs), geometry(wkt, srs, 2)) is want
    for _ in range(2):      # prepared by the first, found by the second
        assert store.intersects("/d", srs=srs, wkt=wkt)["files"] \
            == (["/d/a.tif"] if want else [])
        wkt = wkt + " "     # another question to the answer cache
    # a candidate by its bbox is prepared once and found once; the
    # prefilter alone turns the others away
    assert (store.footprint_hits, store.footprint_misses) in ((1, 1), (0, 0))
    if want:
        assert (store.footprint_hits, store.footprint_misses) == (1, 1)


def test_the_dateline_fixtures_are_what_they_are_meant_to_be():
    assert len(geometry(ACROSS[1], ACROSS[0])[1]) == 2
    assert len(geom.from_wkt(ACROSS[1]).split_dateline().polys) == 2
    for (srs, polygon), sign in ((ZONE60, 1), (ZONE1, -1)):
        g = geom.from_wkt(polygon).transform(
            lambda x, y: parse_crs(srs).transform_to(EPSG4326, x, y))
        lons = sign * g.polys[0][0][:, 0]
        assert lons.min() < 179 and lons.max() > 181
        assert len(geometry(polygon, srs)[1]) == 1


def test_a_ring_of_many_vertices_puts_every_64th_to_the_query():
    """A footprint of 640 vertices: the refinement asks the query about
    every tenth, as it always has; a query that holds none of those,
    lies outside the ring's interior and crosses no edge is not met."""
    n = 640
    ang = 2 * np.pi * np.arange(n) / n
    ring = [(50 + 10 * math.cos(a), 50 + 10 * math.sin(a)) for a in ang]
    polygon = "POLYGON((" + ",".join(f"{x!r} {y!r}" for x, y in
                                     ring + ring[:1]) + "))"
    store = MASStore()
    store.ingest(_row("/d/round.tif", polygon))
    inside = _box(49, 49, 51, 51)
    crossing = _box(59, 49, 62, 51)
    outside = _box(57.5, 57.5, 59, 59)          # inside the bbox's corner
    for wkt, want in ((inside, True), (crossing, True), (outside, False)):
        assert oracle(geometry(polygon, ""), geometry(wkt, "")) is want
        assert bool(store.intersects("/d", wkt=wkt)["files"]) is want


def test_a_polygon_that_does_not_parse_keeps_its_row():
    """A database something else wrote: the row is a candidate by its
    bbox and its polygon is no WKT.  It stays in, as it always has, and
    is not parsed again by the next query."""
    store = MASStore()
    store.ingest(_row("/d/a.tif", SQ))
    store.ingest(_row("/d/b.tif", SQ))
    store._conn().execute(
        "UPDATE datasets SET polygon = 'POLYGON((not wkt))' WHERE path = ?",
        ("/d/b.tif",))
    store._conn().execute(
        "UPDATE datasets SET srs = 'EPSG:999999' WHERE path = ?",
        ("/d/a.tif",))
    store._conn().commit()
    calls = []
    real = geom.from_wkt
    try:
        geom.from_wkt = lambda w: calls.append(w) or real(w)
        for k in range(3):
            got = store.intersects("/d", wkt=_box(100 + k, 10, 101 + k, 11))
            # far from both: the bbox prefilter alone decides
            assert got["files"] == []
            got = store.intersects("/d", wkt=_box(12 + k, 12, 13 + k, 13))
            assert got["files"] == ["/d/a.tif", "/d/b.tif"]
    finally:
        geom.from_wkt = real
    assert sum(w.startswith("POLYGON((not") for w in calls) == 1
    assert (store.footprint_hits, store.footprint_misses) == (4, 2)


def test_limit_cuts_the_loop():
    store = MASStore()
    store.ingest_many(_row(f"/d/f{i}.tif", _box(10 + i, 10, 20 + i, 20))
                      for i in range(6))
    wkt = _box(16.5, 12, 17, 13)        # inside all six
    everything = store.intersects("/d", wkt=wkt, metadata="gdal")["gdal"]
    assert [r["file_path"] for r in everything] \
        == [f"/d/f{i}.tif" for i in range(6)]
    hits0, misses0 = store.footprint_hits, store.footprint_misses
    cut = store.intersects("/d", wkt=wkt, metadata="gdal", limit=2)["gdal"]
    assert [r["file_path"] for r in cut] \
        == [r["file_path"] for r in everything[:2]]
    # the rows past the cut were not looked at
    assert (store.footprint_hits - hits0,
            store.footprint_misses - misses0) == (2, 0)
    # and without a geometry the cut is the same
    assert len(store.intersects("/d", metadata="gdal", limit=4)["gdal"]) == 4


# -- (c) a footprint dies with its generation -----------------------------------

EAST, WEST = _box(20, 10, 30, 20), _box(0, 10, 10, 20)
IN_EAST, IN_WEST = _box(24, 14, 25, 15), _box(4, 14, 5, 15)


@pytest.mark.parametrize("same_store", [True, False],
                         ids=["same-store", "second-store-same-file"])
def test_an_ingest_prepares_anew(tmp_path, same_store):
    db = str(tmp_path / "mas.sqlite")
    reader = MASStore(db)
    writer = reader if same_store else MASStore(db)
    writer.ingest(_row("/d/a.tif", EAST))
    writer.ingest(_row("/d/still.tif", EAST))
    for k in range(2):
        assert reader.intersects("/d", wkt=IN_EAST + " " * k)["files"] \
            == ["/d/a.tif", "/d/still.tif"]
    assert (reader.footprint_hits, reader.footprint_misses) == (2, 2)
    writer.ingest(_row("/d/a.tif", WEST))      # replaced: same path, moved
    assert reader.intersects("/d", wkt=IN_EAST + "  ")["files"] \
        == ["/d/still.tif"]
    assert reader.intersects("/d", wkt=IN_WEST)["files"] == ["/d/a.tif"]
    # both rows prepared again under the new generation, once
    assert (reader.footprint_hits, reader.footprint_misses) == (2, 4)
    gen, kept = reader._rows
    assert gen == reader.generation and len(kept) == 2


def test_a_kept_footprint_answers_only_for_the_row_it_was_made_from():
    """sqlite hands a deleted row's id to the next insert, and a query
    may read its generation before an ingest and select after it: a
    footprint kept under the same id and generation must not answer for
    a row with another polygon."""
    store = MASStore()
    store.ingest(_row("/d/a.tif", EAST))
    gen = store.generation
    row, = store._fetchall("SELECT * FROM datasets")
    i = store._columns.index("polygon")
    forged = row[:i] + (WEST,) + row[i + 1:]
    in_east = _Footprint(geom.from_wkt(IN_EAST))
    in_west = _Footprint(geom.from_wkt(IN_WEST))
    assert store._refine([row], in_east, gen, 0) == [row]
    assert store._refine([forged], in_east, gen, 0) == []
    assert store._refine([forged], in_west, gen, 0) == [forged]
    assert store._refine([row], in_west, gen, 0) == []
    assert (store.footprint_hits, store.footprint_misses) == (1, 3)
    # a query that read an older generation prepares for itself and
    # leaves nothing behind
    assert store._refine([forged], in_west, gen - 1, 0) == [forged]
    assert (store.footprint_hits, store.footprint_misses) == (1, 4)
    assert store._rows[0] == gen and len(store._rows[1]) == 1


def test_a_record_and_a_footprint_share_the_rows_entry():
    """One home for what is derived from a row: whichever query comes
    first begins the entry, the other adds to it."""
    store = MASStore()
    store.ingest(_row("/d/a.tif", EAST))
    store.intersects("/d", metadata="gdal")            # no geometry
    (entry,) = store._rows[1].values()
    assert entry.record is not None
    assert (store.footprint_hits, store.footprint_misses) == (0, 0)
    store.intersects("/d", wkt=IN_EAST, metadata="gdal")
    assert list(store._rows[1].values()) == [entry]
    assert (store.footprint_hits, store.footprint_misses) == (0, 1)
    assert (store.row_hits, store.row_misses) == (1, 1)


# -- (d) what a warm query runs ---------------------------------------------------

def test_a_warm_query_parses_and_reprojects_its_own_geometry_only(
        archives, monkeypatch):
    cell, archive, records, root = archives["sentinel2-rgb.pan-cold"]
    store = MASStore()
    store.ingest_many(records)
    gen, reqs = _requests(cell, archive, 5, 40)
    mas = MASClient(store)
    crs, x0, y0, x1, y1 = archive.extent(cell.config["archive"])
    everything = store.intersects(root, srs=crs, wkt=_box(x0, y0, x1, y1))
    assert len(everything["files"]) == 12   # every row met once
    assert store.footprint_misses == 12
    for req in reqs[:20]:
        _ask(cell, root, mas, gen, req)
    parsed, transformed, ring_tests = [], [], []
    real_wkt, real_transform = geom.from_wkt, geom.Geometry.transform
    real_ring = geom._point_in_ring
    monkeypatch.setattr(geom, "from_wkt",
                        lambda w: parsed.append(w) or real_wkt(w))
    monkeypatch.setattr(
        geom.Geometry, "transform",
        lambda self, fn: transformed.append(self) or real_transform(self, fn))
    monkeypatch.setattr(
        geom, "_point_in_ring",
        lambda *a: ring_tests.append(a) or real_ring(*a))
    hits0, misses0 = store.footprint_hits, store.footprint_misses
    rows0 = store.row_hits
    for req in reqs[20:]:
        _ask(cell, root, mas, gen, req)
    assert len(parsed) == 20 and len(transformed) == 20
    assert not ring_tests
    assert store.footprint_misses == misses0 == 12
    assert store.footprint_hits - hits0 >= store.row_hits - rows0 >= 3


def test_debug_cache_has_mas_footprints():
    from gsky_tpu.server.metrics import cache_stats
    before = cache_stats()["mas_footprints"]
    store = MASStore()
    store.ingest_many(_row(f"/d/f{i}.tif", _box(10 + i, 10, 20 + i, 20))
                      for i in range(3))
    for k in range(4):
        store.intersects("/d", wkt=_box(16 + 0.1 * k, 12, 17, 13))
    # one answered by the store's answer cache refines nothing
    store.intersects("/d", wkt=_box(16 + 0.1 * 0, 12, 17, 13))
    after = cache_stats()["mas_footprints"]
    assert set(after) == {"hits", "misses"}
    assert after["misses"] - before["misses"] == 3
    assert after["hits"] - before["hits"] == 9
    assert (store.footprint_hits, store.footprint_misses) == (9, 3)


def test_the_bound_on_kept_rows_holds_for_footprints(monkeypatch):
    monkeypatch.setattr(MASStore, "_ROW_CACHE_MAX", 4)
    store = MASStore()
    store.ingest_many(_row(f"/d/f{i}.tif", SQ) for i in range(10))
    for k in range(3):
        got = store.intersects("/d", wkt=_box(12 + 0.1 * k, 12, 13, 13))
        assert len(got["files"]) == 10
        assert len(store._rows[1]) <= 4
    hits0 = store.footprint_hits
    for k in range(3):      # two rows fit: kept and found again
        store.intersects("/d/f0", wkt=_box(14 + 0.1 * k, 12, 15, 13))
    assert store.footprint_hits - hits0 >= 2


# -- (e) queries beside an ingest -----------------------------------------------

def test_six_threads_beside_an_ingest_answer_by_the_rows_they_read():
    """One writer moves /d/a.tif between EAST and WEST (its id is handed
    out again each time) while six readers ask about both places.  Each
    answer is the oracle's over the very rows that query's SQL selected:
    no footprint of an older row answers for a newer one."""
    store = MASStore()
    store.ingest(_row("/d/still.tif", EAST))
    store.ingest(_row("/d/a.tif", EAST))
    watched = Watched(store)
    readers, per_reader, errors = 6, 40, []
    reading = [True] * readers
    start = threading.Barrier(readers + 1)

    def write():
        try:
            start.wait()
            k = 0
            while any(reading) or k < 20:
                store.ingest(_row("/d/a.tif", WEST if k % 2 == 0 else EAST))
                k += 1
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    def read(t):
        try:
            start.wait()
            for k in range(per_reader):
                x = (24 if k % 2 else 4) + 0.001 * (t * per_reader + k)
                watched.intersects("/d", wkt=_box(x, 14, x + 0.5, 15),
                                   metadata="gdal")
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)
        finally:
            reading[t] = False

    ts = [threading.Thread(target=write)] + [
        threading.Thread(target=read, args=(t,)) for t in range(readers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts)
    assert not errors, errors[:1]
    assert len(watched.calls) == readers * per_reader
    seen = set()
    for kw, candidates, answer in watched.calls:
        assert answered(answer) == expected(store, kw, candidates), kw
        seen.add(tuple(sorted(p for p, _ in answered(answer))))
    # the readers saw the row in both places
    assert seen <= {("/d/still.tif",), ("/d/a.tif", "/d/still.tif"),
                    ("/d/a.tif",), ()}
    assert len(seen) >= 3
    assert len(store._rows[1]) <= 2
