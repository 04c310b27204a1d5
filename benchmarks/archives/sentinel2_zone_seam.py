"""Archive kind `sentinel2_zone_seam`: MGRS granules of one sensing date
either side of a UTM zone line, each granule in its own zone's CRS, one
single-band GeoTIFF per granule and band, each band its own namespace.

Parameters (the configuration's "archive" group): `zones`, one group a
column of granules ({"crs", "x0", "file_prefix", "wedge_edge": "east"
or "west", the edge that faces the other zone}), `rows_y0` the northern
edge of each row of granules (the same in every zone), res, granule_hw,
date, bands [{"name", "namespace", "base"}], nodata, wedge_px, compress,
collection.  A band may name a `resolution`, a group of `resolutions`
that gives its own res, granule_hw and wedge_px over the same ground
(Sentinel-2's 20 m bands beside its 10 m ones, as
`sentinel2_bands_by_res` has them).

The imagery is a function of geographic position: a smooth field per
band of (longitude, latitude) (phases drawn from [seed, band]), plus +-2
DN of noise keyed by the absolute row and column of the zone's grid.
So both zones show one ground, as the product does, and a granule warped
with the other zone's control grid lands kilometres off.  The field is
evaluated at the exactly projected position of every 16th pixel centre
of a granule and interpolated between, ~0.1 DN off the field at a
pixel's own position on these 160 m cells (its shortest period is 15
km).
Each granule lacks a wedge of its own along the edge that faces the
other zone, at most `wedge_px` wide, inside the strip the two zones
share, where the other zone holds data: a mosaic that drops a zone's
sets shows there.  All granules carry one timestamp, as one datatake
does.  Any raster is made again from the seed without the others.
"""

import datetime as dt
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..reference import Source

WRITERS = 4     # a band is ~0.4 GB of host memory while it is made
LATTICE = 16    # px between the nodes the field is evaluated at
# where the field's phases are measured from, and metres a degree there
LON0, LAT0 = 150.0, -34.3
M_PER_DEG_LON, M_PER_DEG_LAT = 92_100.0, 110_900.0


def _timestamp(p):
    return dt.datetime.fromisoformat(p["date"]).replace(
        tzinfo=dt.timezone.utc)


def _granules(p):
    """(zone, row) of every granule, zone by zone."""
    return [(z, i) for z in range(len(p["zones"]))
            for i in range(len(p["rows_y0"]))]


def at_res(p, b):
    """The parameters of band b: the archive's, at the band's
    resolution where it names one."""
    res = p["bands"][b].get("resolution")
    return dict(p, **p["resolutions"][res]) if res else p


def _corner(p, z, i):
    """Outer north-west corner of granule (z, i) in its zone's CRS."""
    return p["zones"][z]["x0"], p["rows_y0"][i]


@functools.lru_cache(maxsize=16)
def _lattice(crs, x0, y0, res, H, W):
    """(lon, lat) float64 (gh, gw) of every LATTICE-th pixel centre of a
    granule, and of one node past its last row and column."""
    from gsky_tpu.geo.crs import parse_crs
    xs = x0 + (np.arange((W - 1) // LATTICE + 2) * LATTICE + 0.5) * res
    ys = y0 - (np.arange((H - 1) // LATTICE + 2) * LATTICE + 0.5) * res
    X, Y = np.meshgrid(xs, ys)
    lon, lat = parse_crs(crs).to_lonlat(X, Y)
    return np.asarray(lon), np.asarray(lat)


def lonlat(p, z, i):
    """`_lattice` of granule (z, i)."""
    x0, y0 = _corner(p, z, i)
    return _lattice(p["zones"][z]["crs"], x0, y0, p["res"],
                    *p["granule_hw"])


def field(lon, lat, base, ph):
    """The band's smooth reflectance at (lon, lat): periods of ~45 km
    east-west, ~33 km north-south and ~15 km diagonally, as
    `sentinel2_granules` draws them in UTM metres."""
    x = (lon - LON0) * M_PER_DEG_LON
    y = (lat - LAT0) * M_PER_DEG_LAT
    return (base + 900.0 * np.cos(2 * np.pi * y / 33000 + ph[0])
            * np.sin(2 * np.pi * x / 45000 + ph[1])
            + 200.0 * np.sin(2 * np.pi * (x + y) / 15000 + ph[2]))


def band(p, seed, z, i, b):
    """(H, W) int16 of band index b of granule (z, i), at its
    resolution."""
    p = at_res(p, b)
    H, W = p["granule_hw"]
    rng = np.random.default_rng([seed, b])
    ph = rng.uniform(0, 2 * np.pi, 3)
    nodes = field(*lonlat(p, z, i), p["bands"][b]["base"], ph) \
        .astype(np.float32)
    # the noise: -1, 0 or 1 for each row and for each column of the
    # zone's grid (rows counted from the first row's northern edge)
    nrng = np.random.default_rng([seed, b, z])
    top = int(round((p["rows_y0"][0] - p["rows_y0"][-1]) / p["res"]))
    noise_y = nrng.integers(-1, 2, top + H).astype(np.float32)
    noise_x = nrng.integers(-1, 2, W).astype(np.float32)
    iy0 = int(round((p["rows_y0"][0] - p["rows_y0"][i]) / p["res"]))
    # a pixel's place between its two lattice columns: each lattice
    # cell's LATTICE columns are one broadcast, no gather
    g = (np.arange(LATTICE) / LATTICE).astype(np.float32)
    d = np.empty((H, W), np.int16)
    for r in range(0, H, 512):
        n = min(512, H - r)
        t = (r + np.arange(n)) / LATTICE
        k = np.floor(t).astype(int)
        f = (t - k).astype(np.float32)[:, None]
        rows = nodes[k] * (1 - f) + nodes[k + 1] * f
        v = rows[:, :-1, None] * (1 - g) + rows[:, 1:, None] * g
        v = v.reshape(n, -1)[:, :W]
        v += noise_y[iy0 + r:iy0 + r + n, None] + noise_x[None, :]
        np.copyto(d[r:r + n], v, casting="unsafe")
    # the swath edge: a triangle along the edge that faces the other
    # zone, widening southwards, where the other zone's granule of the
    # row reaches as far south or further
    t = (np.arange(H) + 0.5) / H
    east = p["zones"][z]["wedge_edge"] == "east"
    for r, k in enumerate(np.ceil(p["wedge_px"] * t).astype(int)):
        if east:
            d[r, W - k:] = p["nodata"]
        else:
            d[r, :k] = p["nodata"]
    return d


def dates(p):
    return [_timestamp(p).strftime("%Y-%m-%dT%H:%M:%S.000Z")]


def granule_lonlat(p, z, i, n=33):
    """(lon, lat) of n points along each edge of granule (z, i)."""
    from gsky_tpu.geo.crs import parse_crs
    H, W = p["granule_hw"]
    x0, y0 = _corner(p, z, i)
    x1, y1 = x0 + W * p["res"], y0 - H * p["res"]
    t = np.linspace(0.0, 1.0, n)
    ex = np.concatenate([x0 + t * (x1 - x0), np.full(n, x1),
                         x1 - t * (x1 - x0), np.full(n, x0)])
    ey = np.concatenate([np.full(n, y0), y0 + t * (y1 - y0),
                         np.full(n, y1), y1 - t * (y1 - y0)])
    lon, lat = parse_crs(p["zones"][z]["crs"]).to_lonlat(ex, ey)
    return np.asarray(lon), np.asarray(lat)


def strip(p):
    """(lon_w, lat_s, lon_e, lat_n): the box every zone's column of
    granules covers, the strip where a map tile reads granules of both
    zones.  Its west edge is the easternmost west edge of any granule,
    its east edge the westernmost east edge; north and south alike."""
    west, east, north, south = [], [], [], []
    H, W = p["granule_hw"]
    for z, i in _granules(p):
        lon, lat = granule_lonlat(p, z, i)
        n = len(lon) // 4
        north.append(lat[:n].min())
        east.append(lon[n:2 * n].min())
        south.append(lat[2 * n:3 * n].max())
        west.append(lon[3 * n:].max())
    rows = len(p["rows_y0"])
    # a column's rows overlap, so its north is its first row's and its
    # south its last row's
    north = min(north[k] for k in range(0, len(north), rows))
    south = max(south[k] for k in range(rows - 1, len(south), rows))
    return max(west), south, min(east), north


def extent(p):
    """(crs, xmin, ymin, xmax, ymax) of the strip, in EPSG:4326: where
    the cell's sessions look."""
    w, s, e, n = strip(p)
    return ("EPSG:4326", w, s, e, n)


def _rasters(p):
    """(z, i, b) in the order `build` crawls and `sources` lists."""
    return [(z, i, b) for z, i in _granules(p)
            for b in range(len(p["bands"]))]


def sources(p, seed):
    """What the reference reads: the same arrays, made from the seed,
    each in its own zone's CRS."""
    out = []
    for z, i, b in _rasters(p):
        x0, y0 = _corner(p, z, i)
        q = at_res(p, b)
        out.append(Source(
            namespace=p["bands"][b]["namespace"],
            timestamp=_timestamp(p).timestamp(), crs=p["zones"][z]["crs"],
            x0=x0, y0=y0, dx=q["res"], dy=-q["res"],
            shape=tuple(q["granule_hw"]), nodata=float(p["nodata"]),
            read=_once(lambda z=z, i=i, b=b: band(p, seed, z, i, b))))
    return out


def _once(make):
    box = []

    def read():
        if not box:
            box.append(make())
        return box[0]
    return read


def build(p, seed, root):
    """Write the collection under root/<collection>/ and return its
    crawl records, `WRITERS` rasters at a time."""
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index.crawler import extract_geotiff
    from gsky_tpu.io import write_geotiff

    coll = os.path.join(root, p["collection"])
    os.makedirs(coll)

    def one(zib):
        z, i, b = zib
        zone = p["zones"][z]
        x0, y0 = _corner(p, z, i)
        spec = p["bands"][b]
        path = os.path.join(
            coll, f"{zone['file_prefix']}_R{i}_{_timestamp(p):%Y%m%d}"
                  f"_{spec['name']}.tif")
        res = at_res(p, b)["res"]
        write_geotiff(path, band(p, seed, z, i, b),
                      GeoTransform(x0, res, 0.0, y0, 0.0, -res),
                      parse_crs(zone["crs"]), nodata=p["nodata"],
                      compress=p["compress"])
        return extract_geotiff(path, namespace=spec["namespace"])

    with ThreadPoolExecutor(WRITERS) as ex:
        return list(ex.map(one, _rasters(p)))
