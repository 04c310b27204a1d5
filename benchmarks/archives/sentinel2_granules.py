"""Archive kind `sentinel2_granules`: adjacent MGRS granules of one UTM
zone and one sensing date, one single-band GeoTIFF per granule and band,
each band its own namespace.

Parameters (the configuration's "archive" group): crs, origin [x, y] of
the north-west granule's outer corner, res, granule_hw, pitch_m (corner
to corner: 100 km against a 109.8 km granule, so neighbours overlap by
9.8 km on one pixel grid), grid [rows, cols], date, bands [{"name",
"namespace", "base"}], nodata, wedge_px, compress, collection,
file_prefix.

The imagery is a function of absolute UTM coordinates, so overlapping
pixels are identical in both granules, as they are in the product: a
smooth field per band (phases drawn from [seed, band]) plus +-2 DN of
noise keyed by the absolute row and column.  Each granule lacks a wedge of
its own, a swath edge: a triangle along the edge that faces its east or
west neighbour, at most `wedge_px` wide and so inside the overlap, where
the neighbour holds data.  A mosaic that drops a granule shows there.
All granules carry one timestamp, as one datatake does.  Any raster is
made again from the seed without the others.
"""

import datetime as dt
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..reference import Source

WRITERS = 4     # a band is ~1 GB of host memory while it is made


def _timestamp(p):
    return dt.datetime.fromisoformat(p["date"]).replace(
        tzinfo=dt.timezone.utc)


def _corner(p, i, j):
    """Outer north-west corner of the granule in grid row i, column j."""
    return (p["origin"][0] + j * p["pitch_m"],
            p["origin"][1] - i * p["pitch_m"])


def _granules(p):
    rows, cols = p["grid"]
    return [(i, j) for i in range(rows) for j in range(cols)]


def band(p, seed, i, j, b):
    """(H, W) int16 of band index b of granule (i, j)."""
    H, W = p["granule_hw"]
    step = int(round(p["pitch_m"] / p["res"]))
    # absolute pixel indices on the zone's grid, shared by all granules
    iy = i * step + np.arange(H)
    ix = j * step + np.arange(W)
    rng = np.random.default_rng([seed, b])
    ph = rng.uniform(0, 2 * np.pi, 4)
    # the noise: -1, 0 or 1 for each row and for each column of the
    # zone's grid, so +-2 DN a pixel and the same in every granule
    noise_y = rng.integers(-1, 2, (p["grid"][0] - 1) * step + H)
    noise_x = rng.integers(-1, 2, (p["grid"][1] - 1) * step + W)
    yy, xx = iy.astype(np.float64), ix.astype(np.float64)
    # periods in pixels: the same ~45 km, ~33 km and ~15 km in metres
    # as `geotiff_scenes` draws at 30 m
    w1x, w1y, w2 = 2 * np.pi / 4500, 2 * np.pi / 3300, 2 * np.pi / 1500
    one = np.ones_like(yy)
    # base + 900 cos(y) sin(x) + 200 sin(y2 + x2) + noise: a rank-5
    # product, made a block of rows at a time into one buffer (a fresh
    # 480 MB temporary costs more than the arithmetic)
    rows = np.stack([900.0 * np.cos(yy * w1y + ph[0]),
                     200.0 * np.cos(yy * w2 + ph[2]),
                     200.0 * np.sin(yy * w2 + ph[2]),
                     p["bands"][b]["base"] + noise_y[iy], one],
                    1).astype(np.float32)
    cols = np.stack([np.sin(xx * w1x + ph[1]),
                     np.sin(xx * w2 + ph[3]),
                     np.cos(xx * w2 + ph[3]),
                     one, noise_x[ix]], 0).astype(np.float32)
    d = np.empty((H, W), np.int16)
    buf = np.empty((512, W), np.float32)
    for r in range(0, H, len(buf)):
        n = min(len(buf), H - r)
        np.matmul(rows[r:r + n], cols, out=buf[:n])
        np.copyto(d[r:r + n], buf[:n], casting="unsafe")
    # the swath edge: west-column granules lack a triangle along their
    # east edge that widens southwards, the others one along their west
    # edge that widens northwards
    t = (np.arange(H) + 0.5) / H
    for r, k in enumerate(np.ceil(p["wedge_px"] * (t if j % 2 == 0
                                                   else 1 - t)).astype(int)):
        if j % 2 == 0:
            d[r, W - k:] = p["nodata"]
        else:
            d[r, :k] = p["nodata"]
    return d


def dates(p):
    return [_timestamp(p).strftime("%Y-%m-%dT%H:%M:%S.000Z")]


def extent(p):
    """(crs, xmin, ymin, xmax, ymax) over all granules, in their CRS."""
    H, W = p["granule_hw"]
    rows, cols = p["grid"]
    x0, y0 = _corner(p, 0, 0)
    x1, y1 = _corner(p, rows - 1, cols - 1)
    return (p["crs"], x0, y1 - H * p["res"], x1 + W * p["res"], y0)


def _rasters(p):
    """(i, j, b) in the order `build` crawls and `sources` lists."""
    return [(i, j, b) for i, j in _granules(p)
            for b in range(len(p["bands"]))]


def sources(p, seed):
    """What the reference reads: the same arrays, made from the seed."""
    out = []
    for i, j, b in _rasters(p):
        x0, y0 = _corner(p, i, j)
        out.append(Source(
            namespace=p["bands"][b]["namespace"],
            timestamp=_timestamp(p).timestamp(), crs=p["crs"],
            x0=x0, y0=y0, dx=p["res"], dy=-p["res"],
            shape=tuple(p["granule_hw"]), nodata=float(p["nodata"]),
            read=_once(lambda i=i, j=j, b=b: band(p, seed, i, j, b))))
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

    crs = parse_crs(p["crs"])
    coll = os.path.join(root, p["collection"])
    os.makedirs(coll)

    def one(ijb):
        i, j, b = ijb
        x0, y0 = _corner(p, i, j)
        spec = p["bands"][b]
        path = os.path.join(
            coll, f"{p['file_prefix']}_R{i}C{j}_{_timestamp(p):%Y%m%d}"
                  f"_{spec['name']}.tif")
        write_geotiff(path, band(p, seed, i, j, b),
                      GeoTransform(x0, p["res"], 0.0, y0, 0.0, -p["res"]),
                      crs, nodata=p["nodata"], compress=p["compress"])
        return extract_geotiff(path, namespace=spec["namespace"])

    with ThreadPoolExecutor(WRITERS) as ex:
        return list(ex.map(one, _rasters(p)))
