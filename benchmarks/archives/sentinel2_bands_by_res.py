"""Archive kind `sentinel2_bands_by_res`: `sentinel2_granules` with each
band at its own resolution, as the Sentinel-2 MSI product has them:
bands 2, 3, 4 and 8 at 10 m (10,980 x 10,980 px), bands 5, 6, 7, 8A, 11
and 12 at 20 m (5,490 x 5,490 px), each granule's rasters over the same
109.8 km square.

Parameters: those of `sentinel2_granules` (crs, origin, pitch_m, grid,
date, nodata, compress, collection, file_prefix), except that `res`,
`granule_hw` and `wedge_px` come from `resolutions`, a group per
resolution ({"r10m": {"res": 10.0, "granule_hw": [10980, 10980],
"wedge_px": 700}, ...}), and each band names its own (`"resolution":
"r10m"`).  A raster is `sentinel2_granules.band` at its resolution: the
imagery is a function of absolute coordinates on that resolution's grid
of the zone, and the swath-edge wedge is the same triangle in metres in
every band (700 px at 10 m, 350 px at 20 m), so along its edge the 10 m
and 20 m bands may lack data a pixel apart, as the product's do.
"""

import os
from concurrent.futures import ThreadPoolExecutor

from ..reference import Source
from . import sentinel2_granules as s2

dates = s2.dates


def at_res(p, b):
    """The `sentinel2_granules` parameters of band b: the archive's, at
    the band's resolution."""
    return dict(p, **p["resolutions"][p["bands"][b]["resolution"]])


def band(p, seed, i, j, b):
    """(H, W) int16 of band index b of granule (i, j), at its resolution."""
    return s2.band(at_res(p, b), seed, i, j, b)


def extent(p):
    """(crs, xmin, ymin, xmax, ymax) over all granules, in their CRS:
    every resolution covers the same ground."""
    return s2.extent(at_res(p, 0))


def _rasters(p):
    """(i, j, b) in the order `build` crawls and `sources` lists."""
    return [(i, j, b) for i, j in s2._granules(p)
            for b in range(len(p["bands"]))]


def sources(p, seed):
    """What the reference reads: the same arrays, made from the seed,
    each with its own pixel size and shape."""
    out = []
    for i, j, b in _rasters(p):
        q = at_res(p, b)
        x0, y0 = s2._corner(q, i, j)
        out.append(Source(
            namespace=q["bands"][b]["namespace"],
            timestamp=s2._timestamp(q).timestamp(), crs=q["crs"],
            x0=x0, y0=y0, dx=q["res"], dy=-q["res"],
            shape=tuple(q["granule_hw"]), nodata=float(q["nodata"]),
            read=s2._once(lambda i=i, j=j, b=b: band(p, seed, i, j, b))))
    return out


def build(p, seed, root):
    """Write the collection under root/<collection>/ and return its
    crawl records, `sentinel2_granules.WRITERS` rasters at a time."""
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index.crawler import extract_geotiff
    from gsky_tpu.io import write_geotiff

    crs = parse_crs(p["crs"])
    coll = os.path.join(root, p["collection"])
    os.makedirs(coll)

    def one(ijb):
        i, j, b = ijb
        q = at_res(p, b)
        x0, y0 = s2._corner(q, i, j)
        spec = q["bands"][b]
        path = os.path.join(
            coll, f"{q['file_prefix']}_R{i}C{j}_{s2._timestamp(q):%Y%m%d}"
                  f"_{spec['name']}.tif")
        write_geotiff(path, band(p, seed, i, j, b),
                      GeoTransform(x0, q["res"], 0.0, y0, 0.0, -q["res"]),
                      crs, nodata=q["nodata"], compress=q["compress"])
        return extract_geotiff(path, namespace=spec["namespace"])

    with ThreadPoolExecutor(s2.WRITERS) as ex:
        return list(ex.map(one, _rasters(p)))
