"""Archive kind `geotiff_scenes`: overlapping single-band scenes of one
sensor on consecutive days, one tiled GeoTIFF each, one namespace.

Parameters (the configuration's "archive" group): crs, origin [x, y] of
scene 0's outer corner, res, scene_hw, scenes, shift_m (metres by which
each next scene lies further east and south), first_date,
step_days, namespace, nodata, nodata_corner (fraction of each edge that
is nodata in the north-west corner), compress, collection.

Each band is imagery-like and not white noise: a smooth field whose
phases are drawn per scene, so that every scene differs and a wrong
mosaic winner shows, plus +-2 DN of sensor noise (<= ~11 DN between
neighbours).  At 7.6k px the program's f32 source coordinate resolves
~1e-3 px; on white noise that alone moves 1 % of bytes (PERF.md,
PR 21).  Scene k is drawn from the generator seeded [seed, k], so the
reference can make any scene again without the others.
"""

import datetime as dt
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..reference import Source


def _timestamp(p, k):
    d = dt.datetime.fromisoformat(p["first_date"]).replace(
        tzinfo=dt.timezone.utc) + dt.timedelta(days=k * p["step_days"])
    return d


def _corner(p, k):
    return (p["origin"][0] + k * p["shift_m"][0],
            p["origin"][1] - k * p["shift_m"][1])


def band(p, seed, k):
    H, W = p["scene_hw"]
    rng = np.random.default_rng([seed, k])
    ph = rng.uniform(0, 2 * np.pi, 4)
    yy = np.arange(H, dtype=np.float64)
    xx = np.arange(W, dtype=np.float64)
    w1x, w1y, w2 = 2 * np.pi / 1500, 2 * np.pi / 1100, 2 * np.pi / 500
    # 1600 + 900 cos(y) sin(x) + 200 sin(y2 + x2): a rank-3 product
    rows = np.stack([900.0 * np.cos(yy * w1y + ph[0]),
                     200.0 * np.cos(yy * w2 + ph[2]),
                     200.0 * np.sin(yy * w2 + ph[2])], 1)
    cols = np.stack([np.sin(xx * w1x + ph[1]),
                     np.sin(xx * w2 + ph[3]),
                     np.cos(xx * w2 + ph[3])], 0)
    f = rows.astype(np.float32) @ cols.astype(np.float32)
    f += np.float32(1600.0)
    d = f.astype(np.int16)
    d += rng.integers(-2, 3, (H, W), dtype=np.int16)
    e = p["nodata_corner"]
    d[: int(H * e), : int(W * e)] = p["nodata"]
    return d


def dates(p):
    return [_timestamp(p, k).strftime("%Y-%m-%dT%H:%M:%S.000Z")
            for k in range(p["scenes"])]


def extent(p):
    """(crs, xmin, ymin, xmax, ymax) over all scenes, in their CRS."""
    H, W = p["scene_hw"]
    xs, ys = zip(*(_corner(p, k) for k in range(p["scenes"])))
    return (p["crs"], min(xs), min(ys) - H * p["res"],
            max(xs) + W * p["res"], max(ys))


def sources(p, seed):
    """What the reference reads: the same arrays, made from the seed."""
    out = []
    for k in range(p["scenes"]):
        x0, y0 = _corner(p, k)
        out.append(Source(
            namespace=p["namespace"], timestamp=_timestamp(p, k).timestamp(),
            crs=p["crs"], x0=x0, y0=y0, dx=p["res"], dy=-p["res"],
            shape=tuple(p["scene_hw"]), nodata=float(p["nodata"]),
            read=_once(lambda k=k: band(p, seed, k))))
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
    crawl records.  Drawing and deflating run in threads (numpy and zlib
    drop the GIL)."""
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index.crawler import extract_geotiff
    from gsky_tpu.io import write_geotiff

    crs = parse_crs(p["crs"])
    coll = os.path.join(root, p["collection"])
    os.makedirs(coll)

    def one(k):
        x0, y0 = _corner(p, k)
        path = os.path.join(
            coll, f"{p['file_prefix']}_{_timestamp(p, k):%Y%m%d}_T1.tif")
        write_geotiff(path, band(p, seed, k),
                      GeoTransform(x0, p["res"], 0.0, y0, 0.0, -p["res"]),
                      crs, nodata=p["nodata"], compress=p["compress"])
        return extract_geotiff(path, namespace=p["namespace"])

    with ThreadPoolExecutor(p["scenes"]) as ex:
        return list(ex.map(one, range(p["scenes"])))
