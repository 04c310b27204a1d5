"""The plain reference a WCS GetCoverage export is held to.

Straight numpy in float64: every output pixel's centre is projected
exactly (`reference.project`: the projection formulas are the one thing
borrowed, as in `reference.py`), no control grid, no gather window, no
tile split, no stack, no float32 anywhere.  It imports nothing from
`gsky_tpu.ops`, `gsky_tpu.pipeline`, `gsky_tpu.server` or `gsky_tpu.io`:
the served file is read by the small TIFF reader below (struct + zlib),
so a fault in the program's writer shows as a mismatch too.  The source
rasters are the archive module's, made from the seed.

The rule, as the configuration guarantees it (`configs/landsat8-export.json`):
an export pixel is the cubic convolution of the scene of TIME at the
pixel centre's position, Catmull-Rom weights (a = -0.5) over the 4 x 4
source pixels around it.  A tap that lies off the raster or holds nodata
drops out of numerator AND denominator (the weights are renormalised
over what is left), and the pixel holds data where the weights left sum
to more than 0.05; a position outside the raster's outer edge holds
none.  Where it holds none the file carries the export's nodata value.

Departures from GDAL's `cubic` (what upstream's `warp.go` calls), each
the program's documented rule (`ops/warp.py::_resample_c`):
- GDAL stretches the kernel by the source-to-output pixel ratio when it
  shrinks an image (anti-aliasing, since GDAL 2.0); here the kernel is 4
  x 4 source pixels at every scale, 0.7 to 1.4 source pixels a pixel in
  the cell;
- GDAL keeps a pixel whose valid weights sum to more than 1e-6 of the
  kernel; here the sum must pass 0.05, because Catmull-Rom's negative
  lobes can cancel and a quotient over a sum near zero is noise;
- GDAL tests the source window's validity mask; here validity is a
  function of the stored value (finite and not the nodata value), which
  is the same thing for this archive.
"""

import struct
import zlib

import numpy as np

from . import reference

WEIGHT_MIN = 0.05       # the valid taps' weights must sum to more
ROW_BLOCK = 256         # output rows computed at a time


def cubic_weights(f):
    """Catmull-Rom (a = -0.5) weights of the taps at offsets -1, 0, 1, 2
    for a fraction f in [0, 1)."""
    a = -0.5
    f2, f3 = f * f, f * f * f
    return (a * (f3 - 2 * f2 + f),
            (a + 2) * f3 - (a + 3) * f2 + 1,
            -(a + 2) * f3 + (2 * a + 3) * f2 - a * f,
            a * (f2 - f3))


def tap_cubic(data, nodata, col, row):
    """(values, valid) at corner-based pixel coordinates (pixel k spans
    [k, k + 1)): the rule of the module's docstring."""
    H, W = data.shape
    finite = np.isfinite(col) & np.isfinite(row)
    inside = finite & (col >= 0) & (col <= W) & (row >= 0) & (row <= H)
    c = np.where(finite, col - 0.5, 0.0)
    r = np.where(finite, row - 0.5, 0.0)
    c0, r0 = np.floor(c), np.floor(r)
    wc, wr = cubic_weights(c - c0), cubic_weights(r - r0)
    acc = np.zeros(c.shape)
    wacc = np.zeros(c.shape)
    for dr in range(4):
        ri = (r0 + (dr - 1)).astype(np.int64)
        for dc in range(4):
            ci = (c0 + (dc - 1)).astype(np.int64)
            inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
            v = data[np.clip(ri, 0, H - 1), np.clip(ci, 0, W - 1)]
            ok = inb & reference._valid_values(v, nodata)
            w = wr[dr] * wc[dc]
            acc += np.where(ok, w * v.astype(np.float64), 0.0)
            wacc += np.where(ok, w, 0.0)
    ok = inside & (wacc > WEIGHT_MIN)
    return acc / np.where(wacc > WEIGHT_MIN, wacc, 1.0), ok


TAPS = dict(reference.TAPS, cubic=tap_cubic)


def centres(bbox, width, height, rows, cols):
    """(X, Y) of the centres of output pixels (rows, cols), index arrays
    that broadcast against each other; row 0 is the northern edge."""
    xmin, ymin, xmax, ymax = bbox
    X = xmin + (np.asarray(cols, np.float64) + 0.5) * ((xmax - xmin) / width)
    Y = ymax - (np.asarray(rows, np.float64) + 0.5) * ((ymax - ymin) / height)
    return np.broadcast_arrays(X, Y)


def resample_at(source, X, Y, crs, method="cubic", data=None):
    """(values, valid) of one `reference.Source` at positions (X, Y) of
    `crs`.  `data`: the raster to read instead of `source.read()` (the
    tests hand in a degraded one)."""
    sx, sy = reference.project(X, Y, crs, source.crs)
    col = (sx - source.x0) / source.dx
    row = (sy - source.y0) / source.dy
    return TAPS[method](source.read() if data is None else data,
                        source.nodata, col, row)


def render(source, bbox, crs, width, height, method="cubic", data=None):
    """(values (height, width) float64, valid bool): the whole export,
    computed ROW_BLOCK rows at a time so that 4096 x 4096 fits (a block
    of 256 x 4096 holds ~0.4 GB of float64 temporaries)."""
    out = np.zeros((height, width))
    valid = np.zeros((height, width), bool)
    cols = np.arange(width)[None, :]
    for r0 in range(0, height, ROW_BLOCK):
        rows = np.arange(r0, min(r0 + ROW_BLOCK, height))[:, None]
        X, Y = centres(bbox, width, height, rows, cols)
        v, ok = resample_at(source, X, Y, crs, method, data)
        out[rows[0, 0]:rows[-1, 0] + 1] = v
        valid[rows[0, 0]:rows[-1, 0] + 1] = ok
    return out, valid


def compare(got, nodata, want, want_valid, tol):
    """How a served plane differs from the reference's: `mismatch`, the
    share of pixels whose validity differs or whose value differs by
    more than `tol`; of it `validity_mismatch`; and the largest
    difference where both hold data."""
    got_valid = got != nodata
    both = got_valid & want_valid
    err = np.abs(got.astype(np.float64) - want)
    far = both & (err > tol)
    n = max(got.size, 1)
    return {"mismatch": float(((got_valid != want_valid) | far).sum() / n),
            "validity_mismatch": float((got_valid != want_valid).sum() / n),
            "max_abs_err": float(err[both].max()) if both.any() else 0.0,
            "data_fraction": float(want_valid.mean())}


# -- the served file ---------------------------------------------------------

_TYPES = {1: "B", 2: "c", 3: "H", 4: "I", 5: "II", 11: "f", 12: "d"}


def read_geotiff(body):
    """(array, tags) of a classic little-endian TIFF as the program
    writes its exports: one IFD, tiled, deflate or none, float32, chunky
    with any number of bands, georeferenced by a pixel scale and one
    tie point.  array: (bands, H, W) float32.  tags: width, height,
    tile, bands, compression, pixel_scale (3), tiepoint (6), nodata
    (text) and geokeys {key id: value}.  Anything else is an error: the
    check holds the file to this form."""
    if body[:4] != b"II*\0":
        raise ValueError("not a little-endian classic TIFF")
    off, = struct.unpack_from("<I", body, 4)
    n, = struct.unpack_from("<H", body, off)
    raw = {}
    for i in range(n):
        tag, typ, cnt = struct.unpack_from("<HHI", body, off + 2 + 12 * i)
        code = _TYPES[typ]
        size = struct.calcsize("<" + code) * cnt
        at = off + 2 + 12 * i + 8
        if size > 4:
            at, = struct.unpack_from("<I", body, at)
        vals = struct.unpack_from("<" + code * cnt, body, at)
        raw[tag] = b"".join(vals).rstrip(b"\0").decode("latin-1") \
            if typ == 2 else vals
    W, H = raw[256][0], raw[257][0]
    bands = raw.get(277, (1,))[0]
    tw, th = raw[322][0], raw[323][0]
    comp = raw.get(259, (1,))[0]
    if raw[258] != (32,) * bands or raw.get(339, (1,)) != (3,) * bands:
        raise ValueError(f"samples are not float32: bits {raw[258]}, "
                         f"format {raw.get(339)}")
    if comp not in (1, 8, 32946) or raw.get(284, (1,))[0] != 1 \
            or raw.get(317, (1,))[0] != 1:
        raise ValueError(f"compression {comp}, planar {raw.get(284)}, "
                         f"predictor {raw.get(317)}: not read here")
    nx, ny = -(-W // tw), -(-H // th)
    out = np.zeros((bands, ny * th, nx * tw), np.float32)
    for k, (o, c) in enumerate(zip(raw[324], raw[325])):
        blob = body[o:o + c]
        if comp != 1:
            blob = zlib.decompress(blob)
        block = np.frombuffer(blob, "<f4").reshape(th, tw, bands)
        ty, tx = divmod(k, nx)
        out[:, ty * th:(ty + 1) * th, tx * tw:(tx + 1) * tw] = \
            np.moveaxis(block, -1, 0)
    keys = raw.get(34735, ())
    geokeys = {keys[i]: keys[i + 3] for i in range(4, len(keys), 4)
               if keys[i + 1] == 0}
    return out[:, :H, :W], {
        "width": W, "height": H, "tile": (tw, th), "bands": bands,
        "compression": comp, "pixel_scale": raw.get(33550),
        "tiepoint": raw.get(33922), "nodata": raw.get(42113),
        "geokeys": geokeys}


def georeferencing_problems(tags, bbox, width, height, epsg, nodata):
    """Reasons why the file's tags do not say what was asked for: size,
    pixel scale and tie point give the requested bbox (to 1e-9 of a
    pixel), the CRS code, the nodata value."""
    out = []
    xmin, ymin, xmax, ymax = bbox
    if (tags["width"], tags["height"]) != (width, height):
        out.append(f"size {tags['width']}x{tags['height']}, "
                   f"asked {width}x{height}")
    px, py = (xmax - xmin) / width, (ymax - ymin) / height
    scale, tie = tags["pixel_scale"], tags["tiepoint"]
    if not scale or abs(scale[0] - px) > 1e-9 * px \
            or abs(scale[1] - py) > 1e-9 * py:
        out.append(f"pixel scale {scale}, asked ({px!r}, {py!r})")
    if not tie or tie[:3] != (0.0, 0.0, 0.0) \
            or abs(tie[3] - xmin) > 1e-9 * px \
            or abs(tie[4] - ymax) > 1e-9 * py:
        out.append(f"tie point {tie}, asked pixel (0, 0) at "
                   f"({xmin!r}, {ymax!r})")
    want_key = 2048 if epsg == 4326 else 3072
    if tags["geokeys"].get(want_key) != epsg:
        out.append(f"geokeys {tags['geokeys']} do not name EPSG:{epsg}")
    try:
        if float(tags["nodata"]) != nodata:
            raise ValueError
    except (TypeError, ValueError):
        out.append(f"nodata tag {tags['nodata']!r}, not {nodata}")
    return out
