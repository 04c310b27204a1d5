"""The plain reference the served answers are held to.

Straight numpy in float64, no cache, no control grid, no buckets.  It
imports nothing from `gsky_tpu.ops`, `gsky_tpu.pipeline` or the
executor.  It does use two things that are not its own, and says so:

- `gsky_tpu.geo.crs` point transforms (the projection formulas; the
  program projects a 16-px control grid with them and interpolates in
  between, the reference projects every pixel centre);
- the source rasters as the archive module made them from the seed (it
  never reads the files the server reads, so a fault in the program's
  writers or readers shows as a mismatch).

Semantics, as docs and upstream state them: a tile pixel is the source
pixel that holds the projected centre of the output pixel (nearest) or
the validity-weighted mean of the four around it (bilinear); where
several scenes of one namespace are valid the newest wins; the value is
clipped to [0, clip], scaled and floored to a byte 0..254, and 255 means
no data.  A drill row is the mean over the polygon's valid pixels of one
timestep, and an empty field where the polygon touches no valid pixel.
"""

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np


@dataclass
class Source:
    """One raster of one timestep: north-up, `x0`/`y0` its outer corner."""
    namespace: str
    timestamp: float            # unix seconds
    crs: str                    # "EPSG:32755"
    x0: float
    y0: float
    dx: float
    dy: float                   # negative: rows run south
    shape: Tuple[int, int]
    nodata: float
    read: Callable[[], np.ndarray]      # (H, W), made lazily from the seed


def select(sources, namespace, time, accum_from: Optional[float] = None):
    """The scenes a layer selects for TIME, as the configuration's first
    guarantee states it (configs/landsat8-mosaic.json, with its source):
    the scene whose timestamp is TIME; for an accumulating layer those
    of [accum_from, TIME), end-exclusive, and the first date's scene
    alone when asked for the first date."""
    point = accum_from is None or accum_from == time
    return [s for s in sources if s.namespace == namespace and (
        abs(s.timestamp - time) < 1.0 if point
        else accum_from <= s.timestamp < time)]


def pixel_centres(bbox, width, height):
    """(X, Y) float64 grids of the output pixels' centres; row 0 is the
    northern edge."""
    xmin, ymin, xmax, ymax = bbox
    xs = xmin + (np.arange(width, dtype=np.float64) + 0.5) \
        * ((xmax - xmin) / width)
    ys = ymax - (np.arange(height, dtype=np.float64) + 0.5) \
        * ((ymax - ymin) / height)
    return np.meshgrid(xs, ys)


def project(X, Y, from_crs, to_crs):
    from gsky_tpu.geo.crs import parse_crs      # point transforms only
    a, b = parse_crs(from_crs), parse_crs(to_crs)
    if a == b:
        return X, Y
    return a.transform_to(b, X, Y)


def _valid_values(data, nodata):
    ok = np.isfinite(data)
    if not np.isnan(nodata):
        ok &= data != nodata
    return ok


def tap_nearest(data, nodata, col, row):
    """col/row: corner-based pixel coordinates (pixel k spans [k, k+1))."""
    H, W = data.shape
    with np.errstate(invalid="ignore"):
        ci = np.floor(col).astype(np.int64)
        ri = np.floor(row).astype(np.int64)
    inb = np.isfinite(col) & np.isfinite(row) \
        & (ci >= 0) & (ci < W) & (ri >= 0) & (ri < H)
    ci = np.clip(ci, 0, W - 1)
    ri = np.clip(ri, 0, H - 1)
    v = data[ri, ci]
    return v.astype(np.float64), inb & _valid_values(v, nodata)


def tap_bilinear(data, nodata, col, row):
    """Mean of the four pixels around the point weighted by distance
    and by validity; invalid where no valid weight is left or the point
    lies outside the raster."""
    H, W = data.shape
    c = col - 0.5
    r = row - 0.5
    finite = np.isfinite(c) & np.isfinite(r)
    inside = finite & (col >= 0) & (col <= W) & (row >= 0) & (row <= H)
    c = np.where(finite, c, 0.0)
    r = np.where(finite, r, 0.0)
    c0 = np.floor(c)
    r0 = np.floor(r)
    fc, fr = c - c0, r - r0
    acc = np.zeros(c.shape)
    wacc = np.zeros(c.shape)
    for dr in (0, 1):
        for dc in (0, 1):
            ri = (r0 + dr).astype(np.int64)
            ci = (c0 + dc).astype(np.int64)
            w = (fr if dr else 1 - fr) * (fc if dc else 1 - fc)
            inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
            v = data[np.clip(ri, 0, H - 1), np.clip(ci, 0, W - 1)]
            ok = inb & _valid_values(v, nodata)
            acc += np.where(ok, w * v.astype(np.float64), 0.0)
            wacc += np.where(ok, w, 0.0)
    ok = inside & (wacc > 1e-6)
    return acc / np.where(wacc > 1e-6, wacc, 1.0), ok


TAPS = {"near": tap_nearest, "nearest": tap_nearest,
        "bilinear": tap_bilinear}


def mosaic(sources, bbox, crs, width, height, method="near"):
    """(values, valid): per pixel the newest valid scene's tap."""
    X, Y = pixel_centres(bbox, width, height)
    out = np.zeros((height, width))
    valid = np.zeros((height, width), bool)
    # oldest first, so that a newer scene overwrites
    for s in sorted(sources, key=lambda s: s.timestamp):
        sx, sy = project(X, Y, crs, s.crs)
        col = (sx - s.x0) / s.dx
        row = (sy - s.y0) / s.dy
        v, ok = TAPS[method](s.read(), s.nodata, col, row)
        out = np.where(ok, v, out)
        valid |= ok
    return out, valid


def scale_byte(values, valid, offset, scale, clip):
    """Clip to [0, clip], scale, floor to 0..254; 255 = no data.  The
    product is taken in float32 as the deployed scaler does, so that a
    value on a byte's edge falls to the same side."""
    if not scale:
        scale = 254.0 / clip if clip else 1.0
    v = np.float32(values) + np.float32(offset)
    v = np.maximum(np.minimum(v, np.float32(clip)), np.float32(0))
    b = np.clip(np.floor(v * np.float32(scale)), 0, 254).astype(np.uint8)
    return np.where(valid, b, np.uint8(255))


def render_tile(sources, bbox, crs, width, height, method, offset, scale,
                clip):
    v, ok = mosaic(sources, bbox, crs, width, height, method)
    return scale_byte(v, ok, offset, scale, clip)


def palette_ramp(colours):
    """256 x RGBA, linear between the given colours in equal sections;
    index 255 transparent.  Float arithmetic: the deployed ramp
    truncates integers, so compare with a tolerance of one level."""
    cols = np.array([[c["R"], c["G"], c["B"], c.get("A", 255)]
                     for c in colours], np.float64)
    bins = len(cols) - 1
    pos = np.arange(256) / (256 / bins)
    lo = np.minimum(pos.astype(int), bins - 1)
    t = (pos - lo)[:, None]
    ramp = cols[lo] * (1 - t) + cols[lo + 1] * t
    ramp[:, 3] = cols[lo, 3]
    ramp[255] = 0
    return ramp


def burn_rectangle(shape, r0, r1, c0, c1):
    """Mask of a rectangle whose edges run through the centres of
    pixel rows r0, r1 and columns c0, c1: every pixel it touches."""
    m = np.zeros(shape, bool)
    m[r0:r1 + 1, c0:c1 + 1] = True
    return m


def drill_means(stack, mask, nodata):
    """stack (T, h, w), mask (h, w) -> (mean, count) per timestep over
    the masked pixels that hold data; mean 0 where none does."""
    ok = _valid_values(stack, nodata) & mask[None]
    n = ok.reshape(len(stack), -1).sum(-1)
    s = np.where(ok, stack, 0).reshape(len(stack), -1) \
        .sum(-1, dtype=np.float64)
    return s / np.maximum(n, 1), n


def footprint_holds_data(cols, rows, nodata_below):
    """Does the all-touched burn of the polygon with corners (cols,
    rows), in corner-based pixel coordinates, hold a pixel outside the
    raster's nodata block (rows < nodata_below[0] and columns <
    nodata_below[1])?  False: every corner lies a pixel or more inside
    the block, and so does all of a polygon.  True: a corner lies a
    pixel or more outside it, and any burn takes the pixel under a
    corner.  None: within a pixel of the block's edge it depends on how
    a burn takes a pixel that the outline only grazes, which this
    reference does not decide."""
    er, ec = nodata_below
    cols, rows = np.asarray(cols, float), np.asarray(rows, float)
    if np.all((cols <= ec - 1) & (rows <= er - 1)):
        return False
    if np.any((cols >= ec + 1) | (rows >= er + 1)):
        return True
    return None
