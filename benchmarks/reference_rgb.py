"""The plain reference for three-band (true-colour) tiles.

Float64 numpy on top of `reference.py`, which it imports and does not
copy; nothing from `gsky_tpu.ops`, `gsky_tpu.pipeline` or the executor.
Like `reference.py` it uses `gsky_tpu.geo.crs` point transforms and the
rasters as the archive module made them from the seed, never the files.

Per channel: the granules the layer selects for TIME in that channel's
namespace (`reference.select`), mosaicked by `reference.mosaic` with the
bilinear, validity-weighted tap (`reference.tap_bilinear`) and scaled to
a byte (`reference.scale_byte`: clip, scale, floor to 0..254, 255 = no
data); `reference.render_tile` is exactly that chain.

Among granules of one timestamp (one datatake) the one indexed later
wins where both are valid: `reference.mosaic` sorts by timestamp with a
stable sort and lets the later overwrite, the program ranks equal
timestamps "later arrival first" (`gsky_tpu/ops/mosaic.py::priority_order`),
and an archive module lists its sources in the order it crawls them.
Overlapping Sentinel-2 granules hold identical pixels, so the rule shows
only on the one-pixel line along a granule's nodata edge, where its
bilinear tap has lost a neighbour and the other granule's has not.

The RGBA rule.  A pixel is transparent (alpha 0) exactly where all three
channels lack data; where one or two lack data the pixel is opaque and
the lacking channel reads 255, the no-data byte.  Source: upstream's RGB
PNG encoder, `utils/ogc_encoders.go:82-142`, as `gsky_tpu/ops/warp.py::
render_rgba_ctrl` and `gsky_tpu/io/png.py::encode_png` (three planes)
both cite and implement it; `server/ows.py` sends the one-granule tile
through the first and the several-granule tile through the second.

Departures from upstream, each the program's too: bilinear weights by
validity (upstream's GDAL warper does the same with its per-band
validity mask); the product offset + clip + scale is taken in float32
(`reference.scale_byte` says why); pixel centres are projected one by
one where the program interpolates a 16-px control grid.
"""

import numpy as np

from .reference import render_tile, select


def select_rgb(sources, namespaces, time):
    """Per channel, the granules of that namespace whose timestamp is
    TIME (a `mas` time generator: one date, no accumulation)."""
    return [select(sources, ns, time) for ns in namespaces]


def render_rgba(channels, bbox, crs, width, height, method, offset, scale,
                clip):
    """(height, width, 4) uint8 from three lists of `reference.Source`,
    one per channel in R, G, B order."""
    planes = [render_tile(srcs, bbox, crs, width, height, method, offset,
                          scale, clip) for srcs in channels]
    rgb = np.stack(planes, axis=-1)
    alpha = np.where((rgb == 255).all(axis=-1), 0, 255).astype(np.uint8)
    return np.concatenate([rgb, alpha[..., None]], axis=-1)


def compare(got, want):
    """What a check records of a served RGBA tile against the
    reference: the share of bytes (all four channels) that differ, the
    share of pixels whose transparency differs, and the largest
    difference of a colour byte where both are opaque and hold data."""
    both = (got[..., :3] != 255) & (want[..., :3] != 255)
    diff = np.abs(got[..., :3].astype(int) - want[..., :3].astype(int))
    return {"mismatch": float(np.mean(got != want)),
            "alpha_mismatch": float(np.mean(got[..., 3] != want[..., 3])),
            "max_byte_diff": int(diff[both].max()) if both.any() else 0}
