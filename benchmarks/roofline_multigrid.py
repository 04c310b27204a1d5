"""The least the three-band kernel has to do where each granule's bands
lie on R pixel grids (see `roofline.py` for what counts: the bytes the
algorithm needs, whatever implements it).  Bound by memory bandwidth."""

import re


def leg_shape(leg_key):
    """(granule sets, grids a set, bands a set) of a
    `render_rgba_mg:((G, R, C), windows)` dispatch key, or None."""
    m = re.search(r":\(\((\d+), (\d+), (\d+)\),", leg_key)
    return tuple(int(g) for g in m.groups()) if m else None


def render_rgba_ctrl(n_granules=1, n_grids=2, n_bands=3, out_hw=(256, 256),
                     taps=4, step=16):
    """(ops, bytes) of one tile from the n_bands bands of each of n
    granules, the bands on n_grids pixel grids: every output pixel reads
    `taps` f32 values of each band of each granule, the two control
    grids once, a parameter row a grid and a priority a band of each
    granule once, and four bytes a pixel are written.  Operations: the
    control grid's upsampling (2 x 8) once; per granule and grid the
    affine and the bounds tests (~14) and the tap weights (~3 a tap)
    once for the grid's bands; per band the weighted sum (2 a tap) and
    the newest-wins pick (~3); per band the scaling (~6); the alpha rule
    (~3)."""
    h, w = out_hw
    px = h * w
    grid = 2 * (h // step + 1) * (w // step + 1) * 4
    nbytes = px * n_granules * n_bands * taps * 4 + grid \
        + n_granules * (11 * n_grids + n_bands) * 4 + px * 4
    ops = px * (16 + n_granules * (n_grids * (14 + 3 * taps)
                                   + n_bands * (2 * taps + 3))
                + n_bands * 6 + 3)
    return ops, nbytes
