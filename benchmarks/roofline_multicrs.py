"""The least the three-band kernel has to do where a tile's granule sets
lie in K CRSs, each set warped on its own CRS's control grid (see
`roofline.py` for what counts: the bytes the algorithm needs, whatever
implements it).  Bound by memory bandwidth."""

import re

from . import roofline_multigrid


def leg_shape(leg_key):
    """(granule sets, CRSs, grids a set, bands a set) of a
    `render_rgba_mc:((G, K, C), window)` or `render_rgba_mc:((G, K, R,
    C), windows)` dispatch key, or None."""
    m = re.search(r"_mc:\(\((\d+), (\d+), (\d+)(?:, (\d+))?\),", leg_key)
    if not m:
        return None
    g, k, a, b = m.groups()
    return (int(g), int(k)) + ((1, int(a)) if b is None
                               else (int(a), int(b)))


def render_rgba_ctrl(n_granules=2, n_crs=2, n_grids=1, n_bands=3,
                     out_hw=(256, 256), taps=4, step=16):
    """(ops, bytes) of one tile from the n_bands bands of each of n
    granule sets in n_crs CRSs: what `roofline_multigrid.render_rgba_ctrl`
    counts for the sets (the taps of every band of every set, a
    parameter row a grid and a priority a band of each set, four bytes
    a pixel written; `roofline_rgb.py`'s count on one grid), plus, for
    each CRS past the first, its control grid read once and upsampled
    (2 x 8 operations a pixel), and the set's CRS index (4 bytes a
    set)."""
    ops, nbytes = roofline_multigrid.render_rgba_ctrl(
        n_granules, n_grids, n_bands, out_hw, taps, step)
    h, w = out_hw
    grid = 2 * (h // step + 1) * (w // step + 1) * 4
    return (ops + (n_crs - 1) * 16 * h * w,
            nbytes + (n_crs - 1) * grid + n_granules * 4)
