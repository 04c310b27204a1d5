"""The least the band-expression kernel has to do, from its shapes (see
`roofline.py` for what counts: the bytes the algorithm needs, whatever
implements it, so the count does not depend on how the kernel is handed
its rasters).  Bound by memory bandwidth."""

import re

from . import reference_expr


def leg_shape(leg_key):
    """(granule sets, bands a set) of a `render_expr:((G, H, W, C),
    window)` dispatch key, or None."""
    m = re.search(r":\(\((\d+), \d+, \d+, (\d+)\)", leg_key)
    return (int(m.group(1)), int(m.group(2))) if m else None


def expression_ops(node):
    """Operations a pixel of the parsed expression takes: one for each
    arithmetic node, comparison, negation and selection."""
    if node[0] in ("num", "var"):
        return 0
    return 1 + sum(expression_ops(c) for c in node[1:]
                   if isinstance(c, tuple))


def ops_by_bands(layers):
    """{variables: mean operations a pixel} over the configuration's
    expression layers: a dispatch key tells how many bands its
    expression read and not which layer it served."""
    by = {}
    for lay in layers:
        node = reference_expr.parse(
            reference_expr.split_product(lay["rgb_products"][0])[1])
        by.setdefault(len(reference_expr.variables(node)), []).append(
            expression_ops(node))
    return {c: sum(v) / len(v) for c, v in by.items()}


def render_expr_ctrl(n_granules=1, n_bands=2, expr_ops=3,
                     out_hw=(256, 256), taps=4, step=16):
    """(ops, bytes) of one tile from the n_bands bands of each of n
    granules: every output pixel reads `taps` f32 values of each band
    of each granule, the two control grids and one row of parameters a
    granule are read once, one byte a pixel is written.  Operations:
    the control grid's upsampling (2 x 8) once; per granule the affine
    and the bounds tests (~14), the tap weights (~3 a tap) once for its
    bands, per band the weighted sum (2 a tap) and the newest-wins pick
    (~3); the expression's own operations and its validity (one test a
    band and the finiteness test); the scaling (~6)."""
    h, w = out_hw
    px = h * w
    grid = 2 * (h // step + 1) * (w // step + 1) * 4
    nbytes = px * n_granules * n_bands * taps * 4 + grid \
        + n_granules * 14 * 4 + px
    ops = px * (16 + n_granules * (14 + 3 * taps
                                   + n_bands * (2 * taps + 3))
                + expr_ops + n_bands + 1 + 6)
    return ops, nbytes
