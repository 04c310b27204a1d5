"""The least the three-band kernel has to do, from its shapes (see
`roofline.py` for what counts: the bytes the algorithm needs, whatever
implements it, so the count does not depend on how the kernel is handed
its rasters).  Bound by memory bandwidth."""


def render_rgba_ctrl(n_granules=1, out_hw=(256, 256), taps=4, step=16):
    """(ops, bytes) of one tile from the three bands of each of n
    granules: every output pixel reads `taps` f32 values of each band
    of each granule, the two control grids and one row of parameters a
    granule are read once, four bytes a pixel are written.  Operations:
    the control grid's upsampling (2 x 8) once; per granule the affine
    and the bounds tests (~14), the tap weights (~3 a tap) once for its
    three bands, per band the weighted sum (2 a tap) and the
    newest-wins pick (~3); per band the scaling (~6); the alpha rule
    (~3)."""
    h, w = out_hw
    px = h * w
    grid = 2 * (h // step + 1) * (w // step + 1) * 4
    nbytes = px * n_granules * 3 * taps * 4 + grid + n_granules * 14 * 4 \
        + px * 4
    ops = px * (16 + n_granules * (14 + 3 * taps + 3 * (2 * taps + 3))
                + 3 * 6 + 3)
    return ops, nbytes
