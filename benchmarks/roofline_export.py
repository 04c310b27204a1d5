"""The least the export's warp kernel has to do, from its shapes (see
`roofline.py` for what counts: the bytes the algorithm needs, whatever
implements it).  Bound by memory bandwidth."""


def warp_scenes_ctrl_scored(n_scenes=1, out_hw=(1024, 1024), taps=16,
                            step=16):
    """(ops, bytes) of one tile of an export from the n scenes it may
    come from: every output pixel reads `taps` f32 source values of each
    scene (16 cubic, 4 bilinear, 1 nearest: `roofline.py`'s convention,
    a tap is a read, although neighbouring pixels share taps), the two
    control grids and one row of parameters a scene are read once, and
    a float32 value and a validity byte a pixel are written.
    Operations: the control grid's bilinear upsampling (2 grids x 8)
    once; per scene the affine to pixel coordinates and the bounds
    tests (~14), the eight Catmull-Rom polynomials of a cubic tap set
    (~6 each, none for fewer taps), per tap the weight's product, the
    validity test and the two accumulations (~5), the quotient and its
    threshold (~3) and the newest-wins pick (~3)."""
    h, w = out_hw
    px = h * w
    grid = 2 * (h // step + 1) * (w // step + 1) * 4
    nbytes = px * n_scenes * taps * 4 + grid + n_scenes * 11 * 4 + px * 5
    weights = 8 * 6 if taps == 16 else 0
    ops = px * (16 + n_scenes * (14 + weights + 5 * taps + 3 + 3))
    return ops, nbytes
