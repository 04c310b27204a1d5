"""The least a kernel has to do, from its shapes, and the chip's peaks.

`peaks.json` is keyed by `device_kind` as JAX reports it; a device that
is not in the table is an error, not a default.  A roofline share is
the least time the chip could take — the larger of operations over the
peak rate and bytes over the peak bandwidth — over the time the kernel
took in the device trace.

All three kernels here are bound by memory bandwidth: they do a few
operations for each value they move, and in float32, which runs at a
fraction of the bf16 peak used below, so the compute bound is stated
generously and is still far under the memory bound.

Bytes are those the algorithm needs, not those an implementation moves:
padding to a bucket, a gather window larger than the footprint and an
intermediate written to HBM between two kernels are the
implementation's, and show as a lower share.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    with open(os.path.join(_HERE, "peaks.json")) as fp:
        table = json.load(fp)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (it has {sorted(table)})")
    return table[device_kind]


def least_seconds(ops, nbytes, peak):
    """(seconds, which bound applies)."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops > t_mem else (t_mem, "memory")


def render_scenes_ctrl(n_scenes, out_hw=(256, 256), taps=1, step=16):
    """(ops, bytes) of one tile: every output pixel reads `taps` f32
    source values from each of the n scenes it may come from (1 nearest,
    4 bilinear, 16 cubic), the two control grids are read once, and one
    byte per pixel is written.  Operations: the control grid's bilinear
    upsampling (2 grids x 8), the affine to pixel coordinates and the
    bounds tests per scene (~14), the tap's weights (~3 per tap), the
    newest-wins pick (~3 per scene) and the scaling (~6).  Memory-bound
    by two orders of magnitude."""
    h, w = out_hw
    px = h * w
    grid = 2 * (h // step + 1) * (w // step + 1) * 4
    nbytes = px * n_scenes * taps * 4 + grid + n_scenes * 11 * 4 + px
    ops = px * (16 + n_scenes * (14 + 3 * taps + 3) + 6)
    return ops, nbytes


def window_gather(steps, window_hw):
    """(ops, bytes): the polygon's bounding window of every timestep is
    read once from the resident stack (f32) and written once, with one
    validity byte per value, for the reduction that follows; the mask is
    read once.  Two comparisons per value.  Memory-bound."""
    h, w = window_hw
    n = steps * h * w
    return 2 * n, n * 4 + n * 4 + n + h * w


def masked_stats(steps, window_hw):
    """(ops, bytes): every value and its validity byte are read once,
    one sum and one count per timestep are written.  Clip, select and
    two additions per value.  Memory-bound."""
    h, w = window_hw
    n = steps * h * w
    return 4 * n, n * 4 + n + steps * 8
