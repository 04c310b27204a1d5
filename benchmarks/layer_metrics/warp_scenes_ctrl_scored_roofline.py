"""Share of its roofline `warp_scenes_ctrl_scored` reaches: the least
time the chip could take for the export tiles of the window
(`roofline_export.py`, from the stack depth in each
`scene_mosaic:((n, H, W), window)` dispatch key, the layer's tile size
and its resampling) over the device time per execution in the trace.
Memory-bound.  An export's tiles are whole ones here (2048 and 4096 are
multiples of 1024)."""

from benchmarks import roofline, roofline_export
from benchmarks.ctx import stack_depth

TAPS = {"near": 1, "nearest": 1, "bilinear": 4, "cubic": 16}


def read(ctx):
    made = ctx.module("warp_scenes_ctrl_scored")
    legs = {k: n for k, n in ctx.legs().items()
            if k.startswith("scene_mosaic:") and stack_depth(k)}
    if not made or not legs:
        return None
    lay = next(lay for lay in ctx.cell.config["layers"]
               if lay["name"] == ctx.cell.traffic["layer"])
    hw = (lay.get("wcs_max_tile_height", 1024),
          lay.get("wcs_max_tile_width", 1024))
    peak = ctx.peaks()
    least = sum(n * roofline.least_seconds(
        *roofline_export.warp_scenes_ctrl_scored(
            stack_depth(k), hw, TAPS[lay.get("resample", "near")]),
        peak)[0] for k, n in legs.items()) / sum(legs.values())
    return 100.0 * least / (made[0] / made[1])
