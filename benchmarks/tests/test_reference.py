"""reference.py against cases worked out by hand on 8 x 8 rasters."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import reference as R       # noqa: E402

# value = 10 * row + col, so a tap says which pixel it took
GRID = (10 * np.arange(8)[:, None] + np.arange(8)[None, :]).astype(np.int16)


def source(data=GRID, ts=0.0, x0=100.0, y0=200.0, nodata=-1.0, ns="b"):
    return R.Source(namespace=ns, timestamp=ts, crs="EPSG:3857", x0=x0, y0=y0,
                    dx=2.0, dy=-2.0, shape=data.shape, nodata=nodata,
                    read=lambda: data)


def test_nearest_takes_the_pixel_that_holds_the_point():
    col = np.array([0.0, 0.99, 1.0, 7.999, 8.0, -0.01, np.nan])
    row = np.array([0.0, 3.5, 3.0, 7.0, 0.0, 0.0, 0.0])
    v, ok = R.tap_nearest(GRID, -1.0, col, row)
    assert ok.tolist() == [True, True, True, True, False, False, False]
    assert v[:4].tolist() == [0, 30, 31, 77]


def test_nearest_treats_nodata_as_absent():
    data = GRID.copy()
    data[2, 3] = -1
    v, ok = R.tap_nearest(data, -1.0, np.array([3.5, 4.5]),
                          np.array([2.5, 2.5]))
    assert ok.tolist() == [False, True] and v[1] == 24


def test_bilinear_is_the_weighted_mean_of_the_four_around():
    # the point (col 3.0, row 2.0) is the corner shared by pixels
    # (1,2) (1,3) (2,2) (2,3): centres are at .5
    v, ok = R.tap_bilinear(GRID, -1.0, np.array([3.0]), np.array([2.0]))
    assert ok[0] and v[0] == pytest.approx((12 + 13 + 22 + 23) / 4)
    # a quarter of the way from centre (2,2) to centre (2,3)
    v, ok = R.tap_bilinear(GRID, -1.0, np.array([2.75]), np.array([2.5]))
    assert v[0] == pytest.approx(22.25)


def test_bilinear_leaves_out_what_is_not_there():
    data = GRID.copy()
    data[1, 2] = -1
    v, ok = R.tap_bilinear(data, -1.0, np.array([3.0]), np.array([2.0]))
    assert ok[0] and v[0] == pytest.approx((13 + 22 + 23) / 3)
    # at the raster's corner only one pixel is there, and outside none
    v, ok = R.tap_bilinear(GRID, -1.0, np.array([0.0, -0.1]),
                           np.array([0.0, 0.0]))
    assert ok.tolist() == [True, False] and v[0] == pytest.approx(0.0)


def test_tile_pixels_come_from_the_pixel_under_their_centre():
    # an 8 x 8 tile over exactly the raster: identity
    out, ok = R.mosaic([source()], (100.0, 184.0, 116.0, 200.0),
                       "EPSG:3857", 8, 8)
    assert ok.all() and (out == GRID).all()
    # a 4 x 4 tile over it: centres fall in pixels (1,1), (1,3), ...
    out, ok = R.mosaic([source()], (100.0, 184.0, 116.0, 200.0),
                       "EPSG:3857", 4, 4)
    assert (out == GRID[1::2, 1::2]).all()
    # shifted by one source pixel east: another column, and the last
    # output column is off the raster
    out, ok = R.mosaic([source()], (102.0, 184.0, 118.0, 200.0),
                       "EPSG:3857", 8, 8)
    assert (out[:, :7] == GRID[:, 1:]).all() and not ok[:, 7].any()


def test_newest_valid_scene_wins():
    old = source(np.full((8, 8), 5, np.int16), ts=100.0)
    new_data = np.full((8, 8), 9, np.int16)
    new_data[:, :4] = -1                   # the newer scene has a hole
    new = source(new_data, ts=200.0)
    for order in ([old, new], [new, old]):
        out, ok = R.mosaic(order, (100.0, 184.0, 116.0, 200.0),
                           "EPSG:3857", 8, 8)
        assert ok.all()
        assert (out[:, :4] == 5).all() and (out[:, 4:] == 9).all()


def test_select_follows_the_documented_range_rule():
    s = [source(ts=float(t)) for t in (10, 20, 30, 40)] \
        + [source(ts=20.0, ns="other")]
    def stamps(got):
        return [x.timestamp for x in got]
    assert stamps(R.select(s, "b", 20.0)) == [20.0]
    assert stamps(R.select(s, "b", 30.0, accum_from=10.0)) == [10.0, 20.0]
    assert stamps(R.select(s, "b", 10.0, accum_from=10.0)) == [10.0]
    assert stamps(R.select(s, "b", 25.0)) == []


def test_scale_byte():
    v = np.array([-5.0, 0.0, 11.8, 11.9, 1500.0, 3000.0, 9999.0, 7.0])
    ok = np.array([True] * 7 + [False])
    b = R.scale_byte(v, ok, 0.0, 254.0 / 3000.0, 3000.0)
    # 11.8 * 0.08467 = 0.999 -> 0; 11.9 -> 1.0075 -> 1; the top clips to 254
    assert b.tolist() == [0, 0, 0, 1, 127, 254, 254, 255]
    # no scale given: 254 / clip
    assert R.scale_byte(np.array([0.5]), np.array([True]), 0.0, 0.0,
                        1.0)[0] == 127
    # an offset moves the value before it is clipped
    assert R.scale_byte(np.array([-10.0]), np.array([True]), 1510.0,
                        254.0 / 3000.0, 3000.0)[0] == 127


def test_a_one_pixel_shift_a_wrong_winner_and_a_wrong_scale_all_show():
    """What the tile bound has to be able to catch, on the imagery the
    benchmark serves."""
    from benchmarks.archives import geotiff_scenes as G
    p = {"scene_hw": [300, 300], "nodata": -999, "nodata_corner": 0.0}
    a, b = G.band(p, 1, 0), G.band(p, 1, 1)
    args = (0.0, 254.0 / 3000.0, 3000.0)
    ok = np.ones(a.shape, bool)
    right = R.scale_byte(a, ok, *args)
    assert np.mean(R.scale_byte(np.roll(a, 1, 1), ok, *args) != right) > 0.2
    assert np.mean(R.scale_byte(b, ok, *args) != right) > 0.5
    assert np.mean(R.scale_byte(a, ok, 0.0, 255.0 / 3000.0, 3000.0)
                   != right) > 0.2


def test_the_modis_field_shows_a_shift_too():
    from benchmarks.archives import netcdf_stack as N
    p = {"hw": [128, 128], "steps": 8, "nodata": -9999.0,
         "nodata_corner": 0.0}
    a = N.Field(p, 1, 0).window([3], 0, 128, 0, 128)[0]
    ok = np.ones(a.shape, bool)
    right = R.scale_byte(a, ok, 0.0, 254.0, 1.0)
    assert np.mean(R.scale_byte(np.roll(a, 1, 0), ok, 0.0, 254.0, 1.0)
                   != right) > 0.9


def test_palette_ramp():
    ramp = R.palette_ramp([{"R": 0, "G": 0, "B": 120, "A": 255},
                           {"R": 250, "G": 250, "B": 90, "A": 255}])
    assert ramp.shape == (256, 4)
    assert ramp[0].tolist() == [0, 0, 120, 255]
    assert ramp[128, 0] == pytest.approx(125.0)
    assert ramp[255].tolist() == [0, 0, 0, 0]


def test_drill_means_over_a_rectangle():
    stack = np.stack([GRID, GRID + 100]).astype(np.float32)
    stack[0, 3, 3] = -1.0                   # one pixel without data
    mask = R.burn_rectangle((8, 8), 2, 4, 2, 5)
    mean, n = R.drill_means(stack, mask, -1.0)
    rows, cols = np.arange(2, 5), np.arange(2, 6)
    full = (10 * rows[:, None] + cols[None, :]).astype(float)
    assert n.tolist() == [11, 12]
    assert mean[0] == pytest.approx((full.sum() - 33) / 11)
    assert mean[1] == pytest.approx(full.mean() + 100)
    # nothing valid: a mean of 0, as the served CSV prints
    mean, n = R.drill_means(np.full((1, 8, 8), -1.0, np.float32), mask, -1.0)
    assert n[0] == 0 and mean[0] == 0.0
