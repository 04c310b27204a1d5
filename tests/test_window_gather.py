"""Gather-window parity: the fused scene kernels gathering from a
dynamic footprint slice (GSKY_WARP_WINDOW) must match the full-scene
gather, at the kernel level and through the pipeline.  The re-indexing
itself is EXACT (integer origin shifts never round in f32); nearest
results are therefore bit-identical, while interpolated methods can
differ by 1 ulp where XLA contracts the tap-weight arithmetic
differently between the two compiled programs.

Why windowing exists: XLA's TPU gather lowering costs proportional to
the SOURCE extent, so a 256-px tile over 2048-px cached scenes pays for
the whole scene per dispatch (~13 ms measured on chip); slicing the
tile's footprint window first bounds the gather source by the tile,
not the archive.  Correctness hinges on the executor's host-side bound
(`pipeline.executor._gather_window`): the dense device coords are the
bilinear interpolation of the ctrl points with the per-granule affine
applied, and affine commutes with interpolation, so evaluating the
affine at the ctrl points in f64 bounds every dense coordinate.
"""

import datetime as dt
import os

import jax.numpy as jnp
import numpy as np
import pytest

from gsky_tpu.geo.crs import EPSG3857, EPSG4326, parse_crs
from gsky_tpu.geo.transform import BBox, GeoTransform, transform_bbox
from gsky_tpu.index import MASClient
from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
from gsky_tpu.pipeline.executor import _gather_window
from gsky_tpu.ops.warp import (render_scenes_bands_ctrl, render_scenes_ctrl,
                               warp_scenes_ctrl, warp_scenes_ctrl_scored)

from fixtures import make_archive


def t(day: int) -> float:
    return dt.datetime(2020, 1, day, tzinfo=dt.timezone.utc).timestamp()


def _synthetic_inputs(S=2048, h=256, w=256, step=16, B=3, seed=5):
    """A scene stack + ctrl grid whose gather footprint is a small
    corner of the scenes (the shape windowing exists for)."""
    rng = np.random.default_rng(seed)
    stack = rng.uniform(200.0, 3000.0, (B, S, S)).astype(np.float32)
    # nodata holes + the NaN-encoded bucket padding convention
    stack[:, 300:340, 300:340] = -999.0
    gh = (h - 1 + step - 1) // step + 1
    gw = (w - 1 + step - 1) // step + 1
    # src-CRS coords covering ~300 px of source with mild nonlinearity
    cc, rr = np.meshgrid(np.arange(gw, dtype=np.float64) * step,
                         np.arange(gh, dtype=np.float64) * step)
    sx = 10.0 + 1.1 * cc + 3.0 * np.sin(rr / 97.0)
    sy = 20.0 + 1.07 * rr + 2.0 * np.cos(cc / 53.0)
    ctrl = np.stack([sx, sy]).astype(np.float32)
    params = np.zeros((B, 11), np.float64)
    for k in range(B):
        # per-granule affine: footprint lands around [600, 950] px
        params[k, :6] = (560.0 + 7.0 * k, 1.0, 0.015, 590.0, 0.01, 1.02)
        params[k, 6] = S - 80      # true dims below the padded bucket
        params[k, 7] = S - 60
        params[k, 8] = -999.0
        params[k, 9] = 10.0 + k    # unique priorities
        params[k, 10] = k % 2      # two namespaces
    return stack, ctrl, params


class TestKernelWindowParity:
    @pytest.mark.parametrize("method", ["near", "bilinear", "cubic"])
    def test_scored_bit_parity(self, method):
        stack, ctrl, params = _synthetic_inputs()
        win, win0 = _gather_window(params, ctrl[0].astype(np.float64),
                                   ctrl[1].astype(np.float64),
                                   stack.shape[1], stack.shape[2])
        assert win is not None
        assert win[0] < stack.shape[1] and win[1] < stack.shape[2]
        p32 = jnp.asarray(params.astype(np.float32))
        full = warp_scenes_ctrl_scored(jnp.asarray(stack),
                                       jnp.asarray(ctrl), p32, method, 2,
                                       (256, 256), 16)
        wind = warp_scenes_ctrl_scored(jnp.asarray(stack),
                                       jnp.asarray(ctrl), p32, method, 2,
                                       (256, 256), 16, win=win,
                                       win0=jnp.asarray(win0))
        np.testing.assert_array_equal(np.asarray(full[1]),
                                      np.asarray(wind[1]))
        np.testing.assert_array_equal(np.asarray(full[0]),
                                      np.asarray(wind[0]))

    def test_render_byte_bit_parity(self):
        stack, ctrl, params = _synthetic_inputs(seed=6)
        win, win0 = _gather_window(params, ctrl[0].astype(np.float64),
                                   ctrl[1].astype(np.float64),
                                   stack.shape[1], stack.shape[2])
        p32 = jnp.asarray(params.astype(np.float32))
        sp = jnp.asarray(np.zeros(3, np.float32))
        a = render_scenes_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                               p32, sp, "bilinear", 2, (256, 256), 16,
                               True, 0)
        b = render_scenes_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                               p32, sp, "bilinear", 2, (256, 256), 16,
                               True, 0, win=win, win0=jnp.asarray(win0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bands_bit_parity(self):
        stack, ctrl, params = _synthetic_inputs(seed=7)
        win, win0 = _gather_window(params, ctrl[0].astype(np.float64),
                                   ctrl[1].astype(np.float64),
                                   stack.shape[1], stack.shape[2])
        p32 = jnp.asarray(params.astype(np.float32))
        sp = jnp.asarray(np.zeros(3, np.float32))
        sel = jnp.asarray(np.array([1, 0], np.int32))
        a = render_scenes_bands_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                                     p32, sp, sel, "near", 2, (256, 256),
                                     16, True, 0)
        b = render_scenes_bands_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                                     p32, sp, sel, "near", 2, (256, 256),
                                     16, True, 0, win=win,
                                     win0=jnp.asarray(win0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_partial_off_scene_granule(self):
        """A granule whose footprint hangs off the scene edge (negative
        rows) must clamp the window, not shift values."""
        stack, ctrl, params = _synthetic_inputs(seed=8)
        params[1, 3] = -120.0      # rows go negative for granule 1
        win, win0 = _gather_window(params, ctrl[0].astype(np.float64),
                                   ctrl[1].astype(np.float64),
                                   stack.shape[1], stack.shape[2])
        assert win is not None and int(win0[0]) == 0
        p32 = jnp.asarray(params.astype(np.float32))
        full = warp_scenes_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                                p32, "cubic", 2, (256, 256), 16)
        wind = warp_scenes_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                                p32, "cubic", 2, (256, 256), 16,
                                win=win, win0=jnp.asarray(win0))
        np.testing.assert_array_equal(np.asarray(full[0]),
                                      np.asarray(wind[0]))
        np.testing.assert_array_equal(np.asarray(full[1]),
                                      np.asarray(wind[1]))

    def test_window_bound_covers_dense_coords(self):
        """Property: every finite dense coordinate's tap range lies in
        the host-computed window (the correctness contract)."""
        from gsky_tpu.ops.warp import _bilerp_grid
        stack, ctrl, params = _synthetic_inputs(seed=9)
        win, win0 = _gather_window(params, ctrl[0].astype(np.float64),
                                   ctrl[1].astype(np.float64),
                                   stack.shape[1], stack.shape[2])
        sx = np.asarray(_bilerp_grid(jnp.asarray(ctrl[0]), 256, 256, 16))
        sy = np.asarray(_bilerp_grid(jnp.asarray(ctrl[1]), 256, 256, 16))
        for p in params:
            cols = p[0] + p[1] * sx + p[2] * sy - 0.5
            rows = p[3] + p[4] * sx + p[5] * sy - 0.5
            ok = np.isfinite(rows) & np.isfinite(cols)
            # cubic taps reach floor-1 .. floor+2
            assert np.floor(rows[ok]).min() - 1 >= win0[0]
            assert np.floor(rows[ok]).max() + 2 <= win0[0] + win[0] - 1
            assert np.floor(cols[ok]).min() - 1 >= win0[1]
            assert np.floor(cols[ok]).max() + 2 <= win0[1] + win[1] - 1

    def test_edge_tile_still_windows(self):
        """A tile straddling the scene edge must clamp the footprint to
        the oob thresholds (off-scene coords are NaN-poisoned on device
        anyway), keep a small window, and stay bit-identical."""
        stack, ctrl, params = _synthetic_inputs(seed=12)
        params[:, 0] = 1800.0   # cols run past true width (S-60)
        win, win0 = _gather_window(params, ctrl[0].astype(np.float64),
                                   ctrl[1].astype(np.float64),
                                   stack.shape[1], stack.shape[2])
        assert win is not None and win[1] <= 512
        p32 = jnp.asarray(params.astype(np.float32))
        full = warp_scenes_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                                p32, "bilinear", 2, (256, 256), 16)
        wind = warp_scenes_ctrl(jnp.asarray(stack), jnp.asarray(ctrl),
                                p32, "bilinear", 2, (256, 256), 16,
                                win=win, win0=jnp.asarray(win0))
        np.testing.assert_array_equal(np.asarray(full[0]),
                                      np.asarray(wind[0]))
        np.testing.assert_array_equal(np.asarray(full[1]),
                                      np.asarray(wind[1]))

    def test_no_finite_coords_declines(self):
        stack, ctrl, params = _synthetic_inputs(seed=10)
        assert _gather_window(params, np.full_like(ctrl[0], np.nan,
                                                   dtype=np.float64),
                              np.full_like(ctrl[1], np.nan,
                                           dtype=np.float64),
                              2048, 2048) is None

    def test_whole_scene_footprint_declines(self):
        """Footprint ~ scene extent: no window (slice would not help)."""
        stack, ctrl, params = _synthetic_inputs(seed=11)
        # blow the footprint up to the whole scene (origin at 0 so the
        # clipped span really covers ~all 2048 px on both axes)
        params[:, 0] = 0.0
        params[:, 3] = 0.0
        params[:, 1] = 7.0
        params[:, 5] = 7.0
        assert _gather_window(params, ctrl[0].astype(np.float64),
                              ctrl[1].astype(np.float64),
                              2048, 2048) is None


class TestPipelineWindowParity:
    @pytest.fixture(scope="class")
    def archive(self, tmp_path_factory):
        return make_archive(str(tmp_path_factory.mktemp("winarch")))

    @pytest.mark.parametrize("method", ["near", "bilinear", "cubic"])
    def test_tile_bit_parity(self, archive, method, monkeypatch):
        bbox = transform_bbox(BBox(148.02, -35.32, 148.12, -35.22),
                              EPSG4326, EPSG3857)
        outs = {}
        for mode in ("0", "1"):
            monkeypatch.setenv("GSKY_WARP_WINDOW", mode)
            req = GeoTileRequest(
                collection=archive["root"], bands=["LC08_20200110_T1"],
                bbox=bbox, crs=EPSG3857, width=128, height=128,
                start_time=t(9), end_time=t(13), resample=method)
            res = TilePipeline(MASClient(archive["store"])).process(req)
            d = np.asarray(res.data["LC08_20200110_T1"])
            ok = np.asarray(res.valid["LC08_20200110_T1"])
            outs[mode] = (np.where(ok, d, 0.0), ok)
        np.testing.assert_array_equal(outs["0"][1], outs["1"][1])
        if method == "near":
            # pure gather: the window is an exact re-indexing
            np.testing.assert_array_equal(outs["0"][0], outs["1"][0])
        elif method == "bilinear":
            # interpolated taps: identical taps and weights, but XLA
            # contracts the weight arithmetic differently between the
            # two compiled programs — ENFORCE the 1-ulp bound (a real
            # windowing defect would exceed it immediately)
            np.testing.assert_array_max_ulp(outs["0"][0], outs["1"][0],
                                            maxulp=2)
        else:
            # cubic: the source COORDINATE itself is interpolated, and
            # the windowed program contracts that bilerp differently —
            # a 1-ulp difference at coordinate magnitude ~2^10 is
            # ~1.2e-4 px, which the data gradient through the
            # Catmull-Rom taps amplifies far past any fixed ulp count
            # (measured: max rel 6.7e-4 on this scene).  A windowing
            # defect shifts taps by whole pixels — orders of magnitude
            # above this bound — so the test keeps its sensitivity.
            np.testing.assert_allclose(outs["0"][0], outs["1"][0],
                                       rtol=2e-3, atol=0.5)

    def test_rgba_bit_parity(self, tmp_path, monkeypatch):
        from gsky_tpu.index import MASStore
        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io import write_geotiff

        utm = parse_crs("EPSG:32755")
        rng = np.random.default_rng(13)
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        rgb = rng.uniform(200, 3000, (3, 512, 512)).astype(np.int16)
        rgb[:, :64, :64] = -999
        p = os.path.join(str(tmp_path), "S2_20200110_T1.tif")
        write_geotiff(p, rgb, gt, utm, nodata=-999)
        store = MASStore()
        store.ingest(extract(p))
        core = BBox(592000.0, 6098000.0, 598000.0, 6100500.0)
        merc = transform_bbox(transform_bbox(core, utm, EPSG4326),
                              EPSG4326, EPSG3857)
        req = GeoTileRequest(
            collection=str(tmp_path),
            bands=["S2_20200110_T1_b1", "S2_20200110_T1_b2",
                   "S2_20200110_T1_b3"],
            bbox=merc, crs=EPSG3857, width=128, height=128,
            start_time=t(9), end_time=t(11), resample="bilinear")
        pipe = TilePipeline(MASClient(store))
        outs = {}
        for mode in ("0", "1"):
            monkeypatch.setenv("GSKY_WARP_WINDOW", mode)
            outs[mode] = np.asarray(pipe.render_rgba_byte(req, auto=True))
        assert outs["0"] is not None and outs["1"] is not None
        np.testing.assert_array_equal(outs["0"], outs["1"])
