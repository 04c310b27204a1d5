"""The on-chip parity checks; tests_tpu/test_device_parity.py runs each
as one test, in the pytest process that holds the chip.

Reference values come from the SAME jax code pinned to the in-process
CPU backend (jax.default_device), so every check compares the real
Mosaic/XLA-TPU lowering against the CPU lowering the hermetic tests/
suite validates — the class of bug this tier exists for (a kernel that
only ever ran interpreted asked for 19.5 MB of a 16 MB VMEM at first
chip contact).  Pallas kernels never run under the CPU pin: a
non-interpret pallas_call has no CPU lowering.
"""

import numpy as np

CHECKS = {}


def check(name):
    def deco(fn):
        CHECKS[name] = fn
        return fn
    return deco


import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

CPU = jax.devices("cpu")[0]

rng = np.random.default_rng(17)


def on_cpu(fn, *args):
    with jax.default_device(CPU):
        return np.asarray(fn(*[jnp.asarray(a) for a in args]))


# --- warp method parity (device vs CPU lowering) -------------------------

_H, _W = 300, 280
_SRC = rng.uniform(100, 3000, (_H, _W)).astype(np.float32)
_VALID = rng.uniform(0, 1, (_H, _W)) > 0.1
_ROWS = rng.uniform(-4, _H + 4, (128, 128)).astype(np.float32)
_COLS = rng.uniform(-4, _W + 4, (128, 128)).astype(np.float32)
_ROWS[0, :5] = np.nan


def _warp_parity(method, atol):
    from gsky_tpu.ops.warp import warp_gather
    out_d, ok_d = warp_gather(jnp.asarray(_SRC), jnp.asarray(_VALID),
                              jnp.asarray(_ROWS), jnp.asarray(_COLS),
                              method)
    out_d, ok_d = np.asarray(out_d), np.asarray(ok_d)
    with jax.default_device(CPU):
        out_c, ok_c = warp_gather(jnp.asarray(_SRC), jnp.asarray(_VALID),
                                  jnp.asarray(_ROWS), jnp.asarray(_COLS),
                                  method)
    out_c, ok_c = np.asarray(out_c), np.asarray(ok_c)
    mism = np.mean(ok_d != ok_c)
    assert mism < 0.001, f"validity mismatch {mism:.2%}"
    both = ok_d & ok_c
    np.testing.assert_allclose(out_d[both], out_c[both], rtol=1e-5,
                               atol=atol)


@check("warp_nearest")
def _():
    _warp_parity("near", 0.0)


@check("warp_bilinear")
def _():
    _warp_parity("bilinear", 0.05)


@check("warp_cubic")
def _():
    _warp_parity("cubic", 0.05)


# --- fused render kernels -------------------------------------------------

def _render_inputs(n_scenes=4, S=512):
    stack = rng.uniform(200, 3000, (n_scenes, S, S)).astype(np.int16)
    gh = 17
    ctrl = np.stack(
        [np.linspace(30.0, 350.0, gh)[None, :].repeat(gh, 0),
         np.linspace(20.0, 340.0, gh)[:, None].repeat(gh, 1)]) \
        .astype(np.float32)
    params = np.zeros((n_scenes, 11), np.float32)
    for k in range(n_scenes):
        params[k, :6] = (k * 5.0, 1.0, 0.0, k * 3.0, 0.0, 1.0)
        params[k, 6] = S
        params[k, 7] = S
        params[k, 8] = 205.0 + k          # some nodata hits
        params[k, 9] = float(n_scenes - k)
        params[k, 10] = k % 2
    return stack, ctrl, params


@check("fused_mosaic_render")
def _():
    from gsky_tpu.ops.warp import render_scenes_ctrl
    stack, ctrl, params = _render_inputs()
    sp = np.zeros(3, np.float32)
    args = (stack, ctrl, params, sp)
    kw = dict(method="near", n_ns=2, out_hw=(256, 256), step=16,
              auto=True, colour_scale=0)
    out_d = np.asarray(render_scenes_ctrl(
        *[jnp.asarray(a) for a in args], **kw))
    out_c = on_cpu(lambda *a: render_scenes_ctrl(*a, **kw), *args)
    mism = np.mean(out_d != out_c)
    assert mism < 0.002, f"byte mismatch {mism:.2%}"


@check("fused_rgba_render")
def _():
    from gsky_tpu.ops.warp import render_rgba_ctrl
    S = 512
    bands = rng.uniform(200, 3000, (3, S, S)).astype(np.int16)
    _, ctrl, _ = _render_inputs()
    param = np.array([0, 1, 0, 0, 0, 1, S, S, 230.0, 0, 0], np.float32)
    sp = np.zeros(3, np.float32)
    kw = dict(method="bilinear", out_hw=(256, 256), step=16, auto=True,
              colour_scale=0)
    out_d = np.asarray(render_rgba_ctrl(
        (tuple(jnp.asarray(b) for b in bands),), jnp.asarray(ctrl),
        jnp.asarray(param[None]), jnp.ones((1, 3), jnp.float32),
        jnp.asarray(sp), **kw))
    out_c = on_cpu(lambda b, *a: render_rgba_ctrl((tuple(b),), *a, **kw),
                   bands, ctrl, param[None], np.ones((1, 3), np.float32),
                   sp)
    assert out_d.shape == (256, 256, 4)
    mism = np.mean(out_d != out_c)
    assert mism < 0.005, f"byte mismatch {mism:.2%}"


@check("rgba_matches_planes_on_chip")
def _():
    """The packed-RGB kernel must agree with the per-band kernel ON THE
    CHIP, not just under the CPU lowering the hermetic tests check."""
    from gsky_tpu.ops.warp import (render_rgba_ctrl,
                                   render_scenes_bands_ctrl)
    S = 512
    planes = rng.uniform(200, 3000, (3, S, S)).astype(np.int16)
    _, ctrl, _ = _render_inputs()
    nodata = 230.0
    params = np.zeros((4, 11), np.float32)
    for k in range(3):
        params[k, :6] = (0, 1, 0, 0, 0, 1)
        params[k, 6] = S
        params[k, 7] = S
        params[k, 8] = nodata
        params[k, 9] = 1.0
        params[k, 10] = k
    params[3, 10] = -1.0
    sp = np.zeros(3, np.float32)
    pl = np.asarray(render_scenes_bands_ctrl(
        jnp.asarray(np.concatenate([planes, planes[:1]])),
        jnp.asarray(ctrl), jnp.asarray(params), jnp.asarray(sp),
        jnp.asarray(np.arange(3, dtype=np.int32)), "near", 4,
        (256, 256), 16, True, 0))
    param1 = np.array([0, 1, 0, 0, 0, 1, S, S, nodata, 0, 0], np.float32)
    packed = np.asarray(render_rgba_ctrl(
        (tuple(jnp.asarray(b) for b in planes),), jnp.asarray(ctrl),
        jnp.asarray(param1[None]), jnp.ones((1, 3), jnp.float32),
        jnp.asarray(sp), "near", (256, 256), 16, True, 0))
    for i in range(3):
        mism = np.mean(packed[..., i] != pl[i])
        assert mism < 0.001, f"band {i}: {mism:.2%}"


@check("window_render_bit_parity")
def _():
    """Gather-window path vs full-scene path ON THE CHIP: the window is
    a pure re-indexing, so the byte tiles must be IDENTICAL under the
    real TPU lowering (the production default enables it there)."""
    from gsky_tpu.ops.warp import render_scenes_ctrl
    from gsky_tpu.pipeline.executor import _gather_window
    # 1024-px scenes: the ~350-px footprint buckets to a 384 window
    # (dense _WIN_BUCKETS), comfortably smaller than the scene
    stack, ctrl, params = _render_inputs(S=1024)
    sp = np.zeros(3, np.float32)
    made = _gather_window(params.astype(np.float64),
                          ctrl[0].astype(np.float64),
                          ctrl[1].astype(np.float64),
                          stack.shape[1], stack.shape[2])
    assert made is not None, "window must engage at this shape"
    win, win0 = made
    kw = dict(method="cubic", n_ns=2, out_hw=(256, 256), step=16,
              auto=True, colour_scale=0)
    full = np.asarray(render_scenes_ctrl(
        jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params),
        jnp.asarray(sp), **kw))
    wind = np.asarray(render_scenes_ctrl(
        jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params),
        jnp.asarray(sp), **kw, win=win, win0=jnp.asarray(win0)))
    # cubic tap weights: 1-ulp XLA-contraction diffs between the two
    # programs can flip a byte at scaling boundaries — bound the RATE
    # of flips AND their magnitude (corruption must not hide in a
    # fraction-only bound)
    diff = np.abs(full.astype(np.int16) - wind.astype(np.int16))
    assert diff.max() <= 1, f"byte delta {diff.max()}"
    mism = np.mean(diff != 0)
    assert mism < 0.002, f"byte mismatch {mism:.2%}"


@check("window_rgba_bit_parity")
def _():
    from gsky_tpu.ops.warp import render_rgba_ctrl
    from gsky_tpu.pipeline.executor import _gather_window
    S = 1024
    scene = (tuple(jnp.asarray(b) for b in
                   rng.uniform(200, 3000, (3, S, S)).astype(np.int16)),)
    _, ctrl, _ = _render_inputs()
    param = np.array([0, 1, 0, 0, 0, 1, S, S, 230.0, 0, 0], np.float32)
    sp = np.zeros(3, np.float32)
    made = _gather_window(param.astype(np.float64)[None, :],
                          ctrl[0].astype(np.float64),
                          ctrl[1].astype(np.float64), S, S)
    assert made is not None, "window must engage at this shape"
    win, win0 = made
    kw = dict(method="bilinear", out_hw=(256, 256), step=16, auto=True,
              colour_scale=0)
    prio = jnp.ones((1, 3), jnp.float32)
    full = np.asarray(render_rgba_ctrl(
        scene, jnp.asarray(ctrl), jnp.asarray(param[None]), prio,
        jnp.asarray(sp), **kw))
    wind = np.asarray(render_rgba_ctrl(
        scene, jnp.asarray(ctrl), jnp.asarray(param[None]), prio,
        jnp.asarray(sp), **kw, win=win, win0=jnp.asarray(win0[None])))
    diff = np.abs(full.astype(np.int16) - wind.astype(np.int16))
    assert diff.max() <= 1, f"byte delta {diff.max()}"
    mism = np.mean(diff != 0)
    assert mism < 0.005, f"byte mismatch {mism:.2%}"


# --- mosaic semantics -----------------------------------------------------

@check("mosaic_newest_wins")
def _():
    """`mosaic_stack` is the dispatch site of the Pallas mosaic kernel
    (`run_with_fallback`), so this is its parity check THROUGH the
    selection, against plain numpy newest-wins."""
    from gsky_tpu.ops import pallas_tpu as pt
    from gsky_tpu.ops.mosaic import mosaic_stack
    rs = [rng.uniform(0, 1, (128, 128)).astype(np.float32)
          for _ in range(5)]
    vs = [rng.uniform(0, 1, (128, 128)) > 0.4 for _ in range(5)]
    stamps = [3.0, 1.0, 5.0, 2.0, 4.0]
    out_d, ok_d = mosaic_stack([jnp.asarray(r) for r in rs],
                               [jnp.asarray(v) for v in vs], stamps)
    out_d, ok_d = np.asarray(out_d), np.asarray(ok_d)
    want = np.zeros((128, 128), np.float32)
    ok = np.zeros((128, 128), bool)
    for i in np.argsort(stamps):            # oldest first, newest last
        want = np.where(vs[i], rs[i], want)
        ok |= vs[i]
    np.testing.assert_array_equal(ok_d, ok)
    np.testing.assert_array_equal(out_d[ok], want[ok])
    assert "mosaic" in pt._LOWERED.get("mosaic_first_valid", ()), \
        pt.kernel_state()
    assert "mosaic_first_valid" not in pt._FAILED, pt._FAILED


@check("mosaic_weighted_fusion")
def _():
    from gsky_tpu.ops.mosaic import mosaic_stack
    rs = [rng.uniform(0, 1, (128, 128)).astype(np.float32)
          for _ in range(3)]
    vs = [rng.uniform(0, 1, (128, 128)) > 0.3 for _ in range(3)]
    stamps = [1.0, 2.0, 3.0]
    w = [0.2, 0.5, 0.3]
    out_d, ok_d = mosaic_stack([jnp.asarray(r) for r in rs],
                               [jnp.asarray(v) for v in vs], stamps,
                               weights=w)
    with jax.default_device(CPU):
        out_c, ok_c = mosaic_stack([jnp.asarray(r) for r in rs],
                                   [jnp.asarray(v) for v in vs], stamps,
                                   weights=w)
    np.testing.assert_array_equal(np.asarray(ok_d), np.asarray(ok_c))
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_c),
                               rtol=1e-5, atol=1e-6)


# --- pallas kernels vs XLA on the real Mosaic backend ---------------------

@check("pallas_masked_stats_vs_xla")
def _():
    from gsky_tpu.ops.drill import masked_mean
    from gsky_tpu.ops.pallas_tpu import masked_stats_pallas, use_pallas
    assert use_pallas(), "pallas disabled on this backend"
    B, N = 1000, 128 * 128
    data = rng.uniform(0, 1, (B, N)).astype(np.float32)
    valid = rng.uniform(0, 1, (B, N)) > 0.35
    s, c = masked_stats_pallas(jnp.asarray(data), jnp.asarray(valid),
                               -3.0e38, 3.0e38)
    s, c = np.asarray(s), np.asarray(c)
    v_x, c_x = masked_mean(jnp.asarray(data), jnp.asarray(valid))
    v_x, c_x = np.asarray(v_x), np.asarray(c_x)
    np.testing.assert_array_equal(c, c_x)
    v = np.where(c > 0, s / np.maximum(c, 1), 0.0)
    np.testing.assert_allclose(v, v_x, rtol=1e-5)


@check("pallas_mosaic_vs_xla")
def _():
    from gsky_tpu.ops.pallas_tpu import (mosaic_first_valid_pallas,
                                         use_pallas)
    assert use_pallas()
    T, H, W = 8, 256, 256
    stack = rng.uniform(0, 1, (T, H, W)).astype(np.float32)
    valid = rng.uniform(0, 1, (T, H, W)) > 0.5
    out_p, ok_p = mosaic_first_valid_pallas(jnp.asarray(stack),
                                            jnp.asarray(valid))
    idx = np.argmax(valid, axis=0)
    ok = valid.any(axis=0)
    ref = np.take_along_axis(stack, idx[None], axis=0)[0]
    np.testing.assert_array_equal(np.asarray(ok_p), ok)
    got = np.asarray(out_p)
    np.testing.assert_allclose(got[ok], ref[ok], rtol=1e-6)


@check("selection_gates")
def _():
    """What a TPU process selects, decided by the code and not by a
    caught exception: the streaming kernels Mosaic compiles are on; the
    gather-form warp kernels it refuses (and with them pages and waves)
    are off, so the XLA bucketed leg serves."""
    from gsky_tpu.ops import pallas_tpu as pt
    from gsky_tpu.ops.paged import paged_enabled
    from gsky_tpu.pipeline.waves import waves_enabled
    assert pt.use_pallas()
    assert not pt.pallas_interpret()
    assert not pt.warp_pallas_enabled()
    assert not pt.warp_pallas_ok(384, 384, 1)
    assert not paged_enabled() and not waves_enabled()


@check("drill_stats_dispatch_site")
def _():
    """`pipeline.drill._stats_tail` is the dispatch site of the Pallas
    masked-stats kernel: parity through the selection (race included)
    against numpy, at the 1,000-step drill shape."""
    from gsky_tpu.ops import pallas_tpu as pt
    from gsky_tpu.pipeline.drill import _stats_tail
    from gsky_tpu.pipeline.types import GeoDrillRequest
    B, N = 1024, 128 * 128
    data = rng.uniform(0, 1, (B, N)).astype(np.float32)
    valid = rng.uniform(0, 1, (B, N)) > 0.35
    req = GeoDrillRequest(collection="", bands=["v"], geometry_wkt="")
    v, c, _ = _stats_tail(jnp.asarray(data), jnp.asarray(valid), req)
    c_ref = valid.sum(-1)
    v_ref = np.where(valid, data, 0).sum(-1, dtype=np.float64) \
        / np.maximum(c_ref, 1)
    np.testing.assert_array_equal(np.asarray(c), c_ref)
    np.testing.assert_allclose(np.asarray(v), v_ref, rtol=1e-5)
    assert "mosaic" in pt._LOWERED.get("masked_stats", ()), \
        pt.kernel_state()
    assert "masked_stats" not in pt._FAILED, pt._FAILED


@check("drill_window_gather_stats")
def _():
    from gsky_tpu.ops.drill import masked_mean, window_gather
    T, H, W = 500, 128, 128
    stack = rng.uniform(0, 1, (T, H, W)).astype(np.float32)
    stack[:, :6, :6] = -9.0
    mask = rng.uniform(0, 1, (96, 96)) > 0.4
    tsel = (np.arange(64, dtype=np.int32) * 7) % T
    dev = jnp.asarray(stack)
    dataf, validf = window_gather(dev, jnp.asarray(tsel), np.int32(8),
                                  np.int32(8), jnp.asarray(mask),
                                  np.float32(-9.0), np.bool_(True),
                                  (96, 96))
    v, c = masked_mean(dataf, validf)
    v, c = np.asarray(v), np.asarray(c)
    win = stack[tsel][:, 8:104, 8:104]
    valid_ref = (win != -9.0) & mask[None]
    c_ref = valid_ref.reshape(64, -1).sum(-1)
    v_ref = np.where(c_ref > 0,
                     np.where(valid_ref, win, 0).reshape(64, -1).sum(-1)
                     / np.maximum(c_ref, 1), 0.0)
    np.testing.assert_array_equal(c, c_ref)
    np.testing.assert_allclose(v, v_ref, rtol=1e-4)


@check("deciles_device_vs_host")
def _():
    from gsky_tpu.ops.drill import deciles, deciles_impl
    B, N = 64, 4000
    data = rng.uniform(0, 1, (B, N)).astype(np.float32)
    valid = rng.uniform(0, 1, (B, N)) > 0.3
    valid[0] = False                     # zero-valid band
    valid[1, 5:] = False                 # n < D+1 padding path
    d_dev = np.asarray(deciles(jnp.asarray(data), jnp.asarray(valid), 9))
    d_host = np.asarray(deciles_impl(data, valid, 9, np))
    np.testing.assert_allclose(d_dev, d_host, rtol=1e-6)


# --- scaling / expressions ------------------------------------------------

@check("scale_to_byte_dtypes")
def _():
    from gsky_tpu.ops.scale import scale_to_byte
    for lo, hi in ((0, 255), (-3000, 3000), (0.0, 1.0)):
        data = rng.uniform(lo, hi, (200, 200)).astype(np.float32)
        valid = rng.uniform(0, 1, (200, 200)) > 0.2
        b_d = np.asarray(scale_to_byte(jnp.asarray(data),
                                       jnp.asarray(valid), auto=True))
        b_c = on_cpu(lambda d, v: scale_to_byte(d, v, auto=True),
                     data, valid)
        mism = np.mean(b_d != b_c)
        assert mism < 0.001, f"[{lo},{hi}]: {mism:.2%}"


@check("band_expr_ndvi")
def _():
    from gsky_tpu.ops.expr import parse_band_expressions
    be = parse_band_expressions(["ndvi = (nir - red) / (nir + red)"])
    nir = rng.uniform(0, 1, (128, 128)).astype(np.float32)
    red = rng.uniform(0, 1, (128, 128)).astype(np.float32)
    v = rng.uniform(0, 1, (128, 128)) > 0.2
    ce = be.expressions[0]
    o_d, ok_d = ce.eval_masked({"nir": jnp.asarray(nir),
                                "red": jnp.asarray(red)},
                               {"nir": jnp.asarray(v),
                                "red": jnp.asarray(v)})
    with jax.default_device(CPU):
        o_c, ok_c = ce.eval_masked({"nir": jnp.asarray(nir),
                                    "red": jnp.asarray(red)},
                                   {"nir": jnp.asarray(v),
                                    "red": jnp.asarray(v)})
    np.testing.assert_array_equal(np.asarray(ok_d), np.asarray(ok_c))
    both = np.asarray(ok_d)
    np.testing.assert_allclose(np.asarray(o_d)[both],
                               np.asarray(o_c)[both], rtol=1e-4)


# --- geolocation (curvilinear) warp ---------------------------------------

@check("geoloc_ctrl_render")
def _():
    """Curvilinear ctrl-grid render on chip == CPU lowering: the full
    executor path with a synthetic swath whose analytic inverse is
    known."""
    from gsky_tpu.ops.warp import warp_scenes_ctrl
    S = 256
    scene = rng.uniform(0, 100, (1, S, S)).astype(np.float32)
    # ctrl carries fractional PIXEL coords directly (identity affine),
    # as the geoloc path produces
    gh = 17
    jj = np.linspace(5.0, S - 5.0, gh)
    ctrl = np.stack([
        jj[None, :].repeat(gh, 0) + 3.0 * np.sin(jj / 40.0)[:, None],
        jj[:, None].repeat(gh, 1) + 2.0 * np.cos(jj / 55.0)[None, :],
    ]).astype(np.float32)
    params = np.array([[0, 1, 0, 0, 0, 1, S, S, np.nan, 1.0, 0.0]],
                      np.float32)
    kw = dict(method="near", n_ns=1, out_hw=(256, 256), step=16)
    canv_d, ok_d = warp_scenes_ctrl(jnp.asarray(scene),
                                    jnp.asarray(ctrl),
                                    jnp.asarray(params), **kw)
    with jax.default_device(CPU):
        canv_c, ok_c = warp_scenes_ctrl(jnp.asarray(scene),
                                        jnp.asarray(ctrl),
                                        jnp.asarray(params), **kw)
    np.testing.assert_array_equal(np.asarray(ok_d), np.asarray(ok_c))
    both = np.asarray(ok_d)
    np.testing.assert_allclose(np.asarray(canv_d)[both],
                               np.asarray(canv_c)[both], rtol=1e-5)


# --- control-grid upsample -------------------------------------------------

@check("ctrl_upsample_f64")
def _():
    """`_bilerp_grid` on the chip against a float64 numpy bilinear of
    the same grid, at the export tile's and the map tile's size.  The
    formula itself, in float32, stands up to 2.3 ulp off the float64
    value (four products and three sums of ~1e5 m; the gather form read
    the same): so the chip is held to the float32 formula within 1 ulp
    (what `_window_slice`'s docstring grants XLA's contraction between
    programs) and to float64 within 4."""
    from gsky_tpu.ops.warp import _bilerp_grid
    for h, w, step in ((1024, 1024, 16), (256, 256, 16)):
        gh = (h - 1 + step - 1) // step + 1
        gw = (w - 1 + step - 1) // step + 1
        cc, rr = np.meshgrid(np.arange(gw) * step * 30.0,
                             np.arange(gh) * step * 30.0)
        ctrl = (1.2e5 + cc + 40.0 * np.sin(rr / 900.0)
                + rng.normal(0.0, 3.0, cc.shape)).astype(np.float32)
        got = np.asarray(_bilerp_grid(jnp.asarray(ctrl), h, w, step))
        assert got.shape == (h, w) and got.dtype == np.float32

        def bilinear(dt):
            c = ctrl.astype(dt)
            yy = (np.arange(h, dtype=dt) / dt(step))[:, None]
            xx = (np.arange(w, dtype=dt) / dt(step))[None, :]
            y0 = np.clip(np.floor(yy).astype(int), 0, gh - 2)
            x0 = np.clip(np.floor(xx).astype(int), 0, gw - 2)
            ty, tx = yy - y0.astype(dt), xx - x0.astype(dt)
            return (c[y0, x0] * (1 - ty) + c[y0 + 1, x0] * ty) * (1 - tx) \
                + (c[y0, x0 + 1] * (1 - ty) + c[y0 + 1, x0 + 1] * ty) * tx

        ulp = np.spacing(np.abs(got)).astype(np.float64)
        off32 = np.abs(got.astype(np.float64) - bilinear(np.float32)) / ulp
        off64 = np.abs(got.astype(np.float64) - bilinear(np.float64)) / ulp
        assert off32.max() <= 1.0, (h, w, step, off32.max())
        assert off64.max() <= 4.0, (h, w, step, off64.max())


# --- shared-source multi-tile gather -------------------------------------

@check("warp_gather_shared")
def _():
    """Shared-source multi-tile gather == per-tile gathers."""
    from gsky_tpu.ops.warp import warp_gather, warp_gather_shared
    rows = np.stack([_ROWS + k for k in range(3)])
    cols = np.stack([_COLS - k for k in range(3)])
    out_b, ok_b = warp_gather_shared(
        jnp.asarray(_SRC), jnp.asarray(_VALID), jnp.asarray(rows),
        jnp.asarray(cols), "bilinear")
    out_b, ok_b = np.asarray(out_b), np.asarray(ok_b)
    for k in range(3):
        o, ok = warp_gather(jnp.asarray(_SRC), jnp.asarray(_VALID),
                            jnp.asarray(rows[k]), jnp.asarray(cols[k]),
                            "bilinear")
        np.testing.assert_array_equal(ok_b[k], np.asarray(ok))
        both = ok_b[k]
        np.testing.assert_allclose(out_b[k][both],
                                   np.asarray(o)[both], rtol=1e-5)



# --- band sets over two pixel grids (Sentinel-2 10 m + 20 m) -------------

@check("multigrid_sets_match_reference")
def _():
    """A four-granule false-colour tile (SWIR at 20 m beside NIR and
    green at 10 m) and an NBR tile, each set on two grids, through the
    served path's kernels ON THE CHIP (each grid from its own gather
    window) against the plain references, within the benchmark cell's
    bound."""
    import datetime as dt
    import tempfile

    from benchmarks import reference, reference_expr, reference_rgb
    from benchmarks.archives import sentinel2_bands_by_res as s2r
    from gsky_tpu.geo.crs import EPSG3857
    from gsky_tpu.geo.transform import BBox
    from gsky_tpu.index import MASClient, MASStore
    from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
    from gsky_tpu.pipeline.executor import WarpExecutor
    from gsky_tpu.pipeline.tile_stages import render_staged

    x0, y0 = 399960.0, 6200020.0
    archive = {
        "kind": "sentinel2_bands_by_res", "collection": "s2",
        "file_prefix": "S2A_T55H", "crs": "EPSG:32755", "origin": [x0, y0],
        "pitch_m": 6380.0, "grid": [2, 2], "date": "2020-01-10",
        "resolutions": {
            "r10m": {"res": 10.0, "granule_hw": [700, 700], "wedge_px": 44},
            "r20m": {"res": 20.0, "granule_hw": [350, 350],
                     "wedge_px": 22}},
        "bands": [{"name": n, "namespace": ns, "base": b, "resolution": r}
                  for n, ns, b, r in (
                      ("green", "nbart_green", 900, "r10m"),
                      ("nir", "nbart_nir_1", 3200, "r10m"),
                      ("swir2", "nbart_swir_2", 2400, "r20m"),
                      ("swir3", "nbart_swir_3", 1600, "r20m"))],
        "nodata": -999, "compress": False}
    stamp = dt.datetime(2020, 1, 10, tzinfo=dt.timezone.utc).timestamp()
    store = MASStore()
    with tempfile.TemporaryDirectory() as root:
        for rec in s2r.build(archive, 43, root):
            store.ingest(rec)
        sources = s2r.sources(archive, 43)
        xs = np.array([x0 + 6380.0 - 900.0, x0 + 6380.0 + 1300.0])
        ys = np.array([y0 - 6380.0 - 1100.0, y0 - 6380.0 + 1100.0])
        mx, my = reference.project(xs, ys, "EPSG:32755", "EPSG:3857")
        bbox = (float(mx[0]), float(my[0]), float(mx[1]), float(my[1]))
        fc = ["nbart_swir_2", "nbart_nir_1", "nbart_green"]
        nbr = "(nbart_nir_1 - nbart_swir_3) / (nbart_nir_1 + nbart_swir_3)"
        for bands, n, style in ((fc, 3, (0.0, 254.0 / 4500.0, 4500.0)),
                                (["nbr = " + nbr], 1, (1.0, 127.0, 2.0))):
            pipe = TilePipeline(MASClient(store), executor=WarpExecutor())
            req = GeoTileRequest(
                collection=root + "/s2", bands=bands, bbox=BBox(*bbox),
                crs=EPSG3857, width=256, height=256, start_time=stamp,
                end_time=None, resample="bilinear")
            kind, got = render_staged(pipe, req, n, *style, 0, False)
            (leg, _), = pipe.executor.bucket_stats.items()
            assert leg.startswith(("render_rgba_mg:((4, 2, 3), ((",
                                   "render_expr_mg:((4, 2, 2), ((")), leg
            if n == 3:
                want = reference_rgb.render_rgba(
                    reference_rgb.select_rgb(sources, fc, stamp), bbox,
                    "EPSG:3857", 256, 256, "bilinear", *style)
                rec = reference_rgb.compare(got, want)
            else:
                names = reference_expr.variables(reference_expr.parse(nbr))
                want = reference_expr.render_byte(
                    nbr, reference_expr.select_vars(sources, names, stamp),
                    bbox, "EPSG:3857", 256, 256, "bilinear", *style)
                rec = reference_expr.compare(got, want)
            assert rec["mismatch"] <= 0.005 and rec["max_byte_diff"] <= 1, \
                (leg, rec)


# --- cubic taps fetched as neighbourhoods --------------------------------

@check("cubic_neighbourhoods_match_per_tap")
def _():
    """The export's kernel ON THE CHIP, a 1024² cubic tile from a 1536²
    gather window of a 2048² scene (NaN nodata, a nodata block, the tile
    over the scene's top and left edges), fetching its taps as
    neighbourhoods (`ops.warp._tap_pairs`) against the same program
    with the per-tap `_resample_c` kept in `tests/test_cubic_neighbourhood.py`:
    bit for bit.  Then a cubic export served over HTTP: every
    `export.tile` span reads `tap_form=neighbourhood`, and `/debug`
    counts each resident tile so."""
    import asyncio
    import json
    import os
    import sys
    import tempfile

    from benchmarks.archives import geotiff_scenes
    from gsky_tpu import obs
    from gsky_tpu.index import MASClient, MASStore
    from gsky_tpu.ops.warp import warp_scenes_ctrl_scored
    from gsky_tpu.server.config import ConfigWatcher
    from gsky_tpu.server.metrics import MetricsLogger
    from gsky_tpu.server.ows import OWSServer
    from tests.test_cubic_neighbourhood import _resample_c_per_tap
    warp = sys.modules["gsky_tpu.ops.warp"]

    S, h, step = 2048, 1024, 16
    scene = rng.uniform(100.0, 3000.0, (1, S, S)).astype(np.float32)
    scene[rng.uniform(0, 1, scene.shape) < 0.03] = np.nan
    scene[:, 300:420, 200:380] = np.nan
    gh = (h - 1 + step - 1) // step + 1
    jj, ii = np.meshgrid(np.arange(gh) * step + 0.5,
                         np.arange(gh) * step + 0.5)
    th = np.deg2rad(2.0)
    ctrl = np.stack([-3.0 + 1.3 * (np.cos(th) * jj - np.sin(th) * ii),
                     -2.0 + 1.3 * (np.sin(th) * jj + np.cos(th) * ii)])
    params = np.array([[0.0, 1.0, 0.0, 0.0, 0.0, 1.0, 2000.0, 2010.0,
                        np.nan, 1.0, 0.0]], np.float32)
    args = (jnp.asarray(scene), jnp.asarray(ctrl.astype(np.float32)),
            jnp.asarray(params), jnp.asarray(np.array([0, 0], np.int32)))

    def run():
        fn = warp_scenes_ctrl_scored.__wrapped__
        canv, best = jax.jit(lambda s, c, p, w0: fn(
            s, c, p, "cubic", 1, (h, h), step, win=(1536, 1536),
            win0=w0))(*args)
        return np.asarray(canv), np.asarray(best)
    canv, best = run()
    resample_c = warp._resample_c
    warp._resample_c = _resample_c_per_tap
    try:
        canv_t, best_t = run()
    finally:
        warp._resample_c = resample_c
    ok = best > -np.inf
    assert 0.5 < ok.mean() < 1.0, ok.mean()
    differ = canv.view(np.uint32) != canv_t.view(np.uint32)
    assert not differ.any() and np.array_equal(
        best.view(np.uint32), best_t.view(np.uint32)), (
        f"{int(differ.sum())} of {differ.size} pixels differ, largest "
        f"{float(np.nanmax(np.abs(canv - canv_t)[differ]))}")

    archive = {
        "kind": "geotiff_scenes", "collection": "landsat",
        "file_prefix": "LC08", "crs": "EPSG:32755",
        "origin": [590000.0, 6105000.0], "res": 30.0,
        "scene_hw": [360, 380], "scenes": 1, "shift_m": [0.0, 0.0],
        "first_date": "2020-01-10", "step_days": 1, "namespace": "nbar",
        "nodata": -999, "nodata_corner": 0.125, "compress": False}
    with tempfile.TemporaryDirectory() as root:
        store = MASStore()
        for rec in geotiff_scenes.build(archive, 44, root):
            store.ingest(rec)
        conf = os.path.join(root, "conf")
        os.mkdir(conf)
        with open(os.path.join(conf, "config.json"), "w") as fh:
            json.dump({"service_config": {"ows_hostname": "",
                                          "mas_address": "inproc"},
                       "layers": [{
                           "name": "scene", "title": "cubic",
                           "data_source": os.path.join(root, "landsat"),
                           "rgb_products": ["nbar"],
                           "time_generator": "mas", "resample": "cubic",
                           "wcs_max_tile_width": 64,
                           "wcs_max_tile_height": 64}]}, fh)
        mas = MASClient(store)
        metrics = MetricsLogger()
        server = OWSServer(
            ConfigWatcher(conf, mas_factory=lambda addr: mas,
                          install_signal=False),
            mas_factory=lambda addr: mas, metrics=metrics, gateway=None,
            temp_dir=os.path.join(root, "tmp"))
        x0, y0 = 590000.0 + 100 * 30.0, 6105000.0 - 100 * 30.0
        path = ("/ows?service=WCS&request=GetCoverage&version=1.0.0"
                "&coverage=scene&crs=EPSG:32755&format=GeoTIFF"
                f"&bbox={x0},{y0 - 128 * 30.0},{x0 + 128 * 30.0},{y0}"
                "&width=128&height=128&time=2020-01-10T00:00:00.000Z")
        obs.reset_recorder()

        async def get():
            from aiohttp.test_utils import TestClient, TestServer
            client = TestClient(TestServer(server.app()))
            await client.start_server()
            try:
                resp = await client.get(path)
                return resp.status, await resp.read()
            finally:
                await client.close()
        status, body = asyncio.new_event_loop().run_until_complete(get())
    assert status == 200, body[:300]
    tiles = [sp for t in obs.default_recorder().traces()
             for sp in t.get("spans", []) if sp["name"] == "export.tile"]
    assert len(tiles) == 4, len(tiles)
    assert {sp["attrs"].get("tap_form") for sp in tiles} \
        == {"neighbourhood"}, [sp["attrs"] for sp in tiles]
    stats = metrics.summary()["export_pipeline"]
    assert stats["tap_form"] == {"neighbourhood": stats["tiles_resident"],
                                 "per_tap": 0}, stats


# --- band sets in two CRSs (a tile across a UTM zone line) ---------------

@check("multicrs_sets_match_reference")
def _():
    """Tiles over both rows of Sentinel-2 granules either side of the
    line between UTM zones 55 and 56, true colour and NDVI, through the
    served path's kernels ON THE CHIP (four sets in two CRSs, each set
    warped on its own zone's control grid from its own gather window)
    against the plain references, within the benchmark cell's bound;
    then one-CRS calls, on one pixel grid and on two, against the same
    calls through the parent's `_mosaic_band_sets` (kept in
    `tests/test_multicrs_sets.py`): bit for bit."""
    import os
    import sys
    import tempfile

    from benchmarks import reference_expr, reference_rgb
    from benchmarks.archives import sentinel2_zone_seam as seam
    from gsky_tpu.geo.crs import EPSG3857
    from gsky_tpu.geo.transform import BBox
    from gsky_tpu.index import MASClient, MASStore
    from gsky_tpu.pipeline import GeoTileRequest, TilePipeline
    from gsky_tpu.pipeline.executor import WarpExecutor
    from gsky_tpu.pipeline.tile_stages import render_staged
    from tests import test_multicrs_sets as t
    warp = sys.modules["gsky_tpu.ops.warp"]

    store = MASStore()
    with tempfile.TemporaryDirectory() as root:
        for rec in seam.build(t.ARCHIVE, t.SEED, root):
            store.ingest(rec)
        sources = seam.sources(t.ARCHIVE, t.SEED)
        bbox = t._bbox("seam_both_rows")
        for layer, leg0 in (("truecolour", "render_rgba_mc:((4, 2, 3), ("),
                            ("ndvi", "render_expr_mc:((4, 2, 2), (")):
            products, offset, scale, clip, _ = t.LAYERS[layer]
            pipe = TilePipeline(MASClient(store), executor=WarpExecutor())
            req = GeoTileRequest(
                collection=os.path.join(root, "s2"), bands=products,
                bbox=BBox(*bbox), crs=EPSG3857, width=256, height=256,
                start_time=t.STAMP, end_time=None, resample="bilinear")
            _, got = render_staged(pipe, req, 1 if t._is_expr(layer) else 3,
                                   offset, scale, clip, 0, False)
            (leg, _), = pipe.executor.bucket_stats.items()
            assert leg.startswith(leg0), leg
            want = t._want(sources, layer, bbox)
            compare = reference_expr.compare if t._is_expr(layer) \
                else reference_rgb.compare
            rec = compare(np.asarray(got), want)
            assert rec["mismatch"] <= 0.005 and rec["max_byte_diff"] <= 1, \
                (leg, rec)

    mosaic = warp._mosaic_band_sets
    for kernel in t.KERNELS:
        for grids in (1, 2):
            operands, kw = t._operands(4, t.KERNELS[kernel][0], grids, True)
            run = t._program(kernel, operands, kw)
            now_jaxpr, now = run()
            warp._mosaic_band_sets = t._parent_mosaic_band_sets
            try:
                then_jaxpr, then = run()
            finally:
                warp._mosaic_band_sets = mosaic
            assert now_jaxpr == then_jaxpr, (kernel, grids)
            assert np.array_equal(now, then), (kernel, grids)
