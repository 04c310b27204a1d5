"""A cubic tap set is fetched as neighbourhoods, not a gather a tap.

`ops.warp._resample_c` fetched a cubic pixel's 16 Catmull-Rom taps with
16 gathers, each from its own flat index, and a TPU gather costs by the
index it resolves (~7 ns), not by the values it returns: ~120 of the
128 ms of a 1024 x 1024 export tile.  It now unfolds the source into
rows that each hold a 2 x 4 block and fetches a pixel's 4 x 4
neighbourhood with two gathers of such rows (`_tap_pairs`); weights,
the per-tap validity test and the accumulation order are those of the
per-tap form.  That form lives on here, as it stood, and the new one is
held to it BIT FOR BIT, in both kernel forms (`_use_tapside`): on taps
off each edge of the source, NaN coordinates, nodata taps, pixels whose
valid weights sum to 0.05 or less, one and three channels, and through
the export's kernel `warp_scenes_ctrl_scored` on stacks of depth 1 and
3, windowed and not.  Nearest and bilinear keep a gather a tap: their
programs are the per-tap form's, jaxpr for jaxpr.  So does a cubic
program whose unfolded copies would pass `_UNFOLD_BYTES` (a large
window, a deep stack, a whole scene): `tap_form`, which the executor
records, says which.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gsky_tpu.ops.warp  # noqa: F401

warp = sys.modules["gsky_tpu.ops.warp"]


def _resample_c_per_tap(src, nodata, rows, cols, method: str,
                        unfold: bool = False):
    """`ops.warp._resample_c` as it stood before cubic taps were
    fetched as neighbourhoods: the reference (``unfold``, which the
    kernels now pass, is ignored)."""
    if method not in ("near", "nearest", "bilinear", "cubic"):
        raise KeyError(f"unknown resample method {method!r}")
    H, W, C = src.shape

    if warp._use_tapside():
        def tap(ri, ci, inb):
            v = warp._gather2d_c(src, ri, ci).astype(jnp.float32)
            ok = inb[..., None] & jnp.isfinite(v) & (v != nodata)
            return jnp.where(ok, v, 0.0), ok
    else:
        sf = src.astype(jnp.float32)
        validp = jnp.isfinite(sf) & (sf != nodata)
        srcz = jnp.where(validp, sf, 0.0)

        def tap(ri, ci, inb):
            v = warp._gather2d_c(srcz, ri, ci)
            ok = inb[..., None] & warp._gather2d_c(validp, ri, ci)
            return jnp.where(ok, v, 0.0), ok

    if method in ("near", "nearest"):
        ri = jnp.floor(rows + (0.5 + 1e-10)).astype(jnp.int32)
        ci = jnp.floor(cols + (0.5 + 1e-10)).astype(jnp.int32)
        inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W) \
            & jnp.isfinite(rows) & jnp.isfinite(cols)
        return tap(jnp.clip(ri, 0, H - 1), jnp.clip(ci, 0, W - 1), inb)
    finite = jnp.isfinite(rows) & jnp.isfinite(cols)
    rows = jnp.where(finite, rows, -10.0)
    cols = jnp.where(finite, cols, -10.0)
    r0 = jnp.floor(rows)
    c0 = jnp.floor(cols)
    fr = (rows - r0).astype(jnp.float32)
    fc = (cols - c0).astype(jnp.float32)
    r0 = r0.astype(jnp.int32)
    c0 = c0.astype(jnp.int32)
    if method == "bilinear":
        taps = [(dr, dc, (fr if dr else 1 - fr) * (fc if dc else 1 - fc))
                for dr in (0, 1) for dc in (0, 1)]
        thresh = 1e-6
    else:
        wr = warp._cubic_weights(fr)
        wc = warp._cubic_weights(fc)
        taps = [(dr - 1, dc - 1, wr[dr] * wc[dc])
                for dr in range(4) for dc in range(4)]
        thresh = 0.05
    acc = jnp.zeros(rows.shape + (C,), jnp.float32)
    wacc = jnp.zeros(rows.shape + (C,), jnp.float32)
    for dr, dc, w in taps:
        ri = r0 + dr
        ci = c0 + dc
        inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
        v, okt = tap(jnp.clip(ri, 0, H - 1), jnp.clip(ci, 0, W - 1),
                     inb)
        okf = okt.astype(jnp.float32)
        acc = acc + w[..., None] * okf * v
        wacc = wacc + w[..., None] * okf
    ok = finite[..., None] & (wacc > thresh)
    out = acc / jnp.where(wacc > thresh, wacc, 1.0)
    return out, ok


H, W = 29, 34
NODATA = -999.0


def _source(C, dtype, seed=0):
    """(H, W, C): imagery-like values, a nodata block, scattered nodata
    and (f32) NaN pixels, and a sparse valid lattice inside a nodata
    field, where a pixel's few valid taps can weigh 0.05 or less."""
    rng = np.random.default_rng(seed + C)
    a = rng.uniform(100.0, 3000.0, (H, W, C))
    a[rng.uniform(0, 1, a.shape) < 0.08] = NODATA
    a[3:8, 20:27] = NODATA
    a[18:26, 2:12] = NODATA
    a[19:26:3, 3:12:4] = 1500.0
    a = a.astype(dtype)
    if dtype == np.float32:
        a[rng.uniform(0, 1, a.shape) < 0.04] = np.nan
    return a


def _coords(kind, seed=1):
    """(rows, cols), (24, 20) f32, of one case."""
    rng = np.random.default_rng(seed + len(kind))
    shape = (24, 20)
    rows = rng.uniform(2.0, H - 3.0, shape)
    cols = rng.uniform(2.0, W - 3.0, shape)
    if kind == "top_edge":
        rows = rng.uniform(-3.5, 2.0, shape)
    elif kind == "bottom_edge":
        rows = rng.uniform(H - 3.0, H + 2.5, shape)
    elif kind == "left_edge":
        cols = rng.uniform(-3.5, 2.0, shape)
    elif kind == "right_edge":
        cols = rng.uniform(W - 3.0, W + 2.5, shape)
    elif kind == "nan_coords":
        rows[rng.uniform(0, 1, shape) < 0.3] = np.nan
        cols[rng.uniform(0, 1, shape) < 0.3] = np.nan
        rows[0, :] = np.inf
    elif kind == "nodata_taps":
        rows = rng.uniform(1.0, 9.0, shape)
        cols = rng.uniform(17.0, 30.0, shape)
    elif kind == "low_weight":
        rows = rng.uniform(18.0, 26.0, shape)
        cols = rng.uniform(1.0, 12.0, shape)
    else:
        assert kind == "anywhere"
        rows = rng.uniform(-6.0, H + 6.0, shape)
        cols = rng.uniform(-6.0, W + 6.0, shape)
    return rows.astype(np.float32), cols.astype(np.float32)


def _valid_weight_sums(src, rows, cols):
    """numpy: per pixel and channel, the summed cubic weights of the
    taps that lie inside and hold data, and whether any tap does."""
    r0, c0 = np.floor(rows), np.floor(cols)
    wr = warp._cubic_weights((rows - r0).astype(np.float32), np)
    wc = warp._cubic_weights((cols - c0).astype(np.float32), np)
    sf = src.astype(np.float32)
    valid = np.isfinite(sf) & (sf != NODATA)
    tot = np.zeros(rows.shape + (src.shape[2],), np.float64)
    anyv = np.zeros(tot.shape, bool)
    for dr in range(4):
        for dc in range(4):
            ri = r0.astype(int) + dr - 1
            ci = c0.astype(int) + dc - 1
            inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
            ok = inb[..., None] & valid[np.clip(ri, 0, H - 1),
                                        np.clip(ci, 0, W - 1)]
            tot += np.where(ok, (wr[dr] * wc[dc])[..., None], 0.0)
            anyv |= ok
    return tot, anyv


S = 96


def _scored_inputs(B, windowed):
    """A tile's control grid over a (B, 96, 96) f32 stack whose scenes
    are 90 x 92 (bucket-padded), NaN-encoded nodata and -999 blocks: the
    tile runs off the top and left edges of the scenes (off the window's,
    windowed) and past their true extent."""
    rng = np.random.default_rng(11 + B)
    stack = rng.uniform(100.0, 3000.0, (B, S, S)).astype(np.float32)
    stack[rng.uniform(0, 1, stack.shape) < 0.05] = np.nan
    stack[:, 30:40, 50:64] = NODATA
    stack[:, 90:, :] = np.nan
    stack[:, :, 92:] = np.nan
    out_hw, step = (64, 64), 16
    gh = gw = (64 - 1 + step - 1) // step + 1
    ctrl = np.stack([
        np.linspace(-4.0, S + 2.0, gw, dtype=np.float32)[None, :]
        .repeat(gh, 0),
        np.linspace(-3.0, S + 1.0, gh, dtype=np.float32)[:, None]
        .repeat(gw, 1)])
    params = np.zeros((B, 11), np.float32)
    for k in range(B):
        params[k] = [0.37 * k, 1.01, 0.02, 0.21 * k, -0.01, 0.99, 90, 92,
                     NODATA, 10.0 + k, 0.0]
    win, win0 = ((80, 80), jnp.asarray(np.array([4, 6], np.int32))) \
        if windowed else (None, None)
    return (jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params),
            out_hw, step, win, win0)


RESAMPLE = [f"resample:{kind}:C{C}:{dt.__name__}"
            for kind in ("top_edge", "bottom_edge", "left_edge",
                         "right_edge", "nan_coords", "nodata_taps",
                         "low_weight", "anywhere")
            for C in (1, 3) for dt in (np.float32, np.int16)]
SCORED = [f"scored:depth{B}:{'window' if w else 'whole'}"
          for B in (1, 3) for w in (False, True)]


def _run(case, monkeypatch):
    """(new, reference) outputs of one case, each a list of arrays."""
    if case.startswith("resample:"):
        _, kind, C, dt = case.split(":")
        src = _source(int(C[1:]), np.dtype(dt).type)
        rows, cols = _coords(kind)
        if kind == "low_weight":
            # the case holds what it is named for: pixels with a valid
            # tap whose valid weights sum to 0.05 or less
            tot, anyv = _valid_weight_sums(src, rows, cols)
            assert (anyv & (tot <= 0.05)).any()
        args = (jnp.asarray(src), jnp.float32(NODATA), jnp.asarray(rows),
                jnp.asarray(cols))

        def call(fn):
            return list(jax.jit(
                lambda s, n, r, c: fn(s, n, r, c, "cubic", True))(*args))
        return call(warp._resample_c), call(_resample_c_per_tap)
    _, depth, windowed = case.split(":")
    stack, ctrl, params, out_hw, step, win, win0 = _scored_inputs(
        int(depth[5:]), windowed == "window")

    def call():
        fn = warp.warp_scenes_ctrl_scored.__wrapped__
        return list(jax.jit(lambda s, c, p, w0: fn(
            s, c, p, "cubic", 1, out_hw, step, win=win, win0=w0))(
                stack, ctrl, params, win0))
    new = call()
    monkeypatch.setattr(warp, "_resample_c", _resample_c_per_tap)
    return new, call()


@pytest.mark.parametrize("tapside", [True, False])
@pytest.mark.parametrize("case", RESAMPLE + SCORED)
def test_neighbourhoods_equal_the_per_tap_gathers(case, tapside,
                                                  monkeypatch):
    monkeypatch.setattr(warp, "_use_tapside", lambda: tapside)
    new, ref = _run(case, monkeypatch)
    for a, b in zip(new, ref):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        if a.dtype == np.float32:
            # the same bits: NaN where NaN, -0.0 where -0.0
            np.testing.assert_array_equal(a.view(np.uint32),
                                          b.view(np.uint32))
        else:
            np.testing.assert_array_equal(a, b)
    # every case renders something and refuses something
    ok = np.asarray(new[1]) > -np.inf if case.startswith("scored") \
        else np.asarray(new[1])
    assert ok.any() and not ok.all()


@pytest.mark.parametrize("tapside", [True, False])
@pytest.mark.parametrize("method", ["near", "nearest", "bilinear"])
def test_nearest_and_bilinear_trace_the_per_tap_program(method, tapside,
                                                        monkeypatch):
    monkeypatch.setattr(warp, "_use_tapside", lambda: tapside)
    src = jnp.asarray(_source(3, np.float32))
    rows, cols = map(jnp.asarray, _coords("anywhere"))

    def jaxpr(fn):
        return str(jax.make_jaxpr(
            lambda s, r, c: fn(s, jnp.float32(NODATA), r, c, method,
                               True))(src, rows, cols))
    assert jaxpr(warp._resample_c) == jaxpr(_resample_c_per_tap)
    stack = jax.ShapeDtypeStruct((1, S, S), jnp.float32)
    assert warp.tap_form(method, stack, None) == "per_tap"
    assert warp.tap_form("cubic", stack, None) == "neighbourhood"


def _bytes(H, W, C, itemsize):
    """What the unfolded copy of one source holds: 8 values a value of
    the source padded by 3."""
    return (H + 5) * (W + 3) * 8 * C * itemsize


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# The stack the export's scored kernel reads: a 7,601 x 7,761 Landsat
# scene as the scene cache holds it, NaN-encoded f32 padded to 256-px
# buckets, at depth 1 (`test_the_export_stack_is_the_scene_caches`).
EXPORT_STACK = (1, 7680, 7936)

# (stack, dtype, window, the form on the chip's tap-side kernel); the
# tile's size does not enter
SIZES = {
    # the export cell's tile, at each of its window buckets: 76 MB at
    # the largest
    "export_tile": (EXPORT_STACK, jnp.float32, (1536, 1536),
                    "neighbourhood"),
    "export_win1024x1536": (EXPORT_STACK, jnp.float32, (1024, 1536),
                            "neighbourhood"),
    "export_win1024": (EXPORT_STACK, jnp.float32, (1024, 1024),
                       "neighbourhood"),
    "export_win768": (EXPORT_STACK, jnp.float32, (768, 768),
                      "neighbourhood"),
    # a 1536² int16 window: 38 MB a scene
    "win1536_int16": ((1, 2048, 2048), jnp.int16, (1536, 1536),
                      "neighbourhood"),
    "depth2_1536": ((2, 2048, 2048), jnp.int16, (1536, 1536),
                    "neighbourhood"),
    "depth4_1536": ((4, 2048, 2048), jnp.int16, (1536, 1536), "per_tap"),
    "depth2_1536_f32": ((2, 2048, 2048), jnp.float32, (1536, 1536),
                        "per_tap"),
    "win2048_int16": ((1, 4096, 4096), jnp.int16, (2048, 2048),
                      "neighbourhood"),
    "win1024_f32": ((1, 2048, 2048), jnp.float32, (1024, 1024),
                    "neighbourhood"),
    "win4096_int16": ((1, 7680, 7936), jnp.int16, (4096, 4096), "per_tap"),
    "win4096_f32": ((1, 7680, 7936), jnp.float32, (4096, 4096), "per_tap"),
    # no window: the footprint covers the whole stack
    "whole_scenes": ((10, 7680, 7936), jnp.int16, None, "per_tap"),
    "whole_scene": ((1, 7680, 7936), jnp.int16, None, "per_tap"),
    "unstacked": (((1024, 1024),) * 4, jnp.int16, (384, 384),
                  "neighbourhood"),
}


@pytest.mark.parametrize("size", sorted(SIZES))
def test_the_form_fits_the_memory_the_copies_take(size, monkeypatch):
    """`tap_form` (what the executor records) sums `_unfold_bytes` over
    the sources a scored program resamples and unfolds only within
    `_UNFOLD_BYTES`: the export's tiles fetch neighbourhoods at every
    window bucket; large windows, deep stacks and whole scenes keep a
    gather a tap."""
    monkeypatch.setattr(warp, "_use_tapside", lambda: True)
    shape, dt, win, want = SIZES[size]
    if isinstance(shape[0], tuple):
        stack = tuple(_sds(s, dt) for s in shape)
        hw = [win or s for s in shape]
    else:
        stack = _sds(shape, dt)
        hw = [win or shape[1:]] * shape[0]
    need = sum(_bytes(a, b, 1, np.dtype(dt).itemsize) for a, b in hw)
    assert (need <= warp._UNFOLD_BYTES) == (want == "neighbourhood")
    assert warp.tap_form("cubic", stack, win) == want
    # the bound, to the byte
    monkeypatch.setattr(warp, "_UNFOLD_BYTES", need)
    assert warp.tap_form("cubic", stack, win) == "neighbourhood"
    monkeypatch.setattr(warp, "_UNFOLD_BYTES", need - 1)
    assert warp.tap_form("cubic", stack, win) == "per_tap"


def test_the_export_stack_is_the_scene_caches(tmp_path, monkeypatch):
    """The gate counts the dtype the device holds.  An int16 Landsat
    scene with a nodata value reaches the scored kernel as the scene
    cache holds it, NaN-encoded f32 padded to 256-px buckets, stacked
    at depth 1 as `WarpExecutor._stacked` stacks it: at the cell's
    scene size that is `EXPORT_STACK`, and its 1024² tiles from the
    largest window bucket, 1536², fetch their taps as neighbourhoods."""
    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.io import write_geotiff
    from gsky_tpu.pipeline import scene_cache
    from gsky_tpu.pipeline.types import Granule

    monkeypatch.setattr(warp, "_use_tapside", lambda: True)
    data = np.random.default_rng(46).uniform(
        100.0, 3000.0, (300, 380)).astype(np.int16)
    data[:40, :40] = -999
    gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
    path = str(tmp_path / "LC08_scene.tif")
    write_geotiff(path, data, gt, parse_crs("EPSG:32755"), nodata=-999)
    g = Granule(path=path, ds_name=path, namespace="nbar",
                base_namespace="nbar", band=1, time_index=None,
                timestamp=0.0, geo_transform=list(gt.to_gdal()),
                srs="EPSG:32755", nodata=-999.0)
    cache = scene_cache.SceneCache(max_bytes=1 << 30)
    scene = cache.get(g)
    stack = cache.stack((scene.serial, 1), lambda: jnp.stack([scene.dev]))
    assert stack.shape == (1, 512, 512) and stack.dtype == jnp.float32
    got = np.asarray(stack[0])
    assert np.isnan(got[:40, :40]).all() and np.isnan(got[300:]).all()
    np.testing.assert_array_equal(got[40:300, 40:380], data[40:, 40:])

    served = _sds((1, scene_cache._bucket(7601), scene_cache._bucket(7761)),
                  stack.dtype)
    assert served.shape == EXPORT_STACK
    assert warp.tap_form("cubic", served, (1536, 1536)) == "neighbourhood"


def _gathers(jaxpr) -> int:
    return str(jaxpr).count(" gather[")


def _sets_program(G, C, win):
    """The jaxpr of `_mosaic_band_sets` (`render_rgba_ctrl`'s and
    `render_expr_ctrl`'s warp) over G granules of C uint16 bands."""
    rng = np.random.default_rng(5)
    bands = tuple(tuple(jnp.asarray(rng.integers(1, 4000, (S, S))
                                    .astype(np.uint16)) for _ in range(C))
                  for _ in range(G))
    step = 16
    gh = (32 - 1 + step - 1) // step + 1
    ctrl = jnp.asarray(np.stack(np.meshgrid(
        np.linspace(4.0, 40.0, gh), np.linspace(4.0, 40.0, gh)))
        .astype(np.float32))
    params = jnp.asarray(np.tile(np.array(
        [0, 1, 0, 0, 0, 1, S, S, 0, 1, 0], np.float32), (G, 1)))
    prios = jnp.ones((G, C), jnp.float32)
    return jax.make_jaxpr(lambda b, c, p, q, w0: warp._mosaic_band_sets(
        b, c, p, q, "cubic", (32, 32), step, win, w0))(
            bands, ctrl, params, prios, jnp.zeros((G, 2), jnp.int32))


KERNELS = [f"{k}:{side}" for k in ("scored:depth3:window",
                                   "scored:depth1:whole",
                                   "sets:G2:C1:window", "sets:G1:C1:whole",
                                   "sets:G2:C3:window")
           for side in ("within", "over")]


@pytest.mark.parametrize("case", KERNELS)
def test_a_program_over_the_bound_gathers_a_tap_at_a_time(case,
                                                          monkeypatch):
    """The kernels decide from the shapes they trace: the band-set
    kernels sum every granule's window, the scored kernel every scene of
    its stack; one byte over the sum and the program is the per-tap
    one, 16 tap gathers a source where it had 2, whatever its
    channels."""
    monkeypatch.setattr(warp, "_use_tapside", lambda: True)
    kind, n, *rest = case.split(":")
    where, side = rest[-2:]
    if kind == "scored":
        B = int(n[5:])
        win = (80, 80) if where == "window" else None
        stack, ctrl, params, out_hw, step, _, win0 = _scored_inputs(
            B, win is not None)
        if win0 is None:
            win0 = jnp.zeros((2,), jnp.int32)
        hw = win or (S, S)
        need = B * _bytes(*hw, 1, 4)
        fn = warp.warp_scenes_ctrl_scored.__wrapped__

        def program():
            return jax.make_jaxpr(lambda s, c, p, w0: fn(
                s, c, p, "cubic", 1, out_hw, step, win=win, win0=w0))(
                    stack, ctrl, params, win0)
        # the stack's scenes share one vmap'd gather a tap
        unfolded = 1
    else:
        G, C = int(n[1:]), int(rest[0][1:])
        win = (48, 48) if where == "window" else None
        hw = win or (S, S)
        need = G * _bytes(*hw, C, 2)

        def program():
            return _sets_program(G, C, win)
        unfolded = G
    monkeypatch.setattr(warp, "_UNFOLD_BYTES",
                        need if side == "within" else need - 1)
    got = _gathers(program())
    monkeypatch.setattr(warp, "_UNFOLD_BYTES", -1)
    per_tap = _gathers(program())
    assert per_tap - got == (14 * unfolded if side == "within" else 0)
