"""Reprojection warp: the hot kernel, TPU-first.

The reference's warp is a per-row C loop: transform dst pixel centres to src
coords, then nearest-neighbour gather via GDALReadBlock with a hand-rolled
block cache (`worker/gdalprocess/warp.go:82-410`).  Here the same operation
is a fused XLA program: the coordinate grid is elementwise projection math
(`gsky_tpu.geo.crs`) and the resample is a vectorised gather, `vmap`-batched
over granules so one TPU dispatch warps a whole stack of source windows.

Resampling methods: nearest (reference parity), bilinear and cubic
(Catmull-Rom), both nodata-aware via weight renormalisation (matching
GDAL's masked-resample behaviour).

Precision note: coordinate grids should be computed in float64 (host numpy
by default — see `coord_grid`) because projected magnitudes ~2e7 lose
sub-pixel precision in f32; the *gather* then runs on device in f32 on
window-relative coordinates, which are small and exact.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..geo.crs import CRS
from ..geo.transform import GeoTransform

# ---------------------------------------------------------------------------
# Coordinate grids (host, float64)
# ---------------------------------------------------------------------------

def coord_grid(dst_gt: GeoTransform, dst_crs: CRS, height: int, width: int,
               src_gt: GeoTransform, src_crs: CRS, xp=np):
    """Map every dst pixel centre into fractional src *index* coordinates.

    Returns (rows, cols), each (height, width); integer value k means the
    centre of src pixel k.  Out-of-projection points come back NaN and
    resolve to nodata in the gather.
    """
    c = xp.arange(width, dtype=xp.float64) + 0.5
    r = xp.arange(height, dtype=xp.float64) + 0.5
    C, R = xp.meshgrid(c, r)
    x, y = dst_gt.pixel_to_geo(C, R, xp)
    sx, sy = dst_crs.transform_to(src_crs, x, y, xp)
    col, row = src_gt.geo_to_pixel(sx, sy, xp)
    return row - 0.5, col - 0.5


def src_window(rows: np.ndarray, cols: np.ndarray, src_h: int, src_w: int,
               margin: int = 2) -> Optional[Tuple[int, int, int, int]]:
    """Bounding src window (col0, row0, w, h) covering the warp's gather
    footprint, or None when the dst tile misses the source entirely —
    the sub-window clamp of `worker/gdalprocess/warp.go:200-217`."""
    ok = np.isfinite(rows) & np.isfinite(cols)
    if not ok.any():
        return None
    rmin = int(np.floor(rows[ok].min())) - margin
    rmax = int(np.ceil(rows[ok].max())) + margin + 1
    cmin = int(np.floor(cols[ok].min())) - margin
    cmax = int(np.ceil(cols[ok].max())) + margin + 1
    rmin, rmax = max(rmin, 0), min(rmax, src_h)
    cmin, cmax = max(cmin, 0), min(cmax, src_w)
    if rmin >= rmax or cmin >= cmax:
        return None
    return cmin, rmin, cmax - cmin, rmax - rmin


def pick_overview(rows: np.ndarray, cols: np.ndarray,
                  levels: Tuple[int, ...]) -> int:
    """Choose the coarsest decimation level (power-of-two style factor list,
    e.g. (1,2,4,8)) whose resolution still meets the request — the overview
    selection of `worker/gdalprocess/warp.go:156-198`."""
    h, w = rows.shape
    if h < 2 or w < 2:
        return 1
    # median absolute source step per dst pixel
    dr = np.nanmedian(np.abs(np.diff(rows, axis=0)))
    dc = np.nanmedian(np.abs(np.diff(cols, axis=1)))
    stride = min(dr, dc)
    if not np.isfinite(stride) or stride <= 1.0:
        return 1
    best = 1
    for f in sorted(levels):
        if f <= stride:
            best = f
    return best


# ---------------------------------------------------------------------------
# Device gather kernels
# ---------------------------------------------------------------------------

def _gather2d(src, ri, ci):
    """Flat gather from a 2D array with pre-clipped integer indices."""
    H, W = src.shape
    return src.reshape(-1)[ri * W + ci]


def _window_slice(arr, win, win0, axis: int):
    """Dynamic-slice the two spatial axes (axis, axis+1) of ``arr`` to
    the static window ``win`` = (WR, WC) at traced (2,) int32 origin
    ``win0``.  Returns (sliced, r0f, c0f): the f32 origins callers
    subtract from their coordinate grids — exact, because subtracting
    an integer from an f32 coordinate of the same magnitude (both
    < 2^14 at granule size) never rounds.
    Nearest results are bit-identical to the full-scene kernel;
    interpolated methods can differ by 1 ulp where XLA contracts the
    tap-weight arithmetic differently between the two programs."""
    r0 = win0[0]
    c0 = win0[1]
    starts = [jnp.int32(0)] * arr.ndim
    sizes = list(arr.shape)
    starts[axis] = r0
    starts[axis + 1] = c0
    sizes[axis] = win[0]
    sizes[axis + 1] = win[1]
    out = jax.lax.dynamic_slice(arr, tuple(starts), tuple(sizes))
    return out, r0.astype(jnp.float32), c0.astype(jnp.float32)


def _nearest(src, valid, rows, cols):
    H, W = src.shape
    # reference parity: the C kernel truncates (int)(px + 1e-10) in
    # corner-based coords (warp.go:275) == floor(centre_coord + 0.5 + eps);
    # jnp.round would tie-break half-to-even and pick different pixels
    ri = jnp.floor(rows + (0.5 + 1e-10)).astype(jnp.int32)
    ci = jnp.floor(cols + (0.5 + 1e-10)).astype(jnp.int32)
    inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W) \
        & jnp.isfinite(rows) & jnp.isfinite(cols)
    ri = jnp.clip(ri, 0, H - 1)
    ci = jnp.clip(ci, 0, W - 1)
    out = _gather2d(src, ri, ci)
    ok = inb & _gather2d(valid, ri, ci)
    return out, ok


def _bilinear(src, valid, rows, cols):
    H, W = src.shape
    finite = jnp.isfinite(rows) & jnp.isfinite(cols)
    rows = jnp.where(finite, rows, -10.0)
    cols = jnp.where(finite, cols, -10.0)
    r0 = jnp.floor(rows)
    c0 = jnp.floor(cols)
    fr = (rows - r0).astype(src.dtype)
    fc = (cols - c0).astype(src.dtype)
    r0 = r0.astype(jnp.int32)
    c0 = c0.astype(jnp.int32)
    acc = jnp.zeros(rows.shape, src.dtype)
    wacc = jnp.zeros(rows.shape, src.dtype)
    for dr in (0, 1):
        for dc in (0, 1):
            ri = r0 + dr
            ci = c0 + dc
            w = (fr if dr else 1 - fr) * (fc if dc else 1 - fc)
            inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
            ric = jnp.clip(ri, 0, H - 1)
            cic = jnp.clip(ci, 0, W - 1)
            v = _gather2d(src, ric, cic)
            ok = (inb & _gather2d(valid, ric, cic)).astype(src.dtype)
            acc = acc + w * ok * v
            wacc = wacc + w * ok
    ok = finite & (wacc > 1e-6)
    out = acc / jnp.where(wacc > 1e-6, wacc, 1.0)
    return out, ok


def _cubic_weights(f, xp=jnp):
    """Catmull-Rom (a=-0.5) weights for taps at offsets -1,0,1,2."""
    a = -0.5
    f2 = f * f
    f3 = f2 * f
    w0 = a * (f3 - 2 * f2 + f)
    w1 = (a + 2) * f3 - (a + 3) * f2 + 1
    w2 = -(a + 2) * f3 + (2 * a + 3) * f2 - a * f
    w3 = a * (f2 - f3)
    return (w0, w1, w2, w3)


def _cubic(src, valid, rows, cols):
    H, W = src.shape
    finite = jnp.isfinite(rows) & jnp.isfinite(cols)
    rows = jnp.where(finite, rows, -10.0)
    cols = jnp.where(finite, cols, -10.0)
    r0 = jnp.floor(rows)
    c0 = jnp.floor(cols)
    fr = (rows - r0).astype(src.dtype)
    fc = (cols - c0).astype(src.dtype)
    r0 = r0.astype(jnp.int32)
    c0 = c0.astype(jnp.int32)
    wr = _cubic_weights(fr)
    wc = _cubic_weights(fc)
    acc = jnp.zeros(rows.shape, src.dtype)
    wacc = jnp.zeros(rows.shape, src.dtype)
    for dr in range(4):
        for dc in range(4):
            ri = r0 + (dr - 1)
            ci = c0 + (dc - 1)
            w = wr[dr] * wc[dc]
            inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
            ric = jnp.clip(ri, 0, H - 1)
            cic = jnp.clip(ci, 0, W - 1)
            v = _gather2d(src, ric, cic)
            ok = (inb & _gather2d(valid, ric, cic)).astype(src.dtype)
            acc = acc + w * ok * v
            wacc = wacc + w * ok
    # require meaningful positive total weight (cubic weights can cancel)
    ok = finite & (wacc > 0.05)
    out = acc / jnp.where(wacc > 0.05, wacc, 1.0)
    return out, ok


_METHODS = {"near": _nearest, "nearest": _nearest,
            "bilinear": _bilinear, "cubic": _cubic}


@functools.partial(jax.jit, static_argnames=("method",))
def warp_gather(src, valid, rows, cols, method: str = "near"):
    """Resample ``src`` (H, W) at fractional index coords (h, w).

    valid: bool (H, W) — source validity (nodata mask).
    Returns (out (h, w) f32, ok (h, w) bool).
    """
    return _METHODS[method](src, valid, rows, cols)


@functools.partial(jax.jit, static_argnames=("method",))
def warp_gather_batch(src, valid, rows, cols, method: str = "near"):
    """vmap'd warp: src (B, H, W), valid (B, H, W), rows/cols (B, h, w) —
    one XLA dispatch warps a whole granule batch (the TPU replacement for
    the reference's per-granule worker RPCs, cf. SURVEY §2.8 P6)."""
    return jax.vmap(lambda s, v, r, c: _METHODS[method](s, v, r, c))(
        src, valid, rows, cols)


def _cell_corners(ctrl, h: int, w: int, step: int, x0=0):
    """The four corner values of every pixel's control-grid cell, as
    (c00, c10, c01, c11), each (h, w): pixel (i, j) holds the corners
    of cell (min(i // step, gh - 2), min((x0 + j) // step, gw - 2)).

    The grid is regular, so which cell a pixel reads is known when the
    program is traced: each corner plane is a (gh - 1, gw - 1) slice of
    ``ctrl`` with every value repeated ``step`` x ``step`` times, a
    broadcast and a reshape.  No gather: a TPU gather costs by the
    element gathered whatever it reads (PERF.md, PRs 27, 38).  Past the
    last cell (the pixel ON the last node, a strip's padding columns)
    the last cell repeats, which is the clip to ``gh - 2``.

    ``x0`` a Python int: every cut is static.  ``x0`` traced (the SPMD
    render's strip): the strip's cells are cut from the control
    columns first, then the strip from their pixels.

    The planes leave through an optimization barrier, where the gathers'
    results stood: what a kernel does with them is then the program it
    was, and two kernels over one grid (the staged and the modular
    route of a tile) round alike, to the byte."""
    gh, gw = ctrl.shape
    p = jnp.stack([ctrl[:-1, :-1], ctrl[1:, :-1],
                   ctrl[:-1, 1:], ctrl[1:, 1:]])

    if isinstance(x0, int):
        c0 = min(x0 // step, gw - 2)
        nx = -(-(x0 - c0 * step + w) // step)

        def cut(a, start, size):
            return lax.slice_in_dim(a, start, start + size, axis=2)
    else:
        # a strip of w pixels touches at most nx cells, whatever its
        # alignment; past the grid every cell is the last one, so the
        # clamped start of the second cut reads the same values
        c0 = jnp.clip(x0 // step, 0, gw - 2)
        nx = (w - 1) // step + 2

        def cut(a, start, size):
            return lax.dynamic_slice_in_dim(a, start, size, axis=2)
    ny = -(-h // step)
    # the last cell again, as far as a row or a strip may reach
    p = jnp.pad(p, ((0, 0), (0, max(ny - (gh - 1), 0)), (0, nx)),
                mode="edge")[:, :ny]
    p = cut(p, c0, nx)
    # columns first, on (ny, nx) cells: merging (nx, step) into the
    # minor dimension is a relayout, paid on ny rows and not on h; the
    # rows then repeat along the second-minor dimension
    p = jnp.broadcast_to(p[..., None], (4, ny, nx, step))
    p = cut(p.reshape(4, ny, nx * step), x0 - c0 * step, w)
    p = jnp.broadcast_to(p[:, :, None, :], (4, ny, step, w))
    p = lax.optimization_barrier(p.reshape(4, ny * step, w)[:, :h])
    return p[0], p[1], p[2], p[3]


def _bilerp_grid(ctrl, h: int, w: int, step: int, x0=0):
    """Upsample a control-point grid (gh, gw) to full (h, w) dst
    resolution — the on-device analogue of GDAL's approx transformer
    (`worker/gdalprocess/warp.go:219` uses err 0.125 px): the host
    projects only every ``step``-th pixel centre; the dense grid is
    bilinear interpolation, whose error over a few-hundred-metre block is
    far below a pixel for any smooth projection.  The corners reach a
    pixel without a gather (`_cell_corners`); a NaN node poisons
    exactly the pixels of the cells it is a corner of.

    ``x0``: global x of this grid's first column — the SPMD render
    shards the output width, and each shard reconstructs only its strip
    of the dense grid from the (replicated, tiny) ctrl points."""
    gh, gw = ctrl.shape
    yy = jnp.arange(h, dtype=jnp.float32)[:, None] / step
    xx = (x0 + jnp.arange(w, dtype=jnp.float32)[None, :]) / step
    ty = yy - jnp.clip(jnp.floor(yy).astype(jnp.int32), 0, gh - 2)
    tx = xx - jnp.clip(jnp.floor(xx).astype(jnp.int32), 0, gw - 2)
    c00, c10, c01, c11 = _cell_corners(ctrl, h, w, step, x0)
    return (c00 * (1 - ty) + c10 * ty) * (1 - tx) \
        + (c01 * (1 - ty) + c11 * ty) * tx


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "win"))
def warp_scenes_ctrl(stack, ctrl, params, method: str = "near",
                     n_ns: int = 1, out_hw: Tuple[int, int] = (256, 256),
                     step: int = 16, win: Optional[Tuple[int, int]] = None,
                     win0=None):
    """`warp_scenes_batch` with the coordinate grid reconstructed ON
    DEVICE from sparse control points: ctrl (2, gh, gw) f32 holds the
    origin-relative src-CRS coords of every ``step``-th dst pixel centre,
    so a 256x256 tile uploads ~2 KB of coordinates instead of 512 KB.

    win/win0: optional gather window — static (WR, WC) + traced (2,)
    int32 origin.  The executor guarantees the whole tile's gather
    footprint (+resampling margin) fits the window; the kernel then
    gathers from a dynamic slice of the stack instead of the full
    scenes, which cuts the TPU gather cost (it scales with the source
    extent, not the tap count).  Exact re-indexing: nearest is
    bit-identical to the unwindowed path; interpolated methods agree
    to 1 ulp (XLA weight-arithmetic contraction between programs).
    """
    h, w = out_hw
    sx = _bilerp_grid(ctrl[0], h, w, step)
    sy = _bilerp_grid(ctrl[1], h, w, step)
    return _warp_scenes_core(stack, sx, sy, params, method, n_ns,
                             win=win, win0=win0)


def composite_scale(canv, vals, scale_params, auto: bool,
                    colour_scale: int):
    """Shared render epilogue: first-valid composite across namespace
    canvases + byte scaling.  canv (n_ns, h, w) f32, vals (n_ns, h, w)
    bool -> uint8 (h, w), 255 = nodata.  Factored out so the fused
    pallas warp kernel (`ops.pallas_tpu.render_scenes_pallas`) reuses
    the exact op sequence — render parity is composite parity."""
    from .scale import auto_byte_scale, scale_to_byte
    idx = jnp.argmax(vals, axis=0)
    data = jnp.take_along_axis(canv, idx[None], axis=0)[0]
    ok = jnp.any(vals, axis=0)
    if auto:
        if colour_scale == 1:
            logged = jnp.log10(data)
            bad = ~jnp.isfinite(logged)
            data = jnp.where(bad, 0.0, logged)
            ok = ok & ~bad
        big = jnp.float32(3.4e38)
        mn = jnp.min(jnp.where(ok, data, big))
        mx = jnp.max(jnp.where(ok, data, -big))
        return auto_byte_scale(data, ok, mn, mx, jnp.any(ok))
    return scale_to_byte(data, ok, scale_params[0], scale_params[1],
                         scale_params[2], colour_scale=colour_scale,
                         auto=False)


def _render_scenes_core(stack, ctrl, params, scale_params, method: str,
                        n_ns: int, out_hw: Tuple[int, int], step: int,
                        auto: bool, colour_scale: int, win=None,
                        win0=None):
    h, w = out_hw
    sx = _bilerp_grid(ctrl[0], h, w, step)
    sy = _bilerp_grid(ctrl[1], h, w, step)
    canv, vals = _warp_scenes_core(stack, sx, sy, params, method, n_ns,
                                   win=win, win0=win0)
    return composite_scale(canv, vals, scale_params, auto, colour_scale)


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "auto", "colour_scale", "win"))
def render_scenes_ctrl(stack, ctrl, params, scale_params,
                       method: str = "near", n_ns: int = 1,
                       out_hw: Tuple[int, int] = (256, 256),
                       step: int = 16, auto: bool = True,
                       colour_scale: int = 0,
                       win: Optional[Tuple[int, int]] = None, win0=None):
    """The WHOLE GetMap tile in one dispatch: control-grid coords ->
    warp -> per-namespace newest-wins mosaic -> first-valid composite
    across namespaces -> byte scaling.  Returns the PNG-ready uint8
    (h, w) tile (255 = nodata), so a request costs three small uploads,
    one execution and one 64 KB download — the shape that wins when
    device round trips, not FLOPs, bound throughput.

    scale_params: (3,) f32 [offset, scale, clip] (ignored when auto).
    """
    return _render_scenes_core(stack, ctrl, params, scale_params, method,
                               n_ns, out_hw, step, auto, colour_scale,
                               win=win, win0=win0)


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "auto", "colour_scale", "win"))
def render_scenes_bands_ctrl(stack, ctrl, params, scale_params, out_sel,
                             method: str = "near", n_ns: int = 1,
                             out_hw: Tuple[int, int] = (256, 256),
                             step: int = 16, auto: bool = True,
                             colour_scale: int = 0,
                             win: Optional[Tuple[int, int]] = None,
                             win0=None):
    """Multi-band variant of `render_scenes_ctrl` for RGB(A) styles:
    instead of compositing namespaces it emits one scaled uint8 plane
    per selected namespace — out_sel (n_out,) int32 indexes the mosaic
    canvases (expression order -> namespace id).  Auto mode scales each
    band by its own min-max, matching the modular per-band path.
    Returns uint8 (n_out, h, w)."""
    from .scale import auto_byte_scale, scale_to_byte
    h, w = out_hw
    sx = _bilerp_grid(ctrl[0], h, w, step)
    sy = _bilerp_grid(ctrl[1], h, w, step)
    canv, vals = _warp_scenes_core(stack, sx, sy, params, method, n_ns,
                                   win=win, win0=win0)
    data = canv[out_sel]
    ok = vals[out_sel]
    if auto:
        if colour_scale == 1:
            logged = jnp.log10(data)
            bad = ~jnp.isfinite(logged)
            data = jnp.where(bad, 0.0, logged)
            ok = ok & ~bad
        big = jnp.float32(3.4e38)

        def per_band(d, o):
            mn = jnp.min(jnp.where(o, d, big))
            mx = jnp.max(jnp.where(o, d, -big))
            return auto_byte_scale(d, o, mn, mx, jnp.any(o))

        return jax.vmap(per_band)(data, ok)
    return scale_to_byte(data, ok, scale_params[0], scale_params[1],
                         scale_params[2], colour_scale=colour_scale,
                         auto=False)


def _gather2d_c(src, ri, ci):
    """Flat gather from a channel-last (H, W, C) array: one index
    computation retrieves a contiguous C-vector per tap."""
    H, W, C = src.shape
    return src.reshape(-1, C)[ri * W + ci]


# The most one program's neighbourhood fetches may add to its device
# memory by `_unfold_bytes`, summed over every source it resamples:
# above it the program gathers its cubic taps one at a time.  The
# export's tile (a 1536² int16 window, depth 1) counts 71 MB and its
# compiled temp falls (81 -> 50 MB); a 4096² int16 window counts 302 MB
# but took 1.3 GB of temp, and ten whole 7680 x 7936 scenes (the
# executor declines a window that would be the whole stack) did not
# compile in 16 GB (PERF.md, section 6; `tools/tap_probe.py`).
_UNFOLD_BYTES = 128 << 20


def _unfold_bytes(shape, dtype, n_out: int) -> int:
    """What `_tap_pairs` adds for one (H, W, C) source of ``dtype``
    resampled at ``n_out`` pixels: the unfolded copy (8 values a value
    of the source padded by 3) and the two rows of 8 C gathered a
    pixel, for each plane the kernel form gathers (tap-side: the
    native values; mask-gather: f32 values and a validity byte)."""
    H, W, C = shape
    size = np.dtype(dtype).itemsize if _use_tapside() else 5
    return ((H + 5) * (W + 3) + 2 * n_out) * 8 * C * size


def _unfolds(method: str, sources, n_out: int) -> bool:
    """Whether a program that resamples each of ``sources`` ((H, W, C)
    shape, dtype) at ``n_out`` pixels fetches its cubic taps as
    neighbourhoods (`_tap_pairs`): only a cubic tap set, and only while
    the copies fit `_UNFOLD_BYTES` together.  Decided from static shapes
    at trace time, so each program has one form."""
    return method == "cubic" and sum(
        _unfold_bytes(s, d, n_out) for s, d in sources) <= _UNFOLD_BYTES


def _scored_sources(stack, win):
    """The (shape, dtype) of each source `_warp_scenes_scored` resamples
    from ``stack`` under gather window ``win``."""
    if isinstance(stack, (tuple, list)):
        return [(tuple(win or s.shape) + (1,), s.dtype) for s in stack]
    return [(tuple(win or stack.shape[1:]) + (1,), stack.dtype)] \
        * stack.shape[0]


def tap_form(method: str, stack, win, out_hw: Tuple[int, int]) -> str:
    """How `warp_scenes_ctrl_scored` over ``stack`` (B, H, W), or a
    tuple of B (H, W) scenes, with gather window ``win`` fetches a
    pixel's taps for an ``out_hw`` tile: ``neighbourhood`` (a cubic set
    in two row gathers, `_tap_pairs`) or ``per_tap`` (a gather a tap:
    nearest, bilinear, and a cubic set whose copies would not fit
    `_UNFOLD_BYTES`; `_unfolds`)."""
    return "neighbourhood" if _unfolds(
        method, _scored_sources(stack, win), out_hw[0] * out_hw[1]) \
        else "per_tap"


def _tap_pairs(src, r0, c0):
    """Every pixel's 4 x 4 cubic neighbourhood in two gathers: src
    (H, W, C), r0/c0 (h, w) int32 the floor of each pixel's coordinates
    -> 16 arrays (h, w, C), tap (dr, dc) at 4 dr + dc holding
    src[r0 + dr - 1, c0 + dc - 1] wherever that lies inside src (a tap
    outside reads the zero padding or some other pixel: the caller's
    bounds test masks it, as it masks the clipped per-tap index).

    XLA's TPU gather resolves a slice that spans two dimensions, or part
    of the minor one, by a loop of one dynamic slice an index: for the
    1,048,576 pixels of a 1024² tile a (4, 4) slice took 1.8 s and four
    (1, 4) slices 8.3 s, where the 16 scalar gathers took 122 ms.  Whole
    rows of a 2D operand it gathers at about a scalar's cost.  So the
    source, padded by 3 on each side, is unfolded first: row k of
    ``pairs`` holds the 2 x 4 block whose corner is pixel k of the
    padded source, and one gather of rows fetches tap rows 0-1, a second
    tap rows 2-3 two rows on (8.0 ms; PERF.md, section 6).  The copy holds 8
    values a source value: 76 MB for a 1536² f32 gather window."""
    H, W, C = src.shape
    sp = jnp.pad(src, ((3, 3), (3, 3), (0, 0)))
    # the corners of the blocks that reach the source; tap rows 2-3
    # start two corner rows on, so the unfolded rows run to H + 4
    rs = jnp.clip(r0, -2, H) + 2
    cs = jnp.clip(c0, -2, W) + 2
    hp, wp = H + 5, W + 3
    pairs = jnp.stack([sp[dr:dr + hp, dc:dc + wp]
                       for dr in (0, 1) for dc in range(4)], axis=2)
    pairs = pairs.reshape(hp * wp, 8 * C)
    k = rs * wp + cs
    halves = [pairs[k].reshape(r0.shape + (8, C)),
              pairs[k + 2 * wp].reshape(r0.shape + (8, C))]
    return [h[..., j, :] for h in halves for j in range(8)]


def _use_tapside() -> bool:
    """Kernel form selector, evaluated at TRACE time (the backend is
    fixed for the life of the process): tap-side validation avoids the
    per-dispatch full-scene f32/validity prologue — the right shape for
    TPU, where the prologue is pure HBM traffic; XLA CPU prefers the
    mask-gather form (the prologue parallelises across cores while
    gathers run as serial scalar loops — measured cfg3 145 -> 100
    tiles/s when the tap-side form runs on CPU)."""
    from .pallas_tpu import tpu_like_backend
    return tpu_like_backend()


def _resample_c(src, nodata, rows, cols, method: str,
                unfold: bool = False):
    """Channel-vectorised resample from a NATIVE-dtype channel-last
    source: src (H, W, C), rows/cols (h, w) -> (out (h, w, C) f32, ok
    (h, w, C) bool).  The index math runs ONCE for all C channels.
    Validity semantics are identical in both kernel forms (it is a pure
    function of the stored value); `_use_tapside` picks the form that
    fits the backend.  ``unfold`` (static; the program decides it,
    `_unfolds`) fetches a cubic tap set as neighbourhoods (`_tap_pairs`):
    the same 16 values, weights, validity test and accumulation order as
    a gather a tap, bit for bit."""
    if method not in ("near", "nearest", "bilinear", "cubic"):
        # the tap table below would silently render an unknown name as
        # cubic; keep the old _METHODS[method] KeyError contract
        raise KeyError(f"unknown resample method {method!r}")
    H, W, C = src.shape

    # planes: what a tap gathers; tap(fetch, inb): fetch(i) gives plane
    # i at the tap -> (value zeroed where invalid, ok)
    if _use_tapside():
        planes = (src,)

        def tap(fetch, inb):
            v = fetch(0).astype(jnp.float32)
            ok = inb[..., None] & jnp.isfinite(v) & (v != nodata)
            return jnp.where(ok, v, 0.0), ok
    else:
        # mask-gather form: one parallel full-source prologue, taps
        # gather the zeroed values + a precomputed validity plane
        sf = src.astype(jnp.float32)
        validp = jnp.isfinite(sf) & (sf != nodata)
        planes = (jnp.where(validp, sf, 0.0), validp)

        def tap(fetch, inb):
            v = fetch(0)
            ok = inb[..., None] & fetch(1)
            # zero values where ok is False: raw outputs at invalid
            # pixels stay identical between the two kernel forms
            return jnp.where(ok, v, 0.0), ok

    def gathered(ri, ci):
        return lambda i: _gather2d_c(planes[i], ri, ci)

    if method in ("near", "nearest"):
        ri = jnp.floor(rows + (0.5 + 1e-10)).astype(jnp.int32)
        ci = jnp.floor(cols + (0.5 + 1e-10)).astype(jnp.int32)
        inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W) \
            & jnp.isfinite(rows) & jnp.isfinite(cols)
        return tap(gathered(jnp.clip(ri, 0, H - 1),
                            jnp.clip(ci, 0, W - 1)), inb)
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
    else:                       # cubic (Catmull-Rom)
        wr = _cubic_weights(fr)
        wc = _cubic_weights(fc)
        taps = [(dr - 1, dc - 1, wr[dr] * wc[dc])
                for dr in range(4) for dc in range(4)]
        thresh = 0.05
    nbs = [_tap_pairs(p, r0, c0) for p in planes] \
        if unfold and method == "cubic" else None
    acc = jnp.zeros(rows.shape + (C,), jnp.float32)
    wacc = jnp.zeros(rows.shape + (C,), jnp.float32)
    for k, (dr, dc, w) in enumerate(taps):
        ri = r0 + dr
        ci = c0 + dc
        inb = (ri >= 0) & (ri < H) & (ci >= 0) & (ci < W)
        fetch = gathered(jnp.clip(ri, 0, H - 1), jnp.clip(ci, 0, W - 1)) \
            if nbs is None else (lambda i, k=k: nbs[i][k])
        v, okt = tap(fetch, inb)
        okf = okt.astype(jnp.float32)
        acc = acc + w[..., None] * okf * v
        wacc = wacc + w[..., None] * okf
    ok = finite[..., None] & (wacc > thresh)
    out = acc / jnp.where(wacc > thresh, wacc, 1.0)
    return out, ok


def _grid_taps(bands, params, win0, at, sx, sy, method: str, win,
               unfold: bool):
    """Warp indices, tap weights and the vector taps of ONE pixel grid
    of a band set: ``bands`` the (sh, sw) scenes of the channels on that
    grid, ``params[at]`` its param row, ``win0[at]`` its window origin
    -> `_resample_c`'s (out (h, w, c), ok (h, w, c))."""
    p = params[at]
    cols = (p[0] + p[1] * sx + p[2] * sy) - 0.5
    rows = (p[3] + p[4] * sx + p[5] * sy) - 0.5
    oob = (rows < -0.5) | (rows > p[6] - 0.5) \
        | (cols < -0.5) | (cols > p[7] - 0.5)
    rows = jnp.where(oob, jnp.nan, rows)
    if win is not None:
        cut = [_window_slice(b, win, win0[at], axis=0) for b in bands]
        bands = [c[0] for c in cut]
        rows = rows - cut[0][1]
        cols = cols - cut[0][2]
    return _resample_c(jnp.stack(bands, axis=-1), p[8], rows, cols,
                       method, unfold)


def _mosaic_band_sets(granules, ctrl, params, prios, method: str,
                      out_hw: Tuple[int, int], step: int, win, win0,
                      grid_of: Optional[Tuple[int, ...]] = None,
                      crs_of=None):
    """The warp + mosaic the channel-packed kernels share: from the band
    scenes of G granule sets (each a C-tuple of (sh, sw) arrays) to
    (data (h, w, C) f32, best (h, w, C) f32): warp indices and tap
    weights once a pixel grid of a set, vector gathers from the packed
    gather windows, and per channel the valid tap of the highest
    priority (``best`` is -inf where no set holds the channel).

    ``grid_of`` (static) maps channel -> pixel grid where a set's bands
    lie on R > 1 grids of one footprint (Sentinel-2's 10 m and 20 m
    bands; grid 0 the finest): params is then (G, R, 11), a row a (set,
    grid), ``win`` a tuple of R windows and win0 (G, R, 2).  Each grid's
    channels are gathered as one vector a tap from that grid's window,
    keep their own validity, and are put back in channel order at the
    end.  None (one grid) keeps params (G, 11), one window and win0
    (G, 2): a one-grid set traces the program it traced before grids
    existed.

    ``crs_of`` (traced (G,) int32) maps set -> control grid where the
    sets lie in K > 1 CRSs (granules either side of a UTM zone line):
    ``ctrl`` is then (K, 2, gh, gw), a control grid a CRS, and each
    set's param rows are relative to its own grid's origin.  Each grid
    is upsampled once; a set's warp reads its grid's coordinates,
    picked by a dynamic index (a slice, no gather).  K is static, the
    map is not: one program a (G, K), whichever set lies where.
    Newest-wins stays one comparison over all sets by their global
    priorities.  None (one CRS) keeps ctrl (2, gh, gw): a one-CRS tile
    traces the program it traced before CRSs were mixed."""
    h, w = out_hw
    C = len(granules[0])
    grid_of = grid_of or (0,) * C
    grids = [[c for c in range(C) if grid_of[c] == r]
             for r in range(max(grid_of) + 1)]
    one = len(grids) == 1
    if crs_of is None:
        sx = _bilerp_grid(ctrl[0], h, w, step)
        sy = _bilerp_grid(ctrl[1], h, w, step)

        def coords(k):
            return sx, sy
    else:
        gx = jnp.stack([_bilerp_grid(c[0], h, w, step) for c in ctrl])
        gy = jnp.stack([_bilerp_grid(c[1], h, w, step) for c in ctrl])

        def coords(k):
            return (lax.dynamic_index_in_dim(gx, crs_of[k], 0, False),
                    lax.dynamic_index_in_dim(gy, crs_of[k], 0, False))
    wins = [win if one or win is None else win[r]
            for r in range(len(grids))]
    unfold = _unfolds(method, [
        (tuple(wins[r] or bands[cs[0]].shape) + (len(cs),),
         jnp.result_type(*[bands[c] for c in cs]))
        for bands in granules for r, cs in enumerate(grids)], h * w)
    data = [jnp.zeros((h, w, len(cs)), jnp.float32) for cs in grids]
    best = [jnp.full((h, w, len(cs)), -jnp.inf, jnp.float32)
            for cs in grids]
    for k, bands in enumerate(granules):
        for r, cs in enumerate(grids):
            d, o = _grid_taps([bands[c] for c in cs], params, win0,
                              k if one else (k, r), *coords(k), method,
                              wins[r], unfold)
            score = jnp.where(o, prios[k] if one
                              else prios[k, np.asarray(cs)], -jnp.inf)
            take = score > best[r]
            data[r] = jnp.where(take, d, data[r])
            best[r] = jnp.where(take, score, best[r])
    if one:
        return data[0], best[0]
    place = {c: (r, j) for r, cs in enumerate(grids)
             for j, c in enumerate(cs)}

    def in_order(planes):
        return jnp.stack([planes[place[c][0]][..., place[c][1]]
                          for c in range(C)], axis=-1)
    return in_order(data), in_order(best)


@functools.partial(jax.jit,
                   static_argnames=("method", "out_hw", "step", "auto",
                                    "colour_scale", "win", "grid_of"))
def render_rgba_ctrl(granules, ctrl, params, prios, scale_params,
                     method: str = "near",
                     out_hw: Tuple[int, int] = (256, 256),
                     step: int = 16, auto: bool = True,
                     colour_scale: int = 0,
                     win: Optional[Tuple[int, int]] = None, win0=None,
                     grid_of: Optional[Tuple[int, ...]] = None,
                     crs_of=None):
    """RGB fast path: one dispatch from the band scenes of G granules,
    each a 3-tuple of (sh, sw) arrays as the scene cache holds them (on
    one grid, or on the grids ``grid_of`` names: `_mosaic_band_sets`),
    to the PNG-ready (h, w, 4) RGBA tile.  Only the gather
    window of each band is packed channel-last (without a window the
    packed scene is a temporary of this program), so no second copy of
    a raster stays on the device.  Compared with
    `render_scenes_bands_ctrl` this computes warp indices and tap
    weights ONCE a granule for all three bands and gathers 3-vectors:
    a TPU gather costs by the element gathered, so a bilinear tile
    over two granules takes 8 tap gathers of 3-vectors where the
    per-band kernel takes 24 of scalars (22.7 ms against 5.6 ms for one
    granule, PERF.md PR 27), and none beside the taps: the control grid
    reaches a pixel without one (`_cell_corners`).  The host
    pulls one contiguous buffer that feeds the PNG encoder without an
    interleave pass.  Alpha is 0 exactly where all three scaled bytes
    are 255 — the transparency rule of the RGB PNG encoder
    (`utils/ogc_encoders.go:82-142` parity).

    params: (G, 11), the granule params of `warp_scenes_batch` (priority
    and namespace id unused here).  prios: (G, 3) f32, the mosaic
    priority of each granule's band in each channel (the per-band
    kernel's newest-wins, channel by channel); a row of -inf is a
    padding granule.  win0: (G, 2), an origin a granule.  With
    ``grid_of`` params is (G, R, 11) and win0 (G, R, 2), a row and an
    origin a (granule, grid), and ``win`` R windows.  With ``crs_of``
    (G,) int32 the sets lie in K CRSs and ctrl is (K, 2, gh, gw), a
    control grid a CRS (`_mosaic_band_sets`).
    scale_params (3,) as elsewhere.
    """
    from .scale import auto_byte_scale, scale_to_byte
    data, best = _mosaic_band_sets(granules, ctrl, params, prios, method,
                                   out_hw, step, win, win0, grid_of,
                                   crs_of)
    ok = best > -jnp.inf
    if auto:
        if colour_scale == 1:
            logged = jnp.log10(data)
            bad = ~jnp.isfinite(logged)
            data = jnp.where(bad, 0.0, logged)
            ok = ok & ~bad
        big = jnp.float32(3.4e38)
        mn = jnp.min(jnp.where(ok, data, big), axis=(0, 1))
        mx = jnp.max(jnp.where(ok, data, -big), axis=(0, 1))
        rgb = jax.vmap(auto_byte_scale, in_axes=(2, 2, 0, 0, 0),
                       out_axes=2)(data, ok, mn, mx,
                                   jnp.any(ok, axis=(0, 1)))
    else:
        rgb = scale_to_byte(
            jnp.moveaxis(data, -1, 0), jnp.moveaxis(ok, -1, 0),
            scale_params[0], scale_params[1], scale_params[2],
            colour_scale=colour_scale, auto=False)
        rgb = jnp.moveaxis(rgb, 0, -1)
    alpha = jnp.where(jnp.all(rgb == jnp.uint8(255), axis=-1),
                      jnp.uint8(0), jnp.uint8(255))
    return jnp.concatenate([rgb, alpha[..., None]], axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("fp", "method", "out_hw", "step",
                                    "auto", "colour_scale", "win",
                                    "grid_of"))
def render_expr_ctrl(granules, ctrl, params, prios, scale_params, consts,
                     fp: tuple, method: str = "near",
                     out_hw: Tuple[int, int] = (256, 256),
                     step: int = 16, auto: bool = True,
                     colour_scale: int = 0,
                     win: Optional[Tuple[int, int]] = None, win0=None,
                     grid_of: Optional[Tuple[int, ...]] = None,
                     crs_of=None):
    """Band-algebra fast path in `render_rgba_ctrl`'s form: one dispatch
    from the band scenes of G granule sets, each a C-tuple of (sh, sw)
    arrays as the scene cache holds them (slot i of the tuple is
    variable i of the expression), to the PNG-ready (h, w) uint8 tile,
    255 = no data.  The per-channel newest-wins mosaic is
    `_mosaic_band_sets`; the expression is evaluated AFTER it, as the
    merger does (`processor/tile_merger.go:523-731`), by the traced
    epilogue every fused leg shares (`ops.paged.expr_epilogue`: valid
    where every referenced slot is and the result is finite,
    `CompiledExpr.eval_masked`'s rule), then `scale_to_byte`.

    The kernel takes unstacked scenes because a stack is a copy: at
    Sentinel-2 granule size a band is 484.7 MB on the device, so the
    (n, bh, bw) stack `warp_scenes_ctrl_scored` asks for is 0.97 GB for
    NDVI over one granule and 3.88 GB over four, beside 5.8 GB of
    resident scenes.  Only the gather windows are packed here.

    ``fp`` (static) is the normalized fingerprint key
    (`ops.expr.fingerprint`): one program a structure, a window bucket
    and a granule count, never one an expression string; ``consts``
    (K,) f32 are its lifted literals.  params, prios (G, C), win0 (G, 2)
    and scale_params as `render_rgba_ctrl`, and as there a set's bands
    may lie on the grids ``grid_of`` names and the sets in the CRSs
    ``crs_of`` names."""
    from .paged import expr_epilogue
    from .scale import scale_to_byte
    data, best = _mosaic_band_sets(granules, ctrl, params, prios, method,
                                   out_hw, step, win, win0, grid_of,
                                   crs_of)
    plane, ok = expr_epilogue(jnp.moveaxis(data, -1, 0)[None],
                              jnp.moveaxis(best, -1, 0)[None], fp,
                              consts[None])
    return scale_to_byte(plane[0], ok[0], scale_params[0], scale_params[1],
                         scale_params[2], colour_scale, auto)


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "win"))
def warp_scenes_ctrl_scored(stack, ctrl, params, method: str = "near",
                            n_ns: int = 1,
                            out_hw: Tuple[int, int] = (256, 256),
                            step: int = 16,
                            win: Optional[Tuple[int, int]] = None,
                            win0=None):
    """`warp_scenes_ctrl` that also returns the per-pixel winning
    priority — one per-source-CRS group dispatch of a multi-CRS mosaic
    (granule sets spanning UTM zones)."""
    h, w = out_hw
    sx = _bilerp_grid(ctrl[0], h, w, step)
    sy = _bilerp_grid(ctrl[1], h, w, step)
    return _warp_scenes_scored(stack, sx, sy, params, method, n_ns,
                               win=win, win0=win0)


@jax.jit
def combine_scored(canvs, bests):
    """Combine G partial mosaics by per-pixel priority: canvs
    (G, n_ns, h, w) f32, bests (G, n_ns, h, w) f32 (-inf = no data) ->
    (canvases (n_ns, h, w), valids bool)."""
    idx = jnp.argmax(bests, axis=0)
    canv = jnp.take_along_axis(canvs, idx[None], axis=0)[0]
    ok = jnp.max(bests, axis=0) > -jnp.inf
    return jnp.where(ok, canv, 0.0), ok


@functools.partial(jax.jit, static_argnames=("method", "n_ns"))
def warp_scenes_batch(stack, sxy, params, method: str = "near",
                      n_ns: int = 1):
    """Fused warp + mosaic from DEVICE-CACHED full scenes.

    This variant warps from scenes already resident in HBM
    (`pipeline.scene_cache`), so a tile costs one ~0.5 MB coordinate
    upload instead of re-shipping ~MBs of source windows host->device
    per request.  The per-granule affine (src-CRS metres ->
    scene pixel) runs on device in f32 on ORIGIN-RELATIVE coordinates to
    keep sub-pixel precision (absolute projected magnitudes ~2e7 would
    swamp f32).

    stack  (B, sh, sw) native dtype (int16/uint8/f32/...);
    sxy    (2, h, w) f32 shared origin-relative dst-pixel coords in the
           scenes' common CRS (NaN = unprojectable);
    params (B, 11) f32 per granule, host-packed in f64 then cast:
           [0:6]  origin-folded inverse geotransform:
                  col = p0 + p1*sx + p2*sy, row = p3 + p4*sx + p5*sy
           [6:8]  true (rows, cols) of the scene (stack is bucket-padded;
                  coords past the true extent are rejected)
           [8]    nodata (NaN = none)
           [9]    mosaic priority (strictly unique, higher wins)
           [10]   namespace id (< 0 = padding granule).
    Returns (canvases (n_ns, h, w) f32, valids (n_ns, h, w) bool).
    """
    return _warp_scenes_core(stack, sxy[0], sxy[1], params, method, n_ns)


def _resample_native(src, nodata, rows, cols, method: str,
                     unfold: bool = False):
    """Resample directly from a NATIVE-dtype (H, W) source, deriving
    validity from each gathered tap's VALUE (finite and != nodata)
    instead of pre-materialising full-scene f32 + validity arrays.  For
    a 256-px tile over a 2048-px scene stack the old elementwise
    prologue moved ~80 MB of HBM per dispatch; tap-side validation
    moves O(taps x tile).  Semantics identical: validity is a pure
    function of the stored value.  Implemented as the C=1 case of
    `_resample_c` (XLA folds the size-1 channel axis away), so the tap
    machinery exists once."""
    out, ok = _resample_c(src[..., None], nodata, rows, cols, method,
                          unfold)
    return out[..., 0], ok[..., 0]


def _warp_scenes_scored(stack, sx, sy, params, method: str, n_ns: int,
                        win=None, win0=None):
    """Core warp + per-namespace mosaic returning (canvases, best) where
    ``best`` is the winning granule's mosaic priority per pixel (-inf
    where no granule contributed) — the carrier that lets partial
    mosaics from several dispatches (e.g. per-source-CRS groups) combine
    with newest-wins semantics preserved.

    win (static (WR, WC)) + win0 (traced (2,) int32): gather from one
    shared dynamic slice of the stack instead of the full scenes.  The
    caller guarantees every granule's finite gather footprint (incl.
    the 2-px cubic tap margin) lies inside the window; the origin
    subtraction is an exact f32 op (an integer off a coordinate of the
    same magnitude, both < 2^14), so the windowed kernel reads exactly
    the taps the unwindowed one does (nearest: bit-identical;
    interpolated: 1-ulp XLA-contraction differences between the two
    programs).  ``stack`` may also be a tuple of (H, W) scenes, with
    win0 (B, 2): see below.  A cubic tap set comes as neighbourhoods
    while their copies fit (`_unfolds`, `tap_form`).
    """
    unfold = _unfolds(method, _scored_sources(stack, win), sx.size)

    def per(scene, p, r0=None, c0=None):
        cols = (p[0] + p[1] * sx + p[2] * sy) - 0.5
        rows = (p[3] + p[4] * sx + p[5] * sy) - 0.5
        oob = (rows < -0.5) | (rows > p[6] - 0.5) \
            | (cols < -0.5) | (cols > p[7] - 0.5)
        rows = jnp.where(oob, jnp.nan, rows)
        if r0 is not None:
            rows = rows - r0
            cols = cols - c0
        return _resample_native(scene, p[8], rows, cols, method, unfold)

    if isinstance(stack, (tuple, list)):
        # the scenes as the scene cache holds them, one (H, W) array
        # each, and win0 (B, 2), an origin a scene.  Each is sliced and
        # resampled by itself, so no copy of a raster is made, and the
        # taps are gathers from one flat array: XLA's TPU gather over a
        # batch of windows took 8 ms a tap for 8 scenes where these take
        # ~0.1 ms a scene (PERF.md, PR 27)
        def one(k, scene):
            if win is None:
                return per(scene, params[k])
            cut, r0, c0 = _window_slice(scene, win, win0[k], axis=0)
            return per(cut, params[k], r0, c0)

        made = [one(k, scene) for k, scene in enumerate(stack)]
        out = jnp.stack([m[0] for m in made])
        ok = jnp.stack([m[1] for m in made])
    elif win is not None:
        stack, r0f, c0f = _window_slice(stack, win, win0, axis=1)
        out, ok = jax.vmap(lambda scene, p: per(scene, p, r0f, c0f))(
            stack, params)
    else:
        out, ok = jax.vmap(per)(stack, params)
    prio = params[:, 9]
    ns_id = params[:, 10].astype(jnp.int32)
    score = jnp.where(ok, prio[:, None, None], -jnp.inf)
    canv = []
    best = []
    for n in range(n_ns):
        member = (ns_id == n)[:, None, None]
        s = jnp.where(member, score, -jnp.inf)
        idx = jnp.argmax(s, axis=0)
        b = jnp.max(s, axis=0)
        c = jnp.take_along_axis(out, idx[None], axis=0)[0]
        # deterministic fill at invalid pixels (encoders key off the mask,
        # but downstream comparisons and file writers see the raw values)
        canv.append(jnp.where(b > -jnp.inf, c, 0.0))
        best.append(b)
    return jnp.stack(canv), jnp.stack(best)


def _warp_scenes_core(stack, sx, sy, params, method: str, n_ns: int,
                      win=None, win0=None):
    canv, best = _warp_scenes_scored(stack, sx, sy, params, method, n_ns,
                                     win=win, win0=win0)
    return canv, best > -jnp.inf


@functools.partial(jax.jit, static_argnames=("method",))
def warp_gather_shared(src, valid, rows, cols, method: str = "near"):
    """Batch of output tiles gathered from ONE shared source: src (H, W),
    rows/cols (B, h, w).  vmap over coords only — avoids materialising a
    per-tile broadcast of the source (the fast path for many concurrent
    GetMap tiles over the same mosaic/granule)."""
    return jax.vmap(lambda r, c: _METHODS[method](src, valid, r, c))(
        rows, cols)


# ---------------------------------------------------------------------------
# Host convenience wrapper
# ---------------------------------------------------------------------------

def warp(src_data: np.ndarray, src_gt: GeoTransform, src_crs: CRS,
         nodata: Optional[float],
         dst_gt: GeoTransform, dst_crs: CRS, height: int, width: int,
         method: str = "near") -> Tuple[np.ndarray, np.ndarray]:
    """One-shot warp of a full in-memory source raster.  Computes the grid
    in f64 on host, gathers on device, returns (data f32, valid bool)."""
    from .raster import nodata_mask
    rows, cols = coord_grid(dst_gt, dst_crs, height, width, src_gt, src_crs)
    src = jnp.asarray(src_data.astype(np.float32))
    valid = jnp.asarray(nodata_mask(src_data, nodata))
    out, ok = warp_gather(src, valid,
                          jnp.asarray(rows.astype(np.float32)),
                          jnp.asarray(cols.astype(np.float32)), method)
    return np.asarray(out), np.asarray(ok)
