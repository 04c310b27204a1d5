"""The TPU warp executor: shape-bucketed batched gather dispatch.

Replaces the reference's per-granule worker RPC fan-out
(`processor/tile_grpc.go:219-242` + the C warp loop) with one XLA dispatch
per (source-shape bucket, method): source windows are padded up to a small
set of shapes so recompilation is bounded (SURVEY §7 "padded shape
buckets"), coordinates are computed once per (dst grid, src CRS) in f64 on
host and only the cheap affine part is per-granule.
"""

from __future__ import annotations

import contextvars
import math
import os
import threading
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..geo.crs import CRS
from ..geo.transform import GeoTransform
from ..ops.paged import PARAMS_W as PAGED_PARAMS_W
from ..ops.paged import paged_enabled
from ..ops.pallas_tpu import render_byte_raced, warp_scored_raced
from ..ops.warp import (combine_scored, render_scenes_bands_ctrl, tap_form,
                        warp_gather_batch)
from ..mesh.dispatch import compat_spmd
from ..obs import set_attr as obs_set_attr
from .decode import DecodedWindow

# padded source-window shape buckets (H and W independently bucketed)
_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)

# how the scored warps a context dispatched last fetched their taps
# (`WarpExecutor._note_taps`); the export reads it back for each tile
TAP_FORM: contextvars.ContextVar = contextvars.ContextVar(
    "gsky_tap_form", default=None)


def _prefetch(x):
    """Start the device->host copy of a TERMINAL result now, without
    blocking: the caller's eventual np.asarray overlaps with other
    requests' transfers instead of serialising per-buffer."""
    try:
        x.copy_to_host_async()
    except Exception:  # backend lacks copy_to_host_async (CPU) - sync pull still works
        pass
    return x


def _bucket_in(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return int(math.ceil(n / 4096) * 4096)


def _bucket(n: int) -> int:
    return _bucket_in(n, _BUCKETS)


def _bucket_pow2(n: int, lo: int = 1) -> int:
    """Next power of two >= n (batch-count and namespace-count padding so
    jit specialisations stay bounded)."""
    b = lo
    while b < n:
        b *= 2
    return b


def _window_mode() -> bool:
    """Gather-window gate (GSKY_WARP_WINDOW): '1' on, '0' off, default
    'auto' = on for TPU-like backends only.  XLA's TPU gather lowering
    costs proportional to the SOURCE extent, so slicing the tile's
    footprint window out of the scene stack before the gather is the
    difference between ~13 ms and ~1 ms per 256-px tile over 2048-px
    scenes; on CPU the gather is a per-tap scalar loop and the slice is
    pure overhead."""
    v = os.environ.get("GSKY_WARP_WINDOW", "auto")
    if v == "0":
        return False
    if v == "1":
        return True
    from ..ops.pallas_tpu import tpu_like_backend
    return tpu_like_backend()


_WIN_MARGIN = 2  # covers cubic's +2 tap and f32-vs-f64 coord rounding

# gather-window sizes get a DENSER bucket list than the decode-path
# shape buckets: a 300-px footprint over a 512-px scene must land in a
# 384 window, not bucket up to the whole scene and decline.  Still a
# bounded set (jit variants per (win_h, win_w) pair), just finer.
_WIN_BUCKETS = (64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536,
                2048, 3072, 4096)


def _win_bucket(n: int) -> int:
    return _bucket_in(n, _WIN_BUCKETS)


def _granule_bounds(p: np.ndarray, cx: np.ndarray, cy: np.ndarray):
    """Raw gather-footprint bounds (r_lo, r_hi, c_lo, c_hi) of ONE
    granule's param row, or None when the granule has no finite coords
    (nothing to gather).  Exactness: the dense device coords are the
    bilinear interpolation of the ctrl-point coords with the affine
    applied — affine commutes with interpolation, so the dense extremes
    are bounded by the affine evaluated at the ctrl points, computed
    here in f64.  The same margin rules serve `_gather_window` (bucketed
    windows) and `_paged_from_group` (page-grid coverage), so the two
    paths gather the same taps."""
    # clamp to the kernel's oob thresholds (coords past the true
    # extent are NaN-poisoned on device and never gathered): a tile
    # straddling a scene edge must not inflate the footprint to its
    # off-scene extent and lose the window
    cols = np.clip(p[0] + p[1] * cx + p[2] * cy - 0.5, -1.0, p[7])
    rows = np.clip(p[3] + p[4] * cx + p[5] * cy - 0.5, -1.0, p[6])
    ok = np.isfinite(rows) & np.isfinite(cols)
    if not ok.any():
        return None
    rmin = float(rows[ok].min())
    rmax = float(rows[ok].max())
    cmin = float(cols[ok].min())
    cmax = float(cols[ok].max())
    r_lo = math.floor(rmin) - _WIN_MARGIN
    c_lo = math.floor(cmin) - _WIN_MARGIN
    # high edge gets one extra pixel: the device recomputes coords in
    # f32, which can land just past the f64 bound and bump floor() by
    # one, pushing cubic's +2 tap one past _WIN_MARGIN
    r_hi = math.floor(rmax) + _WIN_MARGIN + 2
    c_hi = math.floor(cmax) + _WIN_MARGIN + 2
    return r_lo, r_hi, c_lo, c_hi


def _gather_window(params64: np.ndarray, cx: np.ndarray, cy: np.ndarray,
                   bucket_h: int, bucket_w: int):
    """(win, win0) covering every granule's finite gather footprint, or
    None when windowing can't help (footprint ~ scene, or no finite
    coords).

    params64: (B, 11) f64 granule params (ns_id < 0 rows are padding);
    cx/cy: host ctrl coords (gh, gw), possibly NaN."""
    r_lo = c_lo = None
    r_hi = c_hi = None
    for p in params64:
        if p[10] < 0:
            continue
        made = _granule_bounds(p, cx, cy)
        if made is None:
            continue
        if r_lo is None:
            r_lo, r_hi, c_lo, c_hi = made
        else:
            r_lo = min(r_lo, made[0])
            r_hi = max(r_hi, made[1])
            c_lo = min(c_lo, made[2])
            c_hi = max(c_hi, made[3])
    if r_lo is None:
        return None
    return finish_window(r_lo, r_hi, c_lo, c_hi, bucket_h, bucket_w)


def _gather_windows(params64: np.ndarray, cx: np.ndarray, cy: np.ndarray,
                    bucket_h: int, bucket_w: int):
    """`_gather_window` for a kernel that slices each scene by itself:
    (win, win0 (B, 2)), one window size, as large as the largest
    granule footprint, and one origin per granule, where the tile
    touches that granule.  Neighbouring granules of one grid lie a
    granule's pitch apart in each other's pixel coordinates, so one
    origin for all would span a window over everything between them
    (11,008 x 512 px where 512 x 512 do).  ``cx``, ``cy`` one grid for
    every granule, or one a granule (`_grid_at`)."""
    bounds = [None if p[10] < 0 else _granule_bounds(
        p, _grid_at(cx, k), _grid_at(cy, k))
        for k, p in enumerate(params64)]
    real = [b for b in bounds if b is not None]
    if not real:
        return None
    rows = max(b[1] - b[0] for b in real)
    cols = max(b[3] - b[2] for b in real)

    def finish(r_lo, c_lo):
        return finish_window(r_lo, r_lo + rows, c_lo, c_lo + cols,
                             bucket_h, bucket_w)

    made = finish(0, 0)         # the size alone decides whether it helps
    if made is None:
        return None
    win0 = np.stack([np.zeros(2, np.int32) if b is None
                     else finish(b[0], b[2])[1] for b in bounds])
    return made[0], win0


def finish_window(r_lo: int, r_hi: int, c_lo: int, c_hi: int,
                  bucket_h: int, bucket_w: int):
    """Bucket raw footprint bounds into (win, win0), or None when the
    window would be the whole stack — the ONE place the bucket /
    decline / origin-clamp rules live (`_gather_window` and
    `_gather_windows` both finish through here)."""
    wr = min(_win_bucket(r_hi - r_lo), bucket_h)
    wc = min(_win_bucket(c_hi - c_lo), bucket_w)
    if wr >= bucket_h and wc >= bucket_w:
        return None
    r0 = min(max(r_lo, 0), bucket_h - wr)
    c0 = min(max(c_lo, 0), bucket_w - wc)
    return (wr, wc), np.array([r0, c0], np.int32)


def _dev_win0(win0):
    return None if win0 is None else jnp.asarray(win0)


def _inv_gt_params(gt: GeoTransform, ox: float, oy: float):
    """Origin-folded inverse geotransform (src-CRS coords relative to
    (ox, oy) -> granule pixel): the 6-tuple every scene kernel takes in
    params[:6] — col = p0 + p1*sx + p2*sy, row = p3 + p4*sx + p5*sy."""
    det = gt.dx * gt.dy - gt.rx * gt.ry
    inv = (gt.dy / det, -gt.rx / det, -gt.ry / det, gt.dx / det)
    a0 = inv[0] * (ox - gt.x0) + inv[1] * (oy - gt.y0)
    a3 = inv[2] * (ox - gt.x0) + inv[3] * (oy - gt.y0)
    return (a0, inv[0], inv[1], a3, inv[2], inv[3])


def _complete_sets(grids, ns_ids, n_chan: int):
    """Band sets of a granule list: ``grids[i]`` names the footprint
    granule i covers (`_footprint`), ``ns_ids[i]`` its channel.  Returns
    one list per footprint, in first-seen order, of the granule index of
    each channel 0..n_chan-1, or None unless every footprint holds
    exactly one granule per channel (two dates on one footprint, or a
    footprint that lacks a band, are the per-band kernels')."""
    sets: Dict[tuple, Dict[int, int]] = {}
    for i, grid in enumerate(grids):
        members = sets.setdefault(grid, {})
        if ns_ids[i] in members:
            return None
        members[ns_ids[i]] = i
    want = list(range(n_chan))
    if not sets or any(sorted(m) != want for m in sets.values()):
        return None
    return [[m[c] for c in want] for m in sets.values()]


# pixel grids a band set may span: Sentinel-2 has three (10, 20, 60 m),
# and each is one more operand row and window in the program's key
_MAX_GRIDS = 3

# CRSs a tile's band sets may lie in, a control grid each: two where a
# tile crosses a UTM zone line, three where it also meets a zone's
# other neighbour near a corner of the grid; more decline
_MAX_CRS = 3


def _footprint(s) -> tuple:
    """The ground a cached scene covers: its CRS, outer corner and the
    two edge vectors, W (dx, ry) and H (rx, dy), to a micrometre.  A
    Sentinel-2 granule's 10 m and 20 m rasters share it (10,980 x 10 m
    = 5,490 x 20 m = 109,800 m); adjacent granules never do."""
    gt = s.gt
    return (s.crs.name(), round(gt.x0, 6), round(gt.y0, 6),
            round(s.width * gt.dx, 6), round(s.width * gt.ry, 6),
            round(s.height * gt.rx, 6), round(s.height * gt.dy, 6))


def _grid_key(s) -> tuple:
    """What the scenes of one pixel grid of a band set share, so that
    one param row and one gather window serve them: pixel size and
    shear, true and bucket shape, dtype and nodata."""
    gt = s.gt
    return (gt.dx, gt.dy, gt.rx, gt.ry, s.height, s.width,
            tuple(s.bucket), str(s.dtype),
            None if np.isnan(s.nodata) else float(s.nodata))


def _grid_sets(scenes, chans, n_chan: int, order=None, footprints=None):
    """(sets, grid_of) for the fused band-set kernels, or None.  Sets
    group the scenes by footprint (`_footprint`, or ``footprints[i]``
    where the caller knows better; `_complete_sets`: one scene a
    channel), each a list of scene indices in channel order (``order``
    picks and orders the channels: an RGB request's `out_sel`);
    ``grid_of`` maps a channel to its pixel grid within a set, grids
    numbered finest first, and is None where all lie on one.  Every set
    must split into grids alike, a set spans at most `_MAX_GRIDS` grids,
    and the sets lie in at most `_MAX_CRS` CRSs, a control grid each
    (`_set_crs`)."""
    sets = _complete_sets(footprints or [_footprint(s) for s in scenes],
                          chans, n_chan)
    if sets is None:
        return None
    if order is not None:
        sets = [[m[c] for c in order] for m in sets]
    keys = [_grid_key(scenes[i]) for i in sets[0]]
    if any([_grid_key(scenes[i]) for i in m] != keys for m in sets[1:]) \
            or len({scenes[m[0]].crs for m in sets}) > _MAX_CRS:
        return None
    grids = sorted(dict.fromkeys(keys), key=lambda k: (
        abs(k[0] * k[1] - k[2] * k[3]), keys.index(k)))
    if len(grids) > _MAX_GRIDS:
        return None
    grid_of = tuple(grids.index(k) for k in keys)
    return sets, (grid_of if len(grids) > 1 else None)


# the extra rows and columns a coarser grid's window takes over the
# finest one's, scaled: its footprint is the finest's over the pixel
# ratio plus both margins and a pixel each for floor and clip
_GRID_WIN_PAD = 2 * _WIN_MARGIN + 4


def _set_crs(scenes, sets):
    """(origins, crs_of) of band sets (`_grid_sets`): ``origins`` one
    (CRS, x0, y0) a CRS the sets lie in, in first-seen order, the outer
    corner of its first set's first scene, on which that CRS's control
    grid and param rows are taken; ``crs_of`` the index into it of each
    set, None where all lie in one CRS."""
    origins, at = [], {}
    for m in sets:
        s = scenes[m[0]]
        if s.crs not in at:
            at[s.crs] = len(origins)
            origins.append((s.crs, s.gt.x0, s.gt.y0))
    crs_of = tuple(at[scenes[m[0]].crs] for m in sets)
    return origins, (crs_of if len(origins) > 1 else None)


def _rows_on(scenes, origins):
    """Every scene's param row (affine6, height, width, nodata) relative
    to the origin of its CRS (`_set_crs`)."""
    at = {crs: (ox, oy) for crs, ox, oy in origins}
    return [_inv_gt_params(s.gt, *at[s.crs]) + (s.height, s.width, s.nodata)
            for s in scenes]


def _grid_at(c: np.ndarray, k: int) -> np.ndarray:
    """Row k's host control coordinates: ``c`` is one grid (gh, gw) for
    every row, or one a row (B, gh, gw) where the rows lie in several
    CRSs."""
    return c[k] if c.ndim == 3 else c


def _grid_windows(params64: np.ndarray, cx: np.ndarray, cy: np.ndarray,
                  buckets):
    """`_gather_windows` for sets on R grids: (wins, win0 (G, R, 2)) or
    None.  ``params64`` (G, R, 11); ``buckets`` each grid's bucket.  The
    finest grid's window is `_gather_windows`'; a coarser grid's is
    derived from it alone (its size over the pixel ratio, padded, then
    bucketed), so one window of the finest grid keys one program and the
    program lattice does not multiply with R.  Each (set, grid) has its
    own origin; None where a coarser footprint would not fit.  ``cx``,
    ``cy`` one grid for every set, or one a set (`_grid_at`)."""
    made = _gather_windows(params64[:, 0], cx, cy, *buckets[0])
    if made is None:
        return None
    (wr, wc), origin = made
    G, R = params64.shape[:2]
    win0 = np.zeros((G, R, 2), np.int32)
    win0[:, 0] = origin
    real = params64[params64[:, 0, 10] >= 0]
    wins = [(wr, wc)]
    for r in range(1, R):
        bh, bw = buckets[r]
        # one footprint: the pixel ratio is the ratio of the sizes
        size = (min(_win_bucket(math.ceil(wr * real[0, r, 6]
                                          / real[0, 0, 6])
                                + _GRID_WIN_PAD), bh),
                min(_win_bucket(math.ceil(wc * real[0, r, 7]
                                          / real[0, 0, 7])
                                + _GRID_WIN_PAD), bw))
        for k in range(G):
            p = params64[k, r]
            b = None if p[10] < 0 else _granule_bounds(
                p, _grid_at(cx, k), _grid_at(cy, k))
            if b is None:
                continue
            if b[1] - b[0] > size[0] or b[3] - b[2] > size[1]:
                return None
            win0[k, r] = (min(max(b[0], 0), bh - size[0]),
                          min(max(b[2], 0), bw - size[1]))
        wins.append(size)
    return tuple(wins), win0


class BandSets(NamedTuple):
    """What a channel-packed kernel (`ops.warp.render_rgba_ctrl`,
    `render_expr_ctrl`) takes for G granule sets of C bands each."""
    bands: tuple                # G C-tuples of scene arrays, as cached
    params: np.ndarray          # (G, 11) f32, a row a set; (G, R, 11)
    prios: np.ndarray           # (G, C) f32, -inf rows are padding
    key: tuple                  # (G, bh, bw, C); (G, R, C) on R grids;
                                # (G, K, C), (G, K, R, C) in K CRSs
    win: Optional[tuple]        # (wr, wc); R of them on R grids
    win0: Optional[np.ndarray]  # (G, 2); (G, R, 2) on R grids
    grid_of: Optional[tuple]    # channel -> grid, None on one grid
    crs_of: Optional[np.ndarray] = None     # (G,) int32 set -> control
                                            # grid, None in one CRS


def _band_sets(scenes, sets, grid_of, prios, rows, cx, cy,
               crs_of=None) -> BandSets:
    """Kernel operands for ``sets`` (`_grid_sets`): each the indices of
    its C scenes (`DeviceScene`s) in ``scenes``, in channel order, with
    mosaic priorities ``prios[i]`` and param rows ``rows[i]`` (affine6
    relative to the control grid's origin, height, width, nodata).  A
    param row a (set, grid), (G, 11) where the sets lie on one grid.  G
    is a power of two (bounded jit variants): the filling repeats the
    first set with a priority that never wins.  The gather windows are
    those of the same param rows, from the host control coordinates
    ``cx``, ``cy`` (origin-relative, f64).  Where the sets lie in K
    CRSs (``crs_of``, `_set_crs`) ``cx``, ``cy`` are K grids (K, gh,
    gw), each row relative to its own CRS's origin, and a set's window
    is read on its own grid."""
    G = _bucket_pow2(len(sets))
    C = len(sets[0])
    lead = [grid_of.index(r) for r in range(max(grid_of) + 1)] \
        if grid_of else [0]
    bands = tuple(tuple(scenes[i].dev for i in m) for m in sets)
    bands += (bands[0],) * (G - len(sets))
    params = np.zeros((G, len(lead), 11), np.float64)
    params[..., 10] = -1.0
    for k, m in enumerate(sets):
        for r, c in enumerate(lead):
            params[k, r, :9] = rows[m[c]]
            params[k, r, 10] = 0.0
    chan_prios = np.full((G, C), -np.inf, np.float32)
    chan_prios[:len(sets)] = [[prios[i] for i in m] for m in sets]
    buckets = [tuple(scenes[sets[0][c]].dev.shape) for c in lead]
    if grid_of is None:
        params = params[:, 0]
        key = (G,) + buckets[0] + (C,)
    else:
        key = (G, len(lead), C)
    at = None
    if crs_of is not None:
        # the filling sets are the first one again, on its grid
        at = np.zeros(G, np.int32)
        at[:len(sets)] = crs_of
        cx, cy = cx[at], cy[at]
        key = (G, max(crs_of) + 1) + ((C,) if grid_of is None else key[1:])
    made = None
    if _window_mode():
        made = _gather_windows(params, cx, cy, *buckets[0]) \
            if grid_of is None else _grid_windows(params, cx, cy, buckets)
    win, win0 = made or (None, None)
    return BandSets(bands, params.astype(np.float32), chan_prios, key,
                    win, win0, grid_of, at)


def _sets_leg(name: str, b: BandSets) -> str:
    """The dispatch leg of a band-set kernel call: ``name``, ``_mg``
    where a set spans several pixel grids, ``_mc`` where the sets lie in
    several CRSs (with or without several grids)."""
    if b.crs_of is not None:
        return name + "_mc"
    return name if b.grid_of is None else name + "_mg"


def _grids_arg(b: BandSets) -> dict:
    """The kernels' ``grid_of`` and ``crs_of`` keywords, each left out
    on one grid and in one CRS: a call with one and one without are two
    entries of jit's cache, and a one-grid, one-CRS set must run the
    program prewarm and its past made."""
    out = {} if b.grid_of is None else {"grid_of": b.grid_of}
    if b.crs_of is not None:
        out["crs_of"] = jnp.asarray(b.crs_of)
    return out


class SceneGroup(NamedTuple):
    """What `_scene_groups` hands a fused scene kernel for one group of
    granules on one source grid."""
    # (B, bh, bw) stack the scene cache keeps, or (stacked=False) the
    # tuple of the B scene arrays; B a power of two
    stack: object
    ctrl: np.ndarray            # host (2, gh, gw) f32 control grid
    ctrl_dev: object            # its cached device copy
    params: np.ndarray          # (B, 11) f32 kernel params
    params64: np.ndarray        # the same in f64 (footprints, pages)
    step: int                   # control-grid step, validated
    skey: tuple                 # scene serials + (B,): content identity
    win: Optional[Tuple[int, int]]      # gather window, None = whole
    win0: Optional[np.ndarray]  # its origin: (2,), or (B, 2) unstacked
    scenes: list                # the real granules' DeviceScenes
    # a geolocation grid's group: ctrl holds pixel coordinates and the
    # param rows an identity affine
    curvilinear: bool = False


class WarpExecutor:
    """Batches decoded granule windows into device dispatches."""

    # LRU bounds, not clear-alls: a burst of distinct tiles must evict
    # the oldest entries, not dump the whole working set (a clear causes
    # a recompute/re-upload storm exactly when traffic is heaviest)
    _GEO_CACHE_MAX = 256
    # per-granule scalar strides get their own (much larger) map: one
    # tiny entry per granule geotransform must not flush the multi-MB
    # projection grids out of the 256-slot LRU above
    _STRIDE_CACHE_MAX = 8192

    def __init__(self):
        self._geo_cache: OrderedDict = OrderedDict()
        self._stride_cache: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        # dispatch counters by (path, shape bucket) — the /debug
        # side-door's "where do renders actually go" answer
        self.bucket_stats: Dict[str, int] = {}
        # gather-window engagement (window mode on): groups that got a
        # window vs groups that declined (footprint ~ scene / no coords)
        self.win_engaged = 0
        self.win_declined = 0
        # paged-path engagement (GSKY_PAGED on): dispatches served from
        # the page pool vs declined back to buckets (page budget / pool
        # pressure / multi-CRS)
        self.paged_engaged = 0
        self.paged_declined = 0
        # band sets the channel-packed kernels took, by how many pixel
        # grids a set spans, and granule lists on several pixel sizes
        # that formed none (/debug `band_grids`)
        self.band_grids = {"sets_one_grid": 0, "sets_multi_grid": 0,
                           "multi_grid_declined": 0}
        # the same sets by how many CRSs a tile's sets lie in, and
        # granule lists in several CRSs that formed none (/debug
        # `band_crs`)
        self.band_crs = {"sets_one_crs": 0, "sets_multi_crs": 0,
                         "multi_crs_declined": 0}

    def _note_grids(self, made, scenes) -> None:
        """Count what `_grid_sets` made of ``scenes``: its sets by their
        grids and by the CRSs they lie in, or a decline where the scenes
        have several pixel sizes, or lie in several CRSs.  The open span
        (`tile.dispatch`) carries the sets' grid and CRS counts."""
        with self._lock:
            if made is None:
                if len({(abs(s.gt.dx), abs(s.gt.dy)) for s in scenes}) > 1:
                    self.band_grids["multi_grid_declined"] += 1
                if len({s.crs for s in scenes}) > 1:
                    self.band_crs["multi_crs_declined"] += 1
                return
            sets, grid_of = made
            n_crs = len({scenes[m[0]].crs for m in sets})
            self.band_grids["sets_one_grid" if grid_of is None
                            else "sets_multi_grid"] += len(sets)
            self.band_crs["sets_one_crs" if n_crs == 1
                          else "sets_multi_crs"] += len(sets)
        obs_set_attr(grids=1 if grid_of is None else max(grid_of) + 1,
                     crs=n_crs)

    @staticmethod
    def _note_taps(method: str, parts, out_hw) -> None:
        """Record in `TAP_FORM` how the XLA scored program fetches its
        taps over each (stack, window) of ``parts`` (`ops.warp.tap_form`,
        decided from the shapes it is handed): ``per_tap`` where any
        part gathers a tap at a time, else ``neighbourhood``."""
        forms = {tap_form(method, st, win, out_hw) for st, win in parts}
        TAP_FORM.set("per_tap" if "per_tap" in forms else "neighbourhood")

    def _note_win(self, win) -> None:
        """Engagement telemetry, recorded at the dispatches that
        actually pass ``win`` to a kernel."""
        if not _window_mode():
            return
        with self._lock:
            if win is not None:
                self.win_engaged += 1
            else:
                self.win_declined += 1

    def _count(self, path: str, bucket=None) -> None:
        """One dispatch through leg ``path``; the open span (the staged
        tile path's `tile.dispatch`) carries the leg's name."""
        obs_set_attr(leg=path)
        key = f"{path}:{bucket}" if bucket is not None else path
        with self._lock:
            self.bucket_stats[key] = self.bucket_stats.get(key, 0) + 1

    def _geo_cache_get(self, key):
        with self._lock:
            hit = self._geo_cache.get(key)
            if hit is not None:
                self._geo_cache.move_to_end(key)
            return hit

    def _geo_cache_put(self, key, value):
        with self._lock:
            self._geo_cache[key] = value
            self._geo_cache.move_to_end(key)
            while len(self._geo_cache) > self._GEO_CACHE_MAX:
                self._geo_cache.popitem(last=False)

    def _dst_geo_coords(self, dst_gt: GeoTransform, dst_crs: CRS,
                        height: int, width: int,
                        src_crs: CRS) -> Tuple[np.ndarray, np.ndarray]:
        """(sx, sy): dst pixel centres projected into src CRS, cached —
        the projection math is shared by every granule in that CRS (the
        expensive part of `coord_grid`)."""
        key = (dst_gt.to_gdal(), dst_crs, height, width, src_crs)
        hit = self._geo_cache_get(key)
        if hit is not None:
            return hit
        c = np.arange(width, dtype=np.float64) + 0.5
        r = np.arange(height, dtype=np.float64) + 0.5
        C, R = np.meshgrid(c, r)
        x, y = dst_gt.pixel_to_geo(C, R, np)
        sx, sy = dst_crs.transform_to(src_crs, x, y, np)
        sx = np.asarray(sx, np.float64)
        sy = np.asarray(sy, np.float64)
        self._geo_cache_put(key, (sx, sy))
        return sx, sy

    def _ctrl_geo_coords(self, dst_gt: GeoTransform, dst_crs: CRS,
                         height: int, width: int, src_crs: CRS,
                         step: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """Sparse control-point grid: dst pixel centres at every
        ``step``-th row/col projected into src CRS (f64, host).  The
        dense grid is reconstructed on device (`ops.warp._bilerp_grid`),
        GDAL-approx-transformer style, so only ~2 KB of coordinates are
        uploaded per tile.  The helper leans on the grid being regular
        (nodes at ``k * step``, ``gh = (height - 1 + step - 1) // step
        + 1``): a pixel's cell is known when the kernel is traced, so
        its corners are repeated to it and not gathered.

        Like GDAL's approx transformer (0.125 px error bound,
        `worker/gdalprocess/warp.go:219`), the grid is validated once
        per cache entry against exactly projected cell midpoints; the
        step halves until the interpolation error is within bound (so
        strongly nonlinear transforms — polar CRSs — refine instead of
        silently smearing).  Returns (sx, sy, actual_step)."""
        key = ("ctrl", dst_gt.to_gdal(), dst_crs, height, width, src_crs,
               step)
        hit = self._geo_cache_get(key)
        if hit is not None:
            return hit
        while True:
            gh = (height - 1 + step - 1) // step + 1
            gw = (width - 1 + step - 1) // step + 1
            c = np.arange(gw, dtype=np.float64) * step + 0.5
            r = np.arange(gh, dtype=np.float64) * step + 0.5
            C, R = np.meshgrid(c, r)
            x, y = dst_gt.pixel_to_geo(C, R, np)
            sx, sy = dst_crs.transform_to(src_crs, x, y, np)
            sx = np.asarray(sx, np.float64)
            sy = np.asarray(sy, np.float64)
            if step <= 2 or self._ctrl_err_px(
                    sx, sy, dst_gt, dst_crs, src_crs, step) <= 0.125:
                break
            step //= 2
        self._geo_cache_put(key, (sx, sy, step))
        return sx, sy, step

    def _ctrl_sets(self, dst_gt: GeoTransform, dst_crs: CRS, height: int,
                   width: int, origins):
        """The control grids of band sets in the CRSs of ``origins``
        (`_set_crs`), each `_ctrl_geo_coords`' (cached per (dst, src
        CRS)) relative to its CRS's origin and all at one step, the
        finest any of them needs.  Returns (cx, cy, ctrl_dev, step):
        host f64 (gh, gw) and the device (2, gh, gw) f32 in one CRS, (K,
        gh, gw) and (K, 2, gh, gw) in K; the device copy is kept in the
        geo cache, under the one-CRS key `_scene_groups` uses too."""
        step = 16
        while True:
            made = [self._ctrl_geo_coords(dst_gt, dst_crs, height, width,
                                          crs, step)
                    for crs, _, _ in origins]
            if all(m[2] == step for m in made):
                break
            step = min(m[2] for m in made)
        cx = np.stack([m[0] - o[1] for m, o in zip(made, origins)])
        cy = np.stack([m[1] - o[2] for m, o in zip(made, origins)])
        if len(origins) == 1:
            cx, cy = cx[0], cy[0]
        dkey = ("ctrldev", dst_gt.to_gdal(), dst_crs, height, width) \
            + sum(origins, ())
        ctrl_dev = self._geo_cache_get(dkey)
        if ctrl_dev is None:
            ctrl_dev = jnp.asarray(
                np.stack([cx, cy], axis=-3).astype(np.float32))
            self._geo_cache_put(dkey, ctrl_dev)
        return cx, cy, ctrl_dev, step

    @staticmethod
    def _ctrl_err_px(sx: np.ndarray, sy: np.ndarray, dst_gt: GeoTransform,
                     dst_crs: CRS, src_crs: CRS, step: int) -> float:
        """Max bilinear-interpolation error of the ctrl grid at cell
        midpoints, in units of local source-coords-per-dst-pixel."""
        gh, gw = sx.shape
        if gh < 2 or gw < 2:
            return 0.0
        c = (np.arange(gw - 1, dtype=np.float64) + 0.5) * step + 0.5
        r = (np.arange(gh - 1, dtype=np.float64) + 0.5) * step + 0.5
        C, R = np.meshgrid(c, r)
        x, y = dst_gt.pixel_to_geo(C, R, np)
        ex, ey = dst_crs.transform_to(src_crs, x, y, np)
        ix = 0.25 * (sx[:-1, :-1] + sx[:-1, 1:] + sx[1:, :-1]
                     + sx[1:, 1:])
        iy = 0.25 * (sy[:-1, :-1] + sy[:-1, 1:] + sy[1:, :-1]
                     + sy[1:, 1:])
        du = np.hypot(sx[:-1, 1:] - sx[:-1, :-1],
                      sy[:-1, 1:] - sy[:-1, :-1]) / step
        dv = np.hypot(sx[1:, :-1] - sx[:-1, :-1],
                      sy[1:, :-1] - sy[:-1, :-1]) / step
        scale = np.maximum(np.maximum(du, dv), 1e-12)
        with np.errstate(invalid="ignore"):
            px = np.hypot(np.asarray(ex) - ix, np.asarray(ey) - iy) / scale
        if not px.size or np.all(np.isnan(px)):
            return 0.0
        return float(np.nanmax(px))

    def _granule_stride(self, g, dst_gt: GeoTransform, dst_crs: CRS,
                        height: int, width: int) -> float:
        """Source pixels stepped per dst pixel for a granule under this
        request — drives overview-level selection in the scene cache
        (`worker/gdalprocess/warp.go:156-198`).  Reuses the cached ctrl
        grid, so the cost after the first call per (dst, src CRS) is a
        few medians."""
        from ..geo.crs import parse_crs
        try:
            key = (dst_gt.to_gdal(), dst_crs, height, width,
                   g.srs, tuple(g.geo_transform or ()))
            with self._lock:
                hit = self._stride_cache.get(key)
                if hit is not None:
                    self._stride_cache.move_to_end(key)
                    return hit
            src_crs = parse_crs(g.srs) if g.srs else None
            if src_crs is None:
                return 1.0
            sx, sy, step = self._ctrl_geo_coords(dst_gt, dst_crs, height,
                                                 width, src_crs, 16)
            ggt = GeoTransform.from_gdal(g.geo_transform)
            col, row = ggt.geo_to_pixel(sx, sy, np)
            with np.errstate(invalid="ignore"):
                dr = np.nanmedian(np.abs(np.diff(row, axis=0))) / step
                dc = np.nanmedian(np.abs(np.diff(col, axis=1))) / step
            stride = min(float(dr), float(dc))
            stride = stride if np.isfinite(stride) and stride > 1.0 \
                else 1.0
            with self._lock:
                self._stride_cache[key] = stride
                while len(self._stride_cache) > self._STRIDE_CACHE_MAX:
                    self._stride_cache.popitem(last=False)
            return stride
        except Exception:
            return 1.0

    def warm_scene(self, g, dst_gt: GeoTransform, dst_crs: CRS,
                   height: int, width: int, cache=None):
        """Decode + upload one granule's scene into the device cache at
        the overview level this destination grid needs, returning the
        `DeviceScene` or None (uncacheable).  The export engine's decode
        stage calls this ahead of the warp stage so `warp_mosaic_scenes`
        hits a warm cache; the stride logic is exactly `_scene_groups`'
        so both pick the same cache level."""
        from .scene_cache import default_scene_cache
        cache = cache or default_scene_cache
        stride = 1.0 if g.geo_loc else self._granule_stride(
            g, dst_gt, dst_crs, height, width)
        return cache.get(g, stride,
                         dst_bbox=dst_gt.bbox(width, height),
                         dst_crs=dst_crs)

    def warp_all(self, windows: Sequence[Optional[DecodedWindow]],
                 dst_gt: GeoTransform, dst_crs: CRS, height: int, width: int,
                 method: str = "near") -> List[Optional[Tuple[np.ndarray, np.ndarray]]]:
        """Warp every decoded window onto the dst grid.  Returns, per
        input, (data (H,W) f32, ok (H,W) bool) or None."""
        jobs: List[Tuple[int, DecodedWindow, np.ndarray, np.ndarray]] = []
        for i, wdw in enumerate(windows):
            if wdw is None:
                continue
            sx, sy = self._dst_geo_coords(dst_gt, dst_crs, height, width,
                                          wdw.src_crs)
            col, row = wdw.window_gt.geo_to_pixel(sx, sy, np)
            jobs.append((i, wdw, (row - 0.5).astype(np.float32),
                         (col - 0.5).astype(np.float32)))

        results: List[Optional[Tuple[np.ndarray, np.ndarray]]] = \
            [None] * len(windows)
        # bucket by padded source shape
        buckets: Dict[Tuple[int, int], List] = {}
        for job in jobs:
            h, w = job[1].data.shape
            buckets.setdefault((_bucket(h), _bucket(w)), []).append(job)

        for (bh, bw), batch in buckets.items():
            B = _bucket_pow2(len(batch))  # pow2 pad: bounded jit variants
            self._count("window_batch", (bh, bw, B))
            src = np.zeros((B, bh, bw), np.float32)
            valid = np.zeros((B, bh, bw), bool)
            rows = np.full((B, height, width), -1e6, np.float32)
            cols = np.full((B, height, width), -1e6, np.float32)
            for k, j in enumerate(batch):
                rows[k] = j[2]
                cols[k] = j[3]
            for k, (_, wdw, _, _) in enumerate(batch):
                h, w = wdw.data.shape
                src[k, :h, :w] = wdw.data
                valid[k, :h, :w] = wdw.valid
            out, ok = warp_gather_batch(
                jnp.asarray(src), jnp.asarray(valid),
                jnp.asarray(rows), jnp.asarray(cols), method)
            # results stay ON DEVICE (lazy per-granule slices); downstream
            # mosaic/expr/scale stages consume them without a host round
            # trip (every sync stalls the dispatching thread)
            for k, (i, _, _, _) in enumerate(batch):
                results[i] = (out[k], ok[k])
        return results


    def warp_mosaic(self, windows: Sequence[DecodedWindow],
                    ns_ids: Sequence[int], prios: Sequence[float],
                    dst_gt: GeoTransform, dst_crs: CRS,
                    height: int, width: int, n_ns: int,
                    method: str = "near"):
        """Fused fast path: warp every window AND mosaic per namespace in
        one device dispatch per source CRS (uploads: padded window stack
        + ~2 KB control grid + per-granule affine params — NOT the dense
        (2, B, H, W) coordinate grids, which cost ~32 MB/tile for deep
        stacks).  The dense dst->src projection happens once per
        (dst grid, src CRS) on host at control points; the device
        reconstructs it bilinearly (0.125 px validated error, as the
        scene path does).

        Returns (canvases (n_ns_pad, H, W) f32 jax, valids bool jax) —
        callers slice the first ``n_ns`` entries.
        """
        by_crs: Dict[CRS, List[int]] = {}
        for i, wdw in enumerate(windows):
            by_crs.setdefault(wdw.src_crs, []).append(i)
        n_pad = _bucket_pow2(n_ns)
        parts, srcs = [], []
        for crs, idxs in by_crs.items():
            sx, sy, step = self._ctrl_geo_coords(dst_gt, dst_crs, height,
                                                 width, crs, 16)
            gs = [windows[i] for i in idxs]
            bh = _bucket(max(g.data.shape[0] for g in gs))
            bw = _bucket(max(g.data.shape[1] for g in gs))
            B = _bucket_pow2(len(gs))
            src = np.full((B, bh, bw), np.nan, np.float32)
            params = np.zeros((B, 11), np.float64)
            params[:, 10] = -1.0
            ox, oy = gs[0].window_gt.x0, gs[0].window_gt.y0
            ctrl = np.stack([sx - ox, sy - oy]).astype(np.float32)
            for k, (i, wdw) in enumerate(zip(idxs, gs)):
                h0, w0 = wdw.data.shape
                src[k, :h0, :w0] = np.where(wdw.valid, wdw.data, np.nan)
                params[k, :6] = _inv_gt_params(wdw.window_gt, ox, oy)
                params[k, 6] = h0
                params[k, 7] = w0
                params[k, 8] = np.nan   # validity is NaN-encoded in src
                params[k, 9] = prios[i]
                params[k, 10] = ns_ids[i]
            srcs.append((src, None))
            parts.append(warp_scored_raced(
                jnp.asarray(src), jnp.asarray(ctrl),
                jnp.asarray(params.astype(np.float32)), method, n_pad,
                (height, width), step))
        self._note_taps(method, srcs, (height, width))
        if len(parts) == 1:
            canv, best = parts[0]
            return canv, best > -jnp.inf
        canvs = jnp.stack([p[0] for p in parts])
        bests = jnp.stack([p[1] for p in parts])
        return combine_scored(canvs, bests)


    def _choose_leg(self, group: Optional["SceneGroup"], n_pad: int,
                    lane_union: bool = False):
        """Which leg serves one scene group, from what the code can
        observe: ("spmd", the mesh dispatcher) under GSKY_SPMD compat
        routing; ("wave" | "paged", (pool, tables, params16)) where the
        paged kernels run (interpret mode only today: Mosaic refuses
        their gather) and the page pool takes the group, a wave where
        the tick scheduler is on; else ("bucketed", None), the leg a
        TPU takes.  ``group`` None: scenes of several groups (band sets
        over several pixel grids), which no page table serves.  The ONE
        place the tile legs consult `compat_spmd()`, `paged_enabled()`
        and `waves_enabled()`."""
        spmd = compat_spmd()
        if spmd is not None:
            return "spmd", spmd
        if group is not None and paged_enabled():
            made_p = self._paged_from_group(group, n_pad, lane_union)
            self._note_paged(made_p is not None)
            if made_p is not None:
                from .waves import waves_enabled
                return ("wave" if waves_enabled() else "paged"), made_p
        return "bucketed", None

    def _run_leg(self, leg: str, how, group: "SceneGroup", name: str,
                 finish, bucketed, spmd=None, wave=None, paged=None):
        """The spmd / wave / paged / bucketed skeleton, written once:
        counts ``name`` + the leg's suffix and runs the caller's thunk
        for `_choose_leg`'s answer under that leg's guard label.  The
        device result of one tile goes through ``finish``; a wave's
        comes back finished, from the host.

        bucketed()                          the per-call XLA/raced form
        spmd(mesh)                          the mesh-owned form
        wave(pool, tables, params16, percall)
            enqueue to the tick scheduler; ``percall`` is the guarded
            bucketed form, this request alone (incident failover)
        paged(parr, tables (1, T, S), params16)
            the per-call paged form over the locked pool array"""
        from .. import device_guard
        if leg == "spmd":
            self._count(name + "_spmd", (group.stack.shape, group.win))
            self._note_win(group.win)
            return finish(spmd(how))
        if leg == "bucketed":
            self._count(name, (group.stack.shape, group.win))
            self._note_win(group.win)
            return finish(device_guard.run("dispatch.bucketed", bucketed))
        pool, tables, params16 = how
        if leg == "wave":
            self._count(name + "_wave", tables.shape)
            return wave(pool, tables, params16,
                        lambda: device_guard.run("dispatch.bucketed",
                                                 bucketed))
        self._count(name + "_paged", tables.shape)

        def _dispatch():
            with pool.locked_pool() as parr:
                return paged(parr, jnp.asarray(tables[None]),
                             jnp.asarray(params16))

        try:
            return finish(device_guard.run("dispatch.paged", _dispatch))
        finally:
            pool.unpin(tables)

    def warp_mosaic_scenes(self, granules, ns_ids: Sequence[int],
                           prios: Sequence[float], dst_gt: GeoTransform,
                           dst_crs: CRS, height: int, width: int,
                           n_ns: int, method: str = "near", cache=None):
        """Fastest path: fused warp+mosaic from device-cached full scenes
        (`ops.warp.warp_scenes_batch`).  Per tile this uploads only the
        shared ~0.5 MB coordinate grid + a (B, 11) param block; scene
        pixels never leave HBM between requests.

        Returns (canvases, valids) jax arrays, or None when the granule
        set is not uniform enough (mixed CRS/dtype/bucket) or a scene is
        uncacheable — callers fall back to the window path.
        """
        groups = self._scene_groups(granules, ns_ids, prios, dst_gt,
                                    dst_crs, height, width, cache)
        if groups is None:
            return None
        n_pad = _bucket_pow2(n_ns)

        def scored(g):
            return warp_scored_raced(
                g.stack, g.ctrl_dev, jnp.asarray(g.params), method,
                n_pad, (height, width), g.step, win=g.win,
                win0_dev=_dev_win0(g.win0))

        if len(groups) > 1:
            # multi-CRS granule set (e.g. scenes across UTM zones): one
            # scored dispatch per source-CRS group, then a per-pixel
            # priority combine — newest-wins survives the grouping
            # because each partial carries its winners' priorities
            self._count("scene_mosaic_multicrs", len(groups))
            for g in groups:
                self._note_win(g.win)
            self._note_taps(method, [(g.stack, g.win) for g in groups],
                            (height, width))
            parts = [scored(g) for g in groups]
            return combine_scored(jnp.stack([p[0] for p in parts]),
                                  jnp.stack([p[1] for p in parts]))
        g = groups[0]
        statics = (method, n_pad, (height, width), g.step)

        def wave(pool, tables, params16, percall):
            def host():
                c, b = percall()
                return np.asarray(c), np.asarray(b) > -np.inf

            from .waves import default_waves
            c, v = default_waves().warp_scored(
                pool, tables, params16, g.ctrl, statics,
                (g.stack, g.params, g.win, g.win0), host,
                serials=g.skey)
            return jnp.asarray(c), jnp.asarray(v)

        def paged(parr, tables, params16):
            from ..ops.paged import warp_scored_paged_raced
            from ..ops.warp import warp_scenes_ctrl_scored

            def _xla():
                c, b = warp_scenes_ctrl_scored(
                    g.stack, g.ctrl_dev, jnp.asarray(g.params),
                    *statics, win=g.win, win0=_dev_win0(g.win0))
                return c[None], b[None]

            canvs, bests = warp_scored_paged_raced(
                parr, tables, params16, g.ctrl_dev[None], *statics,
                _xla)
            return canvs[0], bests[0]

        leg, how = self._choose_leg(g, n_pad)
        if leg == "bucketed":
            self._note_taps(method, [(g.stack, g.win)], (height, width))
        return self._run_leg(
            leg, how, g, "scene_mosaic",
            lambda cb: (cb[0], cb[1] > -jnp.inf),
            lambda: scored(g),
            spmd=lambda mesh: mesh.mosaic_scored(
                g.stack, g.ctrl_dev, g.params, *statics,
                win=g.win, win0=g.win0),
            wave=wave, paged=paged)

    def render_byte_scenes(self, granules, ns_ids: Sequence[int],
                           prios: Sequence[float], dst_gt: GeoTransform,
                           dst_crs: CRS, height: int, width: int,
                           n_ns: int, method: str = "near",
                           offset: float = 0.0, scale: float = 0.0,
                           clip: float = 0.0, colour_scale: int = 0,
                           auto: bool = True, cache=None):
        """Whole-tile fast path: cached scenes -> PNG-ready uint8
        composite in one dispatch.  Returns a uint8 (H, W) array (host
        from a wave, else device with its readback started) or None
        (fallback)."""
        g = self._scene_inputs(granules, ns_ids, prios, dst_gt,
                               dst_crs, height, width, cache)
        if g is None:
            return None
        sp = np.array([offset, scale, clip], np.float32)
        statics = (method, _bucket_pow2(n_ns), (height, width), g.step,
                   auto, colour_scale)

        def wave(pool, tables, params16, percall):
            from .waves import default_waves
            return default_waves().render_byte(
                pool, tables, params16, g.ctrl, sp, statics,
                (g.stack, g.params, g.win, g.win0),
                lambda: np.asarray(percall()), serials=g.skey)

        def paged(parr, tables, params16):
            from ..ops.paged import render_byte_paged_raced
            from ..ops.warp import render_scenes_ctrl
            return render_byte_paged_raced(
                parr, tables, params16, g.ctrl_dev[None],
                jnp.asarray(sp[None]), *statics,
                lambda: render_scenes_ctrl(
                    g.stack, g.ctrl_dev, jnp.asarray(g.params),
                    jnp.asarray(sp), *statics, win=g.win,
                    win0=_dev_win0(g.win0))[None])[0]

        return self._run_leg(
            *self._choose_leg(g, statics[1]), g, "render_byte", _prefetch,
            lambda: render_byte_raced(
                g.stack, g.ctrl_dev, jnp.asarray(g.params),
                jnp.asarray(sp), *statics, win=g.win,
                win0_dev=_dev_win0(g.win0)),
            spmd=lambda mesh: mesh.render_composite(
                g.stack, g.ctrl_dev, g.params, sp, *statics,
                win=g.win, win0=g.win0),
            wave=wave, paged=paged)

    def render_expr_byte(self, granules, ns_ids: Sequence[int],
                         prios: Sequence[float], dst_gt: GeoTransform,
                         dst_crs: CRS, height: int, width: int,
                         n_slots: int, fp, method: str = "near",
                         offset: float = 0.0, scale: float = 0.0,
                         clip: float = 0.0, colour_scale: int = 0,
                         auto: bool = True, cache=None):
        """Fused band-algebra fast path (GSKY_EXPR_FUSE): cached scenes
        -> one program that gathers EVERY referenced band's window,
        interpolates each, evaluates the expression as a traced
        epilogue and scales to byte — no per-band mosaic dispatches, no
        f32 plane round-trips through HBM.  On the bucketed leg (a
        TPU's) that program is `ops.warp.render_expr_ctrl` over the
        scene cache's own arrays (`_render_expr_sets`); the wave and
        paged legs run the paged epilogue.

        ``ns_ids`` are fingerprint SLOT indices (variable i of ``fp``
        is mosaic slot i); ``fp`` is the `ops.expr.ExprFingerprint`.
        Returns a uint8 (H, W) array or None — the caller then runs
        the unfused `evaluate_expressions` leg (granules that form no
        band sets, page budget, SPMD compat mode).  Scenes of several
        scene groups (a variable's band on a coarser pixel grid:
        Sentinel-2's 20 m SWIR beside 10 m NIR; granules either side of
        a UTM zone line) go to the bucketed leg's band sets, which take
        up to three grids a set and three CRSs a tile."""
        groups = self._scene_groups(granules, ns_ids, prios, dst_gt,
                                    dst_crs, height, width, cache,
                                    stacked=False, windowed=False)
        if groups is None:
            return None
        n_pad = _bucket_pow2(n_slots)
        leg, how = self._choose_leg(groups[0] if len(groups) == 1 else None,
                                    n_pad, lane_union=True)
        if leg == "spmd":
            return None
        if leg == "bucketed":
            return self._render_expr_sets(
                groups, n_slots, fp, method, dst_gt, dst_crs,
                (height, width), offset, scale, clip, colour_scale, auto)
        # the paged forms race a stacked XLA reference
        g = self._stacked(groups[0], cache)
        sp = np.array([offset, scale, clip], np.float32)
        consts = fp.const_array()
        statics = (method, n_pad, (height, width), g.step, auto,
                   colour_scale, fp.key)
        from ..ops.paged import expr_epilogue, note_expr_fused

        def _unfused_xla():
            # the race/fallback reference: bucketed scored mosaic +
            # the SAME epilogue + scale — `evaluate_expressions`
            # semantics op for op
            from ..ops.scale import scale_to_byte
            from ..ops.warp import warp_scenes_ctrl_scored
            c, b = warp_scenes_ctrl_scored(
                g.stack, g.ctrl_dev, jnp.asarray(g.params), method,
                n_pad, (height, width), g.step, win=g.win,
                win0=_dev_win0(g.win0))
            plane, ok = expr_epilogue(c[None], b[None], fp.key,
                                      jnp.asarray(consts[None]))
            return scale_to_byte(plane, ok, offset, scale, clip,
                                 colour_scale, auto)

        def wave(pool, tables, params16, percall):
            # expression lanes coalesce with every other lane of the
            # tick that shares (statics, fingerprint, pool)
            from .waves import default_waves
            return default_waves().render_expr(
                pool, tables, params16, g.ctrl, sp, consts, statics,
                (g.stack, g.params, g.win, g.win0),
                lambda: np.asarray(percall()), serials=g.skey)

        def paged(parr, tables, params16):
            from ..ops.paged import render_expr_paged_raced
            return render_expr_paged_raced(
                parr, tables, params16, g.ctrl_dev[None],
                jnp.asarray(sp[None]), jnp.asarray(consts[None]),
                *statics, fp.hash, _unfused_xla)[0]

        note_expr_fused("wave" if leg == "wave" else "percall")
        return self._run_leg(leg, how, g, "render_expr", _prefetch,
                             lambda: _unfused_xla()[0],
                             wave=wave, paged=paged)

    def _render_expr_sets(self, groups: List["SceneGroup"], n_slots: int,
                          fp, method: str, dst_gt: GeoTransform,
                          dst_crs: CRS, out_hw: Tuple[int, int],
                          offset: float, scale: float, clip: float,
                          colour_scale: int, auto: bool):
        """`render_expr_byte`'s bucketed leg: the unstacked groups'
        scenes as band sets (`_grid_sets`: one a footprint with a scene
        a slot, on up to three pixel grids), through ONE
        `ops.warp.render_expr_ctrl` dispatch, on the first group's
        control grid where the sets lie in one CRS, on a control grid a
        CRS where they lie in several (`_ctrl_sets`: a tile across a UTM
        zone line).  None where they form no such sets (a footprint that
        lacks a variable's band, two dates on one footprint, sets in
        more than `_MAX_CRS` CRSs): the unfused leg's.  A curvilinear
        group's control grid holds pixel coordinates: its sets are the
        bands of one file (rows alike), and it shares a dispatch with no
        other."""
        g0 = groups[0]
        scenes = [s for g in groups for s in g.scenes]
        p64 = np.concatenate([g.params64[:len(g.scenes)] for g in groups])
        chans = p64[:, 10].astype(int).tolist()
        made = None
        if len(groups) == 1:
            rows = p64[:, :9]           # on the group's own origin
            made = _grid_sets(scenes, chans, n_slots, footprints=[
                r.tobytes() for r in rows] if g0.curvilinear else None)
        elif not any(g.curvilinear for g in groups):
            made = _grid_sets(scenes, chans, n_slots)
        self._note_grids(made, scenes)
        if made is None:
            return None
        sets, grid_of = made
        crs_of = None
        if len(groups) == 1:
            cx = np.asarray(g0.ctrl[0], np.float64)
            cy = np.asarray(g0.ctrl[1], np.float64)
            ctrl_dev, step = g0.ctrl_dev, g0.step
        else:
            # scenes of several groups (pixel grids, CRSs): a control
            # grid a CRS, every set's rows on its own CRS's origin
            origins, crs_of = _set_crs(scenes, sets)
            rows = _rows_on(scenes, origins)
            cx, cy, ctrl_dev, step = self._ctrl_sets(
                dst_gt, dst_crs, out_hw[0], out_hw[1], origins)
        b = _band_sets(scenes, sets, grid_of, p64[:, 9], rows, cx, cy,
                       crs_of)
        from ..ops.paged import note_expr_fused
        from ..ops.warp import render_expr_ctrl
        self._count(_sets_leg("render_expr", b), (b.key, b.win))
        self._note_win(b.win)
        note_expr_fused("bucketed")
        sp = np.array([offset, scale, clip], np.float32)
        return _prefetch(render_expr_ctrl(
            b.bands, ctrl_dev, jnp.asarray(b.params),
            jnp.asarray(b.prios), jnp.asarray(sp),
            jnp.asarray(fp.const_array()), fp.key, method, out_hw, step,
            auto, colour_scale, win=b.win, win0=_dev_win0(b.win0),
            **_grids_arg(b)))

    def render_bands_byte(self, granules, ns_ids: Sequence[int],
                          prios: Sequence[float], dst_gt: GeoTransform,
                          dst_crs: CRS, height: int, width: int,
                          n_ns: int, out_sel: Sequence[int],
                          method: str = "near", offset: float = 0.0,
                          scale: float = 0.0, clip: float = 0.0,
                          colour_scale: int = 0, auto: bool = True,
                          cache=None):
        """Multi-band fused fast path (RGB styles): one dispatch from
        cached scenes to per-band uint8 planes
        (`ops.warp.render_scenes_bands_ctrl`).  The kernel takes the
        scenes as the scene cache holds them and stacks only their
        gather windows, so no copy of a raster is made or kept.
        Returns a device uint8 (n_out, H, W) array or None (fallback)."""
        g = self._scene_inputs(granules, ns_ids, prios, dst_gt,
                               dst_crs, height, width, cache,
                               stacked=False)
        if g is None:
            return None
        devs = g.stack
        self._count("render_bands",
                    ((len(devs),) + devs[0].shape, g.win))
        self._note_win(g.win)
        sp = jnp.asarray(np.array([offset, scale, clip], np.float32))
        sel = jnp.asarray(np.asarray(out_sel, np.int32))
        return _prefetch(render_scenes_bands_ctrl(
            devs, g.ctrl_dev, jnp.asarray(g.params), sp, sel,
            method, _bucket_pow2(n_ns), (height, width), g.step, auto,
            colour_scale, win=g.win, win0=_dev_win0(g.win0)))

    def render_rgba_byte(self, granules, ns_ids: Sequence[int],
                         prios: Sequence[float], out_sel: Sequence[int],
                         dst_gt: GeoTransform, dst_crs: CRS,
                         height: int, width: int, method: str = "near",
                         offset: float = 0.0, scale: float = 0.0,
                         clip: float = 0.0, colour_scale: int = 0,
                         auto: bool = True, cache=None):
        """Channel-packed RGB fast path: when the request's granules
        fall into sets of three, one per output band on one grid (the
        Sentinel-2 true-colour shape: a granule's band rasters; several
        such sets where the tile lies on granules' overlap),
        `ops.warp.render_rgba_ctrl` takes the band scenes as the scene
        cache holds them and renders the PNG-ready (H, W, 4) RGBA tile
        in one dispatch, computing warp indices once a set for all
        three bands.  Returns a device uint8 (H, W, 4) or None (caller
        falls back to the per-band path).  Sets in several CRSs (a tile
        across a UTM zone line) are one dispatch too, each set warped on
        its own CRS's control grid (`_ctrl_sets`)."""
        if len(out_sel) != 3 or sorted(out_sel) != [0, 1, 2]:
            return None
        if any(g.geo_loc for g in granules):
            return None
        from .scene_cache import default_scene_cache
        cache = cache or default_scene_cache
        rgba_bbox = dst_gt.bbox(width, height)
        # one cache level a pixel size, the first granule's of that size
        # picks it, so that a set's bands of one grid share a level
        strides: Dict[tuple, float] = {}
        scenes = []
        for g in granules:
            size = (abs(g.geo_transform[1]), abs(g.geo_transform[5]))
            if size not in strides:
                strides[size] = self._granule_stride(g, dst_gt, dst_crs,
                                                     height, width)
            s = cache.get(g, strides[size], dst_bbox=rgba_bbox,
                          dst_crs=dst_crs)
            if s is None:
                return None
            scenes.append(s)
        # out_sel maps expression order -> ns id: channel k of a set
        # comes from its granule whose ns id equals out_sel[k]
        made = _grid_sets(scenes, ns_ids, 3, out_sel)
        self._note_grids(made, scenes)
        if made is None:
            return None
        sets, grid_of = made
        # a control grid a CRS the sets lie in (granules either side of
        # a UTM zone line), each set's rows on its own CRS's origin
        origins, crs_of = _set_crs(scenes, sets)
        cx, cy, ctrl_dev, step = self._ctrl_sets(dst_gt, dst_crs, height,
                                                 width, origins)
        b = _band_sets(scenes, sets, grid_of, prios,
                       _rows_on(scenes, origins), cx, cy, crs_of)
        from ..ops.warp import render_rgba_ctrl
        self._count(_sets_leg("render_rgba", b), (b.key, b.win))
        self._note_win(b.win)
        sp = np.array([offset, scale, clip], np.float32)
        return _prefetch(render_rgba_ctrl(
            b.bands, ctrl_dev, jnp.asarray(b.params), jnp.asarray(b.prios),
            jnp.asarray(sp), method, (height, width), step, auto,
            colour_scale, win=b.win, win0=_dev_win0(b.win0),
            **_grids_arg(b)))

    def _note_paged(self, engaged: bool) -> None:
        with self._lock:
            if engaged:
                self.paged_engaged += 1
            else:
                self.paged_declined += 1

    def _paged_from_group(self, group: "SceneGroup", n_pad: int,
                          lane_union: bool = False):
        """Page tables + 16-wide kernel params for one scene group, or
        None when the paged path can't serve it — page budget exceeded,
        pool full of pinned pages, or the page block over VMEM — and
        the caller keeps the bucketed dispatch.

        Returns (pool, tables (T, S) int32, params16 (T, 16) f32).
        Page coverage per granule comes from the SAME
        `_granule_bounds` margins the bucketed window uses, so both
        paths gather identical taps; table slots come back PINNED and
        the caller must `pool.unpin(tables)` once its dispatch is
        enqueued.  ``lane_union`` (expression lanes) merges the
        per-granule page rects across the lane's bands
        (`autoplan.union_lane_spans`) so every band row shares one
        window shape — widened taps stay correct because off-window
        coords are oob-poisoned before the rebase."""
        from ..ops.paged import page_slots, paged_vmem_ok
        from .pages import default_page_pool
        ctrl, gs, params64 = group.ctrl, group.scenes, group.params64
        pool = default_page_pool()
        if gs:
            # mesh per-chip placement (GSKY_MESH_PLACE=1): the group's
            # pages stage into the pool on the chip that owns its lead
            # scene; wave groups key on the pool object, so per-chip
            # groups dispatch concurrently on their owning chips
            try:
                from ..mesh.pools import staging_pool
                chip_pool = staging_pool(int(gs[0].serial))
            except Exception:   # pragma: no cover - mesh optional
                chip_pool = None
            if chip_pool is not None:
                pool = chip_pool
        pr, pc = pool.page_rows, pool.page_cols
        cx = np.asarray(ctrl[0], np.float64)
        cy = np.asarray(ctrl[1], np.float64)
        T = int(params64.shape[0])
        spans = []
        maxnpg = 1
        cap = page_slots()
        for k in range(T):
            p = params64[k]
            if p[10] < 0 or k >= len(gs):
                spans.append(None)      # batch-padding row
                continue
            made = _granule_bounds(p, cx, cy)
            if made is None:
                spans.append(None)      # nothing to gather
                continue
            r_lo, r_hi, c_lo, c_hi = made
            dev = gs[k].dev
            bh, bw = int(dev.shape[0]), int(dev.shape[1])
            i0 = max(0, r_lo) // pr
            i1 = min(-(-bh // pr) - 1, r_hi // pr)
            j0 = max(0, c_lo) // pc
            j1 = min(-(-bw // pc) - 1, c_hi // pc)
            if i1 < i0 or j1 < j0:
                spans.append(None)      # footprint entirely off-scene
                continue
            npg = (i1 - i0 + 1) * (j1 - j0 + 1)
            if npg > cap:
                return None
            maxnpg = max(maxnpg, npg)
            spans.append((i0, i1, j0, j1))
        if lane_union:
            from .autoplan import union_lane_spans
            spans, maxnpg = union_lane_spans(spans, cap, maxnpg)
        S = _bucket_pow2(maxnpg)
        if not paged_vmem_ok(S, n_pad, pr, pc):
            return None
        tables = np.zeros((T, S), np.int32)
        params16 = np.zeros((T, PAGED_PARAMS_W), np.float32)
        params16[:, :11] = params64[:, :11].astype(np.float32)
        pinned = []
        for k, span in enumerate(spans):
            if span is None:
                # zero-extent row (slots 13/14 stay 0): every tap is
                # out of window, exactly a bucketed all-masked granule
                continue
            i0, i1, j0, j1 = span
            s = gs[k]
            slots = pool.table_for(s.dev, s.serial, i0, i1, j0, j1)
            if slots is None:
                for t in pinned:
                    pool.unpin(t)
                return None
            pinned.append(slots)
            tables[k, :slots.size] = slots
            params16[k, 11] = i0 * pr
            params16[k, 12] = j0 * pc
            params16[k, 13] = (i1 - i0 + 1) * pr
            params16[k, 14] = (j1 - j0 + 1) * pc
            params16[k, 15] = j1 - j0 + 1
        return pool, tables, params16

    def _scene_inputs(self, granules, ns_ids, prios, dst_gt, dst_crs,
                      height, width, cache=None, stacked=True):
        """The one `SceneGroup` of a uniform granule set; None when the
        set is not uniform (the byte fast paths then fall back)."""
        groups = self._scene_groups(granules, ns_ids, prios, dst_gt,
                                    dst_crs, height, width, cache, stacked)
        if groups is None or len(groups) != 1:
            return None
        return groups[0]

    def _geoloc_ctrl(self, g, dst_gt: GeoTransform, dst_crs: CRS,
                     height: int, width: int):
        """Control grid for a curvilinear granule: dst ctrl points
        projected to the geolocation CRS, then inverted through the
        geolocation arrays to fractional source PIXEL coords
        (`geo.geoloc.GeolocGrid`) — the kernels consume them with an
        identity affine, exactly like projected grids.  None when the
        geoloc arrays can't be loaded."""
        from ..geo.crs import parse_crs
        from ..geo.geoloc import load_geoloc_grid
        grid = load_geoloc_grid(g.path, g.geo_loc)
        if grid is None:
            return None
        try:
            gl_crs = parse_crs(g.geo_loc.get("srs") or "EPSG:4326")
        except ValueError:
            return None
        key = ("glctrl", g.path, g.geo_loc.get("x_var"),
               dst_gt.to_gdal(), dst_crs, height, width)
        hit = self._geo_cache_get(key)
        if hit is not None:
            return hit
        step = 16
        while True:
            sx, sy, step = self._ctrl_geo_coords(dst_gt, dst_crs, height,
                                                 width, gl_crs, step)
            col, row = grid.invert(sx, sy)
            # the inversion leg needs its own 0.125-px validation (the
            # projection leg's _ctrl_err_px can't see it): compare the
            # on-device bilinear reconstruction at ctrl-cell midpoints
            # against exact inversion there, halving the step for
            # strongly curved swaths
            if step <= 2:
                break
            gh, gw = sx.shape
            if gh < 2 or gw < 2:
                break
            c = (np.arange(gw - 1, dtype=np.float64) + 0.5) * step + 0.5
            r = (np.arange(gh - 1, dtype=np.float64) + 0.5) * step + 0.5
            C, R = np.meshgrid(c, r)
            mx, my = dst_gt.pixel_to_geo(C, R, np)
            ex, ey = dst_crs.transform_to(gl_crs, mx, my, np)
            ecol, erow = grid.invert(np.asarray(ex), np.asarray(ey))
            icol = 0.25 * (col[:-1, :-1] + col[:-1, 1:] + col[1:, :-1]
                           + col[1:, 1:])
            irow = 0.25 * (row[:-1, :-1] + row[:-1, 1:] + row[1:, :-1]
                           + row[1:, 1:])
            with np.errstate(invalid="ignore"):
                err = np.hypot(ecol - icol, erow - irow)
            if not err.size or np.all(np.isnan(err)) \
                    or float(np.nanmax(err)) <= 0.125:
                break
            step //= 2
        out = (np.stack([col, row]).astype(np.float32), step)
        self._geo_cache_put(key, out)
        return out

    def _scene_groups(self, granules, ns_ids, prios, dst_gt, dst_crs,
                      height, width, cache=None, stacked=True,
                      windowed=True):
        """Device inputs for the fused scene kernels, grouped by
        (source CRS, bucket shape, dtype) — curvilinear granules group
        by their geolocation arrays instead: one `SceneGroup` each;
        multi-group sets (granules spanning UTM zones, or mixing regular
        and curvilinear grids) combine via the scored kernels.  None
        when any scene is uncacheable.

        The group's ``stack`` is one (B, bh, bw) array, a copy of its
        scenes that the scene cache keeps and charges to its budget
        (`SceneCache.stack`), or with ``stacked=False`` the tuple of the
        B scene arrays themselves, for a kernel that stacks only their
        gather windows (its `win0` is then (B, 2), an origin a scene:
        `_gather_windows`).  B is a power of two (bounded jit variants);
        the filling repeats the first scene, which costs the tuple
        nothing.  ``windowed=False`` leaves an unstacked group without a
        window (its caller makes its own)."""
        from .scene_cache import default_scene_cache
        cache = cache or default_scene_cache
        scenes = []
        grp_bbox = dst_gt.bbox(width, height)
        for g in granules:
            stride = 1.0 if g.geo_loc else self._granule_stride(
                g, dst_gt, dst_crs, height, width)
            s = cache.get(g, stride, dst_bbox=grp_bbox, dst_crs=dst_crs)
            if s is None:
                return None
            scenes.append(s)
        by_key: Dict[tuple, List[int]] = {}
        for i, s in enumerate(scenes):
            g = granules[i]
            if g.geo_loc:
                key = ("gl", g.path, g.geo_loc.get("x_var"),
                       g.geo_loc.get("y_var"), s.bucket, str(s.dtype))
            else:
                key = (s.crs.name(), s.bucket, str(s.dtype))
            by_key.setdefault(key, []).append(i)

        groups = []
        for gkey, idxs in by_key.items():
            gs = [scenes[i] for i in idxs]
            s0 = gs[0]
            is_gl = gkey[0] == "gl"
            if is_gl:
                made = self._geoloc_ctrl(granules[idxs[0]], dst_gt,
                                         dst_crs, height, width)
                if made is None:
                    return None
                ctrl, step = made
                gl0 = granules[idxs[0]]
                dkey = ("ctrldev", "gl", gl0.path,
                        gl0.geo_loc.get("x_var"), gl0.geo_loc.get("y_var"),
                        dst_gt.to_gdal(), dst_crs, height, width)
            else:
                sx, sy, step = self._ctrl_geo_coords(
                    dst_gt, dst_crs, height, width, s0.crs, 16)
                ox, oy = s0.gt.x0, s0.gt.y0
                ctrl = np.stack([sx - ox, sy - oy]).astype(np.float32)
                dkey = ("ctrldev", dst_gt.to_gdal(), dst_crs, height,
                        width, s0.crs, ox, oy)
            # the ~2 KB ctrl grid re-uploads on every render otherwise;
            # tile servers see heavy repeats, so keep the DEVICE copy in
            # the same LRU as the host grids.  The HOST array stays the
            # group's ctrl: the wave scheduler np.stacks ctrl grids, and
            # a device array there would force a sync + download per
            # queued tile
            ctrl_dev = self._geo_cache_get(dkey)
            if ctrl_dev is None:
                ctrl_dev = jnp.asarray(ctrl)
                self._geo_cache_put(dkey, ctrl_dev)

            B = _bucket_pow2(len(gs))
            params = np.zeros((B, 11), np.float64)
            params[:, 10] = -1.0
            for k, (i, s) in enumerate(zip(idxs, gs)):
                if is_gl:
                    # ctrl already carries pixel coords: identity affine
                    params[k, :6] = (0.0, 1.0, 0.0, 0.0, 0.0, 1.0)
                else:
                    params[k, :6] = _inv_gt_params(s.gt, ox, oy)
                params[k, 6] = s.height
                params[k, 7] = s.width
                params[k, 8] = s.nodata
                params[k, 9] = prios[i]
                params[k, 10] = ns_ids[i]

            skey = tuple(s.serial for s in gs) + (B,)
            devs = tuple(s.dev for s in gs) + (s0.dev,) * (B - len(gs))
            win = win0 = None
            if _window_mode() and not stacked and windowed:
                made_w = _gather_windows(
                    params, np.asarray(ctrl[0], np.float64),
                    np.asarray(ctrl[1], np.float64), *s0.bucket)
                if made_w is not None:
                    win, win0 = made_w
            group = SceneGroup(
                stack=devs, ctrl=ctrl, ctrl_dev=ctrl_dev,
                params=params.astype(np.float32), params64=params,
                step=step, skey=skey, win=win, win0=win0, scenes=gs,
                curvilinear=is_gl)
            groups.append(self._stacked(group, cache) if stacked
                          else group)
        return groups

    @staticmethod
    def _stacked(group: "SceneGroup", cache) -> "SceneGroup":
        """The group with its scenes stacked: ``stack`` the (B, bh, bw)
        copy the scene cache keeps and charges to its budget, and one
        gather window over the whole stack."""
        from .scene_cache import default_scene_cache
        devs = group.stack
        win = win0 = None
        if _window_mode():
            made_w = _gather_window(
                group.params64, np.asarray(group.ctrl[0], np.float64),
                np.asarray(group.ctrl[1], np.float64), *devs[0].shape)
            if made_w is not None:
                win, win0 = made_w
        return group._replace(
            stack=(cache or default_scene_cache).stack(
                group.skey, lambda: jnp.stack(devs)),
            win=win, win0=win0)


# module-level default executor (compile cache shared across requests)
default_executor = WarpExecutor()
