"""SPMD execution of the PRODUCTION fused render / drill kernels.

`render.py` carries the reference-shaped SPMD steps (explicit src
windows + coordinate grids); this module shards the kernels the real
pipeline dispatches — the ctrl-grid scene renders of `ops.warp` and the
drill reductions of `ops.drill` — so `TilePipeline`, the WCS coverage
path and the drill pipeline run unchanged on 1..N chips (enable with
``GSKY_SPMD=1``; `pipeline.executor` and `pipeline.drill` route here).

Sharding layout (the reference's machine-level fan-outs mapped onto a
device mesh, SURVEY §2.8 P3/P5/P6):

  * granule/time axis -> ``granule`` mesh axis: each chip warps and
    locally mosaics its slice of the priority-ordered stack, then the
    per-chip partials combine by per-pixel priority (`all_gather` over
    ICI — mosaic priorities are strictly unique, so the cross-shard
    winner equals the single-device winner EXACTLY);
  * output width -> ``x`` mesh axis: each chip renders a column strip,
    reconstructing its strip of the dense coordinate grid from the
    replicated ~2 KB ctrl points (`ops.warp._bilerp_grid(x0=...)`);
    auto min-max scaling takes `pmin`/`pmax` over the strips (min/max
    are exact, so again bit-identical to the single-device reduction);
  * drill bands -> ``granule`` axis, pixels -> ``x`` axis with a `psum`
    (floating-point partial-sum order differs from the single-device
    sum, so drill means agree to ~1e-6 relative, not bitwise).

Determinism: winner selection and min-max extrema are exact, so the
sharded byte tile matches the single-device tile except where XLA's
FMA contraction of the affine coordinate math differs between the two
compiled programs and flips a floor() at a pixel boundary — measured
at <=1e-4 of pixels, asserted <=1e-3 in tests and the multichip
dryrun.

Inputs arrive as single-device arrays (the scene cache uploads to the
default device); `jax.jit` re-shards them per the `shard_map` in_specs.
On a real multi-chip pod the scene cache would place shards directly
(`jax.device_put` with these shardings) — the compute path is already
shaped for it.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..ops.scale import auto_byte_scale, scale_to_byte
from ..ops.warp import _bilerp_grid, _warp_scenes_scored
from .mesh import AXIS_GRANULE, AXIS_X, make_mesh


def _win0_arr(win0):
    """Replicated window-origin operand: the shard_map'd kernels always
    take it (a (2,) int32; ignored when the build-time ``win`` static is
    None) so one local() shape serves both modes."""
    if win0 is None:
        win0 = np.zeros(2, np.int32)
    return jnp.asarray(np.asarray(win0, np.int32))


def spmd_enabled() -> bool:
    """GSKY_SPMD=1 and more than one device: the pipelines then route
    their fused dispatches through the mesh."""
    if os.environ.get("GSKY_SPMD", "0") != "1":
        return False
    try:
        return len(jax.devices()) > 1
    except Exception:  # pragma: no cover
        return False


class SpmdRenderer:
    """Mesh-holding wrapper around the sharded production kernels.
    One instance (module default below) caches the jitted steps per
    static configuration, exactly like jax's own jit cache."""

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.ng = self.mesh.shape[AXIS_GRANULE]
        self.nx = self.mesh.shape[AXIS_X]
        self._fns = {}
        self._lock = threading.Lock()

    # -- internals ---------------------------------------------------------

    def _get(self, key, builder):
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                fn = builder()
                self._fns[key] = fn
            return fn

    def _pad_inputs(self, stack, params, out_w: int):
        """Pad the granule axis to the mesh and compute the padded
        width.  Padding granules carry ns_id -1, which
        `_warp_scenes_scored` treats as members of no namespace."""
        B = stack.shape[0]
        Bp = -(-B // self.ng) * self.ng
        if Bp != B:
            stack = jnp.pad(jnp.asarray(stack),
                            [(0, Bp - B), (0, 0), (0, 0)])
            pad_params = np.zeros((Bp - B, 11), np.float32)
            pad_params[:, 10] = -1.0
            pad_params[:, 6:8] = 1.0
            params = np.concatenate(
                [np.asarray(params, np.float32), pad_params])
        wp = -(-out_w // self.nx) * self.nx
        return stack, np.asarray(params, np.float32), wp

    def _build_mosaic(self, method: str, n_ns: int,
                      out_hw: Tuple[int, int], step: int, wp: int,
                      win=None):
        """Sharded `warp_scenes_ctrl_scored`: (canv (n_ns, h, w) f32,
        best (n_ns, h, w) f32) — the WCS / modular-path carrier."""
        h, w_true = out_hw
        wl = wp // self.nx
        mesh = self.mesh

        def local(stack, ctrl, params, win0):
            x0 = jax.lax.axis_index(AXIS_X) * wl
            sx = _bilerp_grid(ctrl[0], h, wl, step, x0=x0)
            sy = _bilerp_grid(ctrl[1], h, wl, step, x0=x0)
            # pixels past the true width exist only as mesh padding;
            # poison their coords so no granule contributes
            xg = x0 + jnp.arange(wl)
            sx = jnp.where(xg[None, :] < w_true, sx, jnp.nan)
            canv, best = _warp_scenes_scored(stack, sx, sy, params,
                                             method, n_ns,
                                             win=win, win0=win0)
            bests = jax.lax.all_gather(best, AXIS_GRANULE)
            canvs = jax.lax.all_gather(canv, AXIS_GRANULE)
            idx = jnp.argmax(bests, axis=0)
            canv = jnp.take_along_axis(canvs, idx[None], axis=0)[0]
            best = jnp.max(bests, axis=0)
            return jnp.where(best > -jnp.inf, canv, 0.0), best

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS_GRANULE, None, None), P(), P(AXIS_GRANULE),
                      P()),
            out_specs=(P(None, None, AXIS_X), P(None, None, AXIS_X)),
            check_vma=False)
        return jax.jit(fn)

    # -- production entries ------------------------------------------------

    def mosaic_scored(self, stack, ctrl, params, method: str, n_ns: int,
                      out_hw: Tuple[int, int], step: int,
                      win=None, win0=None):
        """Sharded equivalent of `ops.warp.warp_scenes_ctrl_scored`:
        returns (canvases (n_ns, h, w) f32, best (n_ns, h, w) f32).
        win/win0: the executor's gather window (replicated across the
        mesh; each shard slices the same window from its granule
        shard)."""
        h, w = out_hw
        stack, params, wp = self._pad_inputs(stack, params, w)
        key = ("mosaic", method, n_ns, out_hw, step, wp,
               stack.shape[0], win)
        fn = self._get(key, lambda: self._build_mosaic(
            method, n_ns, out_hw, step, wp, win))
        canv, best = fn(jnp.asarray(stack), jnp.asarray(ctrl),
                        jnp.asarray(params), _win0_arr(win0))
        if wp != w:
            canv = canv[..., :w]
            best = best[..., :w]
        return canv, best

    def _build_composite(self, method: str, n_ns: int,
                         out_hw: Tuple[int, int], step: int, wp: int,
                         auto: bool, colour_scale: int, win=None):
        """Sharded `render_scenes_ctrl`: the whole GetMap tile —
        warp -> mosaic -> composite -> byte scale — across the mesh."""
        h, w_true = out_hw
        wl = wp // self.nx
        mesh = self.mesh

        def local(stack, ctrl, params, sp, win0):
            x0 = jax.lax.axis_index(AXIS_X) * wl
            sx = _bilerp_grid(ctrl[0], h, wl, step, x0=x0)
            sy = _bilerp_grid(ctrl[1], h, wl, step, x0=x0)
            xg = x0 + jnp.arange(wl)
            sx = jnp.where(xg[None, :] < w_true, sx, jnp.nan)
            canv, best = _warp_scenes_scored(stack, sx, sy, params,
                                             method, n_ns,
                                             win=win, win0=win0)
            bests = jax.lax.all_gather(best, AXIS_GRANULE)
            canvs = jax.lax.all_gather(canv, AXIS_GRANULE)
            idx = jnp.argmax(bests, axis=0)
            canv = jnp.take_along_axis(canvs, idx[None], axis=0)[0]
            vals = jnp.max(bests, axis=0) > -jnp.inf
            # first-valid composite across namespaces (same order as
            # the single-device `_render_scenes_core`)
            nidx = jnp.argmax(vals, axis=0)
            data = jnp.take_along_axis(canv, nidx[None], axis=0)[0]
            ok = jnp.any(vals, axis=0)
            if auto:
                if colour_scale == 1:
                    logged = jnp.log10(data)
                    bad = ~jnp.isfinite(logged)
                    data = jnp.where(bad, 0.0, logged)
                    ok = ok & ~bad
                big = jnp.float32(3.4e38)
                mn = jax.lax.pmin(
                    jnp.min(jnp.where(ok, data, big)), AXIS_X)
                mx = jax.lax.pmax(
                    jnp.max(jnp.where(ok, data, -big)), AXIS_X)
                anyv = jax.lax.pmax(
                    jnp.any(ok).astype(jnp.int32), AXIS_X) > 0
                return auto_byte_scale(data, ok, mn, mx, anyv)
            return scale_to_byte(data, ok, sp[0], sp[1], sp[2],
                                 colour_scale=colour_scale, auto=False)

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS_GRANULE, None, None), P(), P(AXIS_GRANULE),
                      P(), P()),
            out_specs=P(None, AXIS_X),
            check_vma=False)
        return jax.jit(fn)

    def render_composite(self, stack, ctrl, params, scale_params,
                         method: str, n_ns: int,
                         out_hw: Tuple[int, int], step: int, auto: bool,
                         colour_scale: int, win=None, win0=None):
        """Sharded equivalent of `ops.warp.render_scenes_ctrl`: the
        PNG-ready uint8 (h, w) tile (exact winners, exact extrema; see
        the module determinism note)."""
        h, w = out_hw
        stack, params, wp = self._pad_inputs(stack, params, w)
        key = ("composite", method, n_ns, out_hw, step, wp,
               stack.shape[0], auto, colour_scale, win)
        fn = self._get(key, lambda: self._build_composite(
            method, n_ns, out_hw, step, wp, auto, colour_scale, win))
        out = fn(jnp.asarray(stack), jnp.asarray(ctrl),
                 jnp.asarray(params), jnp.asarray(scale_params),
                 _win0_arr(win0))
        return out[:, :w] if wp != w else out

    def _build_stats(self, pixel_count: bool):
        mesh = self.mesh

        def local(data, valid, clips):
            # data (Bl, Nl); psum over the pixel shards
            d = data.astype(jnp.float32)
            inclip = valid & (d >= clips[0]) & (d <= clips[1])
            n_inclip = jax.lax.psum(
                jnp.sum(inclip, axis=-1), AXIS_X)
            if pixel_count:
                total = jax.lax.psum(jnp.sum(valid, axis=-1), AXIS_X)
                value = jnp.where(total > 0,
                                  n_inclip / jnp.maximum(total, 1), 0.0)
                return value.astype(jnp.float32), total.astype(jnp.int32)
            s = jax.lax.psum(
                jnp.sum(jnp.where(inclip, d, 0.0), axis=-1), AXIS_X)
            value = jnp.where(n_inclip > 0,
                              s / jnp.maximum(n_inclip, 1), 0.0)
            return value.astype(jnp.float32), n_inclip.astype(jnp.int32)

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS_GRANULE, AXIS_X), P(AXIS_GRANULE, AXIS_X),
                      P()),
            out_specs=(P(AXIS_GRANULE), P(AXIS_GRANULE)),
            check_vma=False)
        return jax.jit(fn)

    def masked_stats(self, dataf, validf, clip_lower: float,
                     clip_upper: float, pixel_count: bool = False):
        """Sharded drill reductions over (B, N) window data: bands over
        ``granule``, pixels over ``x`` with a `psum` (SURVEY §2.8 P7 on
        the mesh).  Values match the single-device reduction to f32
        partial-sum reassociation (~1e-6 rel); counts are exact."""
        B, N = dataf.shape
        Bp = -(-B // self.ng) * self.ng
        Np = -(-N // self.nx) * self.nx
        if Bp != B or Np != N:
            dataf = jnp.pad(jnp.asarray(dataf),
                            [(0, Bp - B), (0, Np - N)])
            validf = jnp.pad(jnp.asarray(validf),
                             [(0, Bp - B), (0, Np - N)],
                             constant_values=False)
        key = ("stats", pixel_count)
        fn = self._get(key, lambda: self._build_stats(pixel_count))
        clips = jnp.asarray(np.array([clip_lower, clip_upper],
                                     np.float32))
        v, c = fn(jnp.asarray(dataf), jnp.asarray(validf), clips)
        return v[:B], c[:B]


def default_spmd() -> Optional[SpmdRenderer]:
    """Process-wide renderer over the full device mesh when SPMD is
    enabled, else None (callers fall back to single-device paths).

    COMPAT SHIM (PR 14): singleton ownership moved to the mesh
    subsystem — `gsky_tpu.mesh.dispatch` holds the one `SpmdRenderer`
    that both the old ``GSKY_SPMD`` direct-dispatch routing and the
    mesh ``x`` layout share, so exactly one sharded code path (and one
    program cache) exists.  This alias delegates; new code should call
    `gsky_tpu.mesh.compat_spmd` (pipeline/executor and pipeline/drill
    already do)."""
    from ..mesh.dispatch import compat_spmd
    return compat_spmd()
