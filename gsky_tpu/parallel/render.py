"""SPMD render + drill steps over a (granule, x) device mesh.

One step is the full GetMap compute path — batched warp gather, temporal
mosaic, band-expression eval, auto min-max byte scaling, palette LUT —
expressed as a `shard_map` so it runs unchanged on 1..N chips:

  * the granule/time stack is sharded over the ``granule`` mesh axis
    (each chip warps + locally mosaics its granules, then the per-chip
    partial canvases are `all_gather`'d and combined in priority order);
  * the output width is sharded over the ``x`` axis (each chip renders a
    column strip; auto min-max scaling needs the global extrema, obtained
    with `pmin`/`pmax` over ``x``).

This is the TPU-native replacement for the reference's machine-level
fan-outs: per-granule worker RPCs (`processor/tile_grpc.go:219-242`) and
WCS tile sharding across OWS nodes (`ows.go:835-872`) — collectives over
ICI instead of protobuf over TCP.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..ops.scale import auto_byte_scale
from ..ops.warp import _METHODS
from .mesh import AXIS_GRANULE, AXIS_X


def _combine_priority(partials, pvalids):
    """Sequentially combine per-shard mosaic partials (G, ..., H, W) —
    shard 0 holds the newest granules, so first-valid over the shard axis
    preserves newest-wins semantics (`processor/tile_merger.go:281-312`)."""
    idx = jnp.argmax(pvalids, axis=0)
    out = jnp.take_along_axis(partials, idx[None], axis=0)[0]
    ok = jnp.any(pvalids, axis=0)
    return out, ok


def _combine_priority_ring(part, pok, axis_name: str, axis_size: int):
    """Ring-reduce the shard partials instead of `all_gather`ing them:
    each chip keeps one partial canvas + the shard rank of its
    contributing granule per pixel, and in ``G-1`` `ppermute` steps
    folds in its neighbour's candidate, keeping the lower rank (= newer
    granule).  Memory is O(1) in the number of shards where the gather
    variant materialises the full (G, ..., h, w) stack — the difference
    between fitting and not fitting very long granule stacks in HBM.
    The collectives ride ICI neighbour links, the cheapest pattern on a
    TPU torus (cf. ring collectives in the scaling playbook).
    """
    me = jax.lax.axis_index(axis_name)
    inf = jnp.float32(jnp.inf)
    rank = jnp.where(pok, me.astype(jnp.float32), inf)
    data, best = part, rank
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    cand_d, cand_r = part, rank
    for _ in range(axis_size - 1):
        cand_d = jax.lax.ppermute(cand_d, axis_name, perm)
        cand_r = jax.lax.ppermute(cand_r, axis_name, perm)
        take = cand_r < best
        data = jnp.where(take, cand_d, data)
        best = jnp.where(take, cand_r, best)
    return data, best < inf


def make_sharded_render(mesh: Mesh, method: str = "near",
                        expr: Optional[Callable] = None,
                        combine: str = "gather") -> Callable:
    """Build a jitted SPMD render step.

    The returned fn has signature
        step(src, valid, rows, cols, lut) -> rgba
    with
        src   (T, NS, H, W)  f32  source windows, T in priority order
                                  (newest first), NS = band namespaces
        valid (T, NS, H, W)  bool source nodata masks
        rows  (T, h, w)      f32  fractional src row coords per granule
        cols  (T, h, w)      f32  fractional src col coords per granule
        lut   (256, 4)       u8   colour palette
    returning rgba (h, w, 4) uint8.

    ``expr(bands, valids) -> (data, ok)`` reduces the NS canvases to the
    styled single band (default: first namespace pass-through).

    Shardings: T over the ``granule`` mesh axis, w over ``x``.  T and w
    must divide the respective mesh dimensions.

    ``combine``: how per-shard mosaic partials merge across the granule
    axis — "gather" (`all_gather`, one hop, O(G) memory) or "ring"
    (`ppermute` ring reduction, G-1 neighbour hops, O(1) memory; use for
    granule stacks whose gathered partials would not fit HBM).
    """
    if combine not in ("gather", "ring"):
        raise ValueError(f"combine must be 'gather' or 'ring': {combine}")
    gather = _METHODS[method]

    if expr is None:
        def expr(bands, valids):
            return bands[0], valids[0]

    def _local(src, valid, rows, cols, lut):
        # src (Tl, NS, H, W); rows/cols (Tl, h, wl)
        warp = jax.vmap(  # over granules
            jax.vmap(gather, in_axes=(0, 0, None, None)),  # over namespaces
            in_axes=(0, 0, 0, 0))
        out, ok = warp(src, valid, rows, cols)      # (Tl, NS, h, wl)
        # local newest-wins mosaic over this shard's granules
        idx = jnp.argmax(ok, axis=0)
        part = jnp.take_along_axis(out, idx[None], axis=0)[0]   # (NS, h, wl)
        pok = jnp.any(ok, axis=0)
        # combine shard partials: shard g holds granules [g*Tl, (g+1)*Tl)
        # of the priority-ordered stack, so shard order == priority order
        if combine == "ring":
            canvas, cok = _combine_priority_ring(
                part, pok, AXIS_GRANULE,
                mesh.shape[AXIS_GRANULE])                       # (NS, h, wl)
        else:
            parts = jax.lax.all_gather(part, AXIS_GRANULE)      # (G, NS, h, wl)
            poks = jax.lax.all_gather(pok, AXIS_GRANULE)
            canvas, cok = _combine_priority(parts, poks)        # (NS, h, wl)
        data, dok = expr(canvas, cok)                           # (h, wl)
        # auto min-max scaling needs global extrema across the x strips
        big = jnp.float32(3.4e38)
        mn = jax.lax.pmin(jnp.min(jnp.where(dok, data, big)), AXIS_X)
        mx = jax.lax.pmax(jnp.max(jnp.where(dok, data, -big)), AXIS_X)
        anyv = jax.lax.pmax(jnp.any(dok).astype(jnp.int32), AXIS_X) > 0
        byte = auto_byte_scale(data, dok, mn, mx, anyv)
        rgba = lut[byte.astype(jnp.int32)]                      # (h, wl, 4)
        return rgba

    step = shard_map(
        _local, mesh=mesh,
        in_specs=(P(AXIS_GRANULE, None, None, None),
                  P(AXIS_GRANULE, None, None, None),
                  P(AXIS_GRANULE, None, AXIS_X),
                  P(AXIS_GRANULE, None, AXIS_X),
                  P()),
        out_specs=P(None, AXIS_X, None),
        check_vma=False)
    return jax.jit(step)


def make_sharded_render_padded(mesh: Mesh, method: str = "near",
                               expr: Optional[Callable] = None,
                               combine: str = "gather") -> Callable:
    """`make_sharded_render` for inputs whose granule count / width do
    NOT divide the mesh: the granule axis pads with invalid layers (the
    newest-wins combine ignores them — same trick the single-device
    mosaic uses for its pow2 buckets) and the width pads then crops.
    Real granule stacks rarely arrive in mesh-divisible sizes, so this
    is the entry production callers want; the raw step stays available
    for pre-sized inputs."""
    step = make_sharded_render(mesh, method, expr, combine)
    ng = mesh.shape[AXIS_GRANULE]
    nx = mesh.shape[AXIS_X]

    def padded(src, valid, rows, cols, lut):
        src = jnp.asarray(src)
        valid = jnp.asarray(valid)
        rows = jnp.asarray(rows)
        cols = jnp.asarray(cols)
        T = src.shape[0]
        w = rows.shape[-1]
        Tp = -(-T // ng) * ng
        wp = -(-w // nx) * nx
        if Tp != T:
            padT = [(0, Tp - T)] + [(0, 0)] * (src.ndim - 1)
            src = jnp.pad(src, padT)
            valid = jnp.pad(valid, padT, constant_values=False)
            padR = [(0, Tp - T)] + [(0, 0)] * (rows.ndim - 1)
            # out-of-range coords: padded granules sample nothing even
            # before their all-False validity is consulted
            rows = jnp.pad(rows, padR, constant_values=-1e6)
            cols = jnp.pad(cols, padR, constant_values=-1e6)
        if wp != w:
            padW = [(0, 0)] * (rows.ndim - 1) + [(0, wp - w)]
            rows = jnp.pad(rows, padW, constant_values=-1e6)
            cols = jnp.pad(cols, padW, constant_values=-1e6)
        out = step(src, valid, rows, cols, jnp.asarray(lut))
        return out[:, :w] if wp != w else out

    return padded


def make_sharded_drill(mesh: Mesh) -> Callable:
    """Build a jitted SPMD drill step: per-timestep masked means over a
    polygon mask (`worker/gdalprocess/drill.go:128-220`), with the pixel
    sums reduced across the spatially-sharded strips by `psum`.

        step(data, valid, mask) -> (means, counts)
        data  (T, H, W) f32   sharded: T over granule, W over x
        valid (T, H, W) bool
        mask  (H, W)    bool  polygon rasterisation, sharded over x
    returns means (T,) f32 (NaN where empty), counts (T,) f32.
    """

    def _local(data, valid, mask):
        m = valid & mask[None]
        cnt = jax.lax.psum(jnp.sum(m, axis=(1, 2)).astype(jnp.float32),
                           AXIS_X)
        tot = jax.lax.psum(jnp.sum(jnp.where(m, data, 0.0), axis=(1, 2)),
                           AXIS_X)
        means = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), jnp.nan)
        return means, cnt

    step = shard_map(
        _local, mesh=mesh,
        in_specs=(P(AXIS_GRANULE, None, AXIS_X),
                  P(AXIS_GRANULE, None, AXIS_X),
                  P(None, AXIS_X)),
        out_specs=(P(AXIS_GRANULE), P(AXIS_GRANULE)),
        check_vma=False)
    return jax.jit(step)
