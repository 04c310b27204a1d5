"""The control grid reaches a pixel without a gather.

`ops.warp._bilerp_grid` upsamples the (gh, gw) control grid to the dense
(h, w) coordinate grid of every ctrl kernel.  Until PR 38 it indexed
``ctrl[y0, x0]`` with per-pixel index arrays: four XLA gathers of h x w
elements a grid, eight a kernel, and a TPU gather costs by the element
gathered whatever it reads (66 of 190 ms of a 1024 x 1024 export tile).
The grid is regular, so the helper now repeats each cell's corners by a
broadcast and a reshape; the arithmetic is the old one, operand for
operand.  The gather form lives on here, as the reference the helper
is held to BIT FOR BIT, and the lowered programs are held to their tap
gathers so that the eight cannot come back unseen.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsky_tpu.ops.expr import compile_expr, fingerprint
from gsky_tpu.ops.warp import (_bilerp_grid, render_expr_ctrl,
                               render_rgba_ctrl, render_scenes_ctrl,
                               warp_scenes_ctrl_scored)


def _bilerp_grid_gather(ctrl, h: int, w: int, step: int, x0=0):
    """`_bilerp_grid` as it stood before PR 38: the reference."""
    gh, gw = ctrl.shape
    yy = jnp.arange(h, dtype=jnp.float32)[:, None] / step
    xx = (x0 + jnp.arange(w, dtype=jnp.float32)[None, :]) / step
    y0 = jnp.clip(jnp.floor(yy).astype(jnp.int32), 0, gh - 2)
    x0 = jnp.clip(jnp.floor(xx).astype(jnp.int32), 0, gw - 2)
    ty = yy - y0
    tx = xx - x0
    c00 = ctrl[y0, x0]
    c10 = ctrl[y0 + 1, x0]
    c01 = ctrl[y0, x0 + 1]
    c11 = ctrl[y0 + 1, x0 + 1]
    return (c00 * (1 - ty) + c10 * ty) * (1 - tx) \
        + (c01 * (1 - ty) + c11 * ty) * tx


def _grid_shape(h, w, step):
    """`executor._ctrl_geo_coords`' grid: nodes at k * step."""
    return (h - 1 + step - 1) // step + 1, (w - 1 + step - 1) // step + 1


def _ctrl(h, w, step, seed=0):
    """Source-CRS coordinates of the size the cells carry (~1e5 m), with
    NaN nodes at an inner cell, on an edge and at the last corner."""
    gh, gw = _grid_shape(h, w, step)
    rng = np.random.default_rng(seed + 1000 * step + h + w)
    cc, rr = np.meshgrid(np.arange(gw) * step * 30.0,
                         np.arange(gh) * step * 30.0)
    c = 1.2e5 + cc + 40.0 * np.sin(rr / 900.0) \
        + rng.normal(0.0, 3.0, cc.shape)
    c[gh // 2, gw // 2] = np.nan
    c[0, gw // 3] = np.nan
    c[-1, -1] = np.nan
    return c.astype(np.float32)


SHAPES = [(256, 256, 16), (256, 256, 8), (256, 256, 4), (256, 256, 2),
          (1024, 1024, 16), (512, 256, 4), (200, 300, 16), (255, 257, 16),
          (257, 255, 8)]
N_STRIPS = 4        # the SPMD render's width shards


def _strips(w):
    """(x0, wl) of each shard of a width padded to the mesh, as
    `parallel/spmd.py::_pad_inputs` cuts it: the last strip may reach
    past the true width and past the grid's last node."""
    wl = -(-w // N_STRIPS)
    return [(k * wl, wl) for k in range(N_STRIPS)]


def _both(h, w, step, mode):
    """[(new, reference)] arrays of one case."""
    ctrl = jnp.asarray(_ctrl(h, w, step))
    if mode == "whole":
        return [(_bilerp_grid(ctrl, h, w, step),
                 _bilerp_grid_gather(ctrl, h, w, step))]
    if mode == "whole_jit":
        return [(jax.jit(lambda c: _bilerp_grid(c, h, w, step))(ctrl),
                 jax.jit(lambda c: _bilerp_grid_gather(c, h, w,
                                                       step))(ctrl))]
    if mode == "strip_int":
        return [(_bilerp_grid(ctrl, h, wl, step, x0=x0),
                 _bilerp_grid_gather(ctrl, h, wl, step, x0=x0))
                for x0, wl in _strips(w)]
    if mode == "strip_traced":
        wl = _strips(w)[0][1]
        new = jax.jit(lambda c, x0: _bilerp_grid(c, h, wl, step, x0=x0))
        old = jax.jit(lambda c, x0: _bilerp_grid_gather(c, h, wl, step,
                                                        x0=x0))
        return [(new(ctrl, jnp.int32(x0)), old(ctrl, jnp.int32(x0)))
                for x0, _ in _strips(w)]
    assert mode == "vmap"
    # `ops/paged.py` upsamples a batch of tiles' grids, (T, 2, gh, gw)
    ctrls = jnp.stack([
        jnp.stack([jnp.asarray(_ctrl(h, w, step, seed=2 * t + k))
                   for k in range(2)]) for t in range(2)])
    return [(jax.vmap(lambda c: _bilerp_grid(c[k], h, w, step))(ctrls),
             jax.vmap(lambda c: _bilerp_grid_gather(c[k], h, w,
                                                    step))(ctrls))
            for k in range(2)]


@pytest.mark.parametrize("mode", ["whole", "whole_jit", "strip_int",
                                  "strip_traced", "vmap"])
@pytest.mark.parametrize("h,w,step", SHAPES)
def test_bit_identical_to_the_gather_form(h, w, step, mode):
    pairs = [(np.asarray(new), np.asarray(old))
             for new, old in _both(h, w, step, mode)]
    for new, old in pairs:
        assert new.shape == old.shape and new.dtype == np.float32
        assert np.array_equal(new, old, equal_nan=True)
    # a NaN node poisons exactly the pixels it poisoned: 0 * NaN is NaN,
    # so the pixel ON the last node must stay in cell gh - 2
    poisoned = np.concatenate([np.isnan(old).ravel() for _, old in pairs])
    assert poisoned.any() and not poisoned.all()


def _count(jaxpr, names):
    """Equations of ``jaxpr`` whose primitive is in ``names``, through
    every nested jaxpr."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, names)
    return n


@pytest.mark.parametrize("x0", [0, 64, "traced"])
def test_helper_holds_no_gather_and_no_dot(x0):
    ctrl = jnp.asarray(_ctrl(256, 256, 16))
    if x0 == "traced":
        jaxpr = jax.make_jaxpr(
            lambda c, x: _bilerp_grid(c, 256, 64, 16, x0=x))(
                ctrl, jnp.int32(64))
    else:
        jaxpr = jax.make_jaxpr(
            lambda c: _bilerp_grid(c, 256, 64, 16, x0=x0))(ctrl)
    assert _count(jaxpr.jaxpr, {"gather", "dot_general", "scatter",
                                "scatter-add"}) == 0
    assert _count(jaxpr.jaxpr, {"broadcast_in_dim"}) > 0
    ref = jax.make_jaxpr(
        lambda c: _bilerp_grid_gather(c, 256, 64, 16))(ctrl)
    assert _count(ref.jaxpr, {"gather"}) == 4      # the counter counts


S = 96


def _scene_inputs(B=1, h=64, w=64, step=16):
    gh, gw = _grid_shape(h, w, step)
    ctrl = np.stack([
        np.linspace(8.0, S - 16.0, gw,
                    dtype=np.float32)[None, :].repeat(gh, 0),
        np.linspace(8.0, S - 16.0, gh,
                    dtype=np.float32)[:, None].repeat(gw, 1)])
    params = np.zeros((B, 11), np.float32)
    for k in range(B):
        params[k] = [0.3 * k, 1.01, 0.02, 0.2 * k, -0.01, 0.99, S, S,
                     -999.0, 10.0 + k, 0.0]
    rng = np.random.default_rng(3)
    stack = rng.uniform(100.0, 3000.0, (B, S, S)).astype(np.float32)
    return jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params)


def _program(kernel):
    """jaxpr of one benchmark kernel at a small size: (jaxpr, its tap
    gathers, its picks: the `take_along_axis` with which a stack
    mosaic takes the winning scene's value, one a namespace, and the
    composite the first valid namespace's; the channel-packed kernels
    mosaic with `where` and hold none).  The function under its `jit`
    is traced, so that no earlier trace of the same signature
    answers."""
    out_hw, step = (64, 64), 16
    sp = jnp.asarray(np.array([0.0, 0.1, 3000.0], np.float32))
    if kernel == "warp_scenes_ctrl_scored":
        # the export tile: cubic, a depth-1 stack, windowed; its 4 x 4
        # taps in two gathers of two tap rows each (`_tap_pairs`), where
        # a gather a tap took 16 of 1,048,576 elements a tile
        stack, ctrl, params = _scene_inputs()
        fn = lambda s, c, p, w0: warp_scenes_ctrl_scored.__wrapped__(  # noqa: E731
            s, c, p, "cubic", 1, out_hw, step, win=(80, 80), win0=w0)
        return jax.make_jaxpr(fn)(stack, ctrl, params,
                                  jnp.zeros((2,), jnp.int32)), 2, 1
    if kernel == "render_scenes_ctrl":
        # the Landsat mosaic tile: nearest, one tap a scene (11 gathers
        # before PR 38: these three and the eight)
        stack, ctrl, params = _scene_inputs()
        fn = lambda s, c, p: render_scenes_ctrl.__wrapped__(  # noqa: E731
            s, c, p, sp, "near", 1, out_hw, step, False, 0)
        return jax.make_jaxpr(fn)(stack, ctrl, params), 1, 2
    stack, ctrl, params = _scene_inputs()
    w0 = jnp.zeros((1, 2), jnp.int32)
    if kernel == "render_rgba_ctrl":
        # the true-colour tile: bilinear, one granule, 2 x 2 taps of
        # 3-vectors
        bands = ((stack[0], stack[0] + 1.0, stack[0] + 2.0),)
        prios = jnp.ones((1, 3), jnp.float32)
        fn = lambda g, c, p, pr, w: render_rgba_ctrl.__wrapped__(  # noqa: E731
            g, c, p, pr, sp, "bilinear", out_hw, step, False, 0,
            win=(80, 80), win0=w)
        return jax.make_jaxpr(fn)(bands, ctrl, params, prios, w0), 4, 0
    assert kernel == "render_expr_ctrl"
    # the NDVI tile: bilinear, one set of two bands
    fp = fingerprint(compile_expr("(nir - red) / (nir + red)"))
    bands = ((stack[0] + 500.0, stack[0]),)
    prios = jnp.ones((1, 2), jnp.float32)
    consts = jnp.asarray(fp.const_array())
    fn = lambda g, c, p, pr, k, w: render_expr_ctrl.__wrapped__(  # noqa: E731
        g, c, p, pr, sp, k, fp.key, "bilinear", out_hw, step, False, 0,
        win=(80, 80), win0=w)
    return jax.make_jaxpr(fn)(bands, ctrl, params, prios, consts, w0), 4, 0


@pytest.mark.parametrize("kernel", ["warp_scenes_ctrl_scored",
                                    "render_rgba_ctrl",
                                    "render_expr_ctrl",
                                    "render_scenes_ctrl"])
def test_a_kernel_gathers_its_taps_and_nothing_else(kernel, monkeypatch):
    # the chip's form of a tap (one gather of the stored value; this
    # CPU's form gathers a validity plane beside it: `_use_tapside`)
    monkeypatch.setattr(sys.modules["gsky_tpu.ops.warp"], "_use_tapside",
                        lambda: True)
    jaxpr, taps, picks = _program(kernel)
    assert _count(jaxpr.jaxpr, {"gather"}) == taps + picks
