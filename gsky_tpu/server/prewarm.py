"""Server-start shape-bucket prewarm.

The staged tile path (`pipeline/tile_stages.py`) removes host stalls
from the GetMap hot path, but the FIRST request of every
(kernel, shape-bucket, statics) combination still pays an XLA compile —
hundreds of milliseconds to seconds of latency a client sees as a
timeout spike after every deploy.  `prewarm` walks the configured
layers/styles at server start and compiles every bucketed render
program they can dispatch — the same entry points the executor calls
(`render_byte_raced`, `warp_scored_raced`, `render_rgba_ctrl`,
`render_scenes_bands_ctrl`) at the shapes the scene cache buckets to
(pixel dims padded to multiples of 256, batch dims to powers of two).
The raced entry points also run their pallas-vs-XLA race here, so the
kernel ledger's verdict lands off the request path too.  Compiled
programs survive restarts through jax's persistent compilation cache,
which `gsky_tpu.device.ensure_platform` places for every entry point.

A program that fails to compile here, or a pallas kernel that lands in
`pallas_tpu._FAILED` during the sweep, raises `PrewarmError`: the
server does not start on a path it already knows is broken.

`install_compile_probe` counts fresh backend compiles in this process
via `jax.monitoring` (compile requests minus persistent-cache hits) —
`compile_count()` deltas back the zero-recompile assertions in
tests/test_tile_pipeline.py and `tools/soak.py --scenario burst`.

Under paged serving (GSKY_PAGED on a pallas-capable backend,
ops/paged.py) the single-band sweep collapses: instead of one program
per (batch-pow2 x window-bucket) point, prewarm compiles the ragged
paged lattice — (method, granule-pow2, page-slot-pow2, wave-size-pow2)
— and those programs serve EVERY tile/window shape, which is what
lets `tools/soak.py --scenario burst` hold fresh compiles to a small
constant under a heterogeneous-shape storm (docs/PERF.md).  The
wave-size axis covers the stacked programs the wave scheduler
(pipeline/waves.py) dispatches: each wave of N tiles pads N to pow2
and that pad IS the leading compile dim, so sweeping pow2 wave sizes
up to GSKY_WAVE_MAX means the first mosaic storm after a deploy rides
warm programs at every occupancy the scheduler can assemble.  When
mesh serving is live (GSKY_MESH, gsky_tpu/mesh/) the same lattice
gains the mesh-layout axis: the granule-sharded byte/scored wave
programs and the time-sharded drill reduction compile here too
(docs/MESH.md).  When the dataflow autoplanner is live (GSKY_PLAN,
pipeline/autoplan.py) the lattice gains a block-shape axis: each point
also compiles the planner-shaped program whenever the cost model picks
a non-default Pallas block for it (docs/KERNELS.md).  When fused band
algebra is live (GSKY_EXPR_FUSE, default on) the lattice gains an
expression-fingerprint axis: every structurally distinct expression
the configured layers/styles can dispatch compiles its fused paged
program — gather + traced epilogue + scale-to-byte — over the same
wave-size ladder, verdict and all (`ex1` ledger token).  When temporal
animation serving is live (GSKY_ANIM, server/ows.py) the lattice gains
a time-wave axis: the superblock-broadcast byte program — G union
gathers shared by W frame lanes via ``sb_of`` — compiles at the
animation shape (~4 consecutive frames per timestep superblock), so
the first TIME-range GetMap after a deploy rides a warm program
(docs/PERF.md "Temporal waves").

Knobs: GSKY_PREWARM=0 disables; GSKY_PREWARM_SIZES (tile edges,
default "256"), GSKY_PREWARM_BUCKET (scene bucket edge, default 512),
GSKY_PREWARM_MAX_SCENES (largest batched scene count, pow2, default 2),
GSKY_PREWARM_WAVE_SIZES (wave-size lattice, default the pow2 ladder
up to GSKY_WAVE_MAX when waves are live, else "1" — cap it to bound
prewarm time on interpret backends).

Caveat: on the BUCKETED path windowed-gather program shapes are
data-dependent (the window is bounded per granule set), so prewarm
covers the win=None variants — exactly what CPU serving and the
batched path dispatch; on TPU the first windowed bucketed request per
bucket may still compile once.  Paged serving has no such hole: the
page-table contract erases the window axis from the compile key, so
the lattice sweep below is COMPLETE — wave-stacked or per-call, the
first storm hits only warm programs.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

log = logging.getLogger("gsky.prewarm")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_lock = threading.Lock()
_compiles = 0
_probe_installed = False


def _on_duration(event: str, duration: float, **kw) -> None:
    global _compiles
    if event == _COMPILE_EVENT:
        with _lock:
            _compiles += 1


def _on_event(event: str, **kw) -> None:
    global _compiles
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _compiles -= 1


def install_compile_probe() -> None:
    """Count fresh XLA backend compiles in this process (idempotent).
    jax times every compile REQUEST under the compile event, whether
    the persistent cache answers it or the backend does, and records a
    cache-hit event inside that window — so fresh compiles are requests
    minus hits."""
    global _probe_installed
    with _lock:
        if _probe_installed:
            return
        _probe_installed = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_count() -> int:
    """Fresh compiles observed since the probe was installed."""
    with _lock:
        return _compiles


def prewarm_enabled() -> bool:
    return os.environ.get("GSKY_PREWARM", "1") != "0"


class PrewarmError(RuntimeError):
    """A program of the prewarm sweep failed to compile or run."""


def _env_list(name: str, default: str) -> List[int]:
    out = []
    for tok in os.environ.get(name, default).split(","):
        tok = tok.strip()
        if tok:
            try:
                out.append(int(tok))
            except ValueError:
                pass
    return out


def wave_size_lattice() -> List[int]:
    """Pow2 wave sizes the paged sweep covers (the leading compile dim
    of every stacked wave program).  GSKY_PREWARM_WAVE_SIZES overrides
    (comma list, clamped to [1, 64]); default is the full pow2 ladder
    up to `wave_max()` when wave dispatch is live, else just 1 — the
    per-call leading dim the executor uses without waves."""
    env = os.environ.get("GSKY_PREWARM_WAVE_SIZES", "")
    if env:
        sizes = sorted({max(1, min(64, v))
                        for v in _env_list("GSKY_PREWARM_WAVE_SIZES",
                                           "")})
        return sizes or [1]
    from ..pipeline.waves import wave_max, waves_enabled
    if not waves_enabled():
        return [1]
    out, w = [], 1
    while w <= wave_max():
        out.append(w)
        w *= 2
    return out


def layer_specs(configs: Dict) -> Set[Tuple[str, int, bool, int]]:
    """Distinct (method, n_exprs, auto, colour_scale) combinations the
    configured layers and styles can dispatch — the static half of the
    jit cache key; the shape half comes from the bucket/batch sweep."""
    from ..ops.scale import scale_params_auto
    specs: Set[Tuple[str, int, bool, int]] = set()
    for cfg in configs.values():
        for lay in cfg.layers:
            for style in [lay] + list(lay.styles):
                exprs = style.rgb_products or lay.rgb_products
                n = len(exprs) or 1
                if n > 4:
                    continue          # beyond the fused fast path
                method = style.resample or lay.resample or "near"
                auto = scale_params_auto(style.offset_value,
                                         style.scale_value,
                                         style.clip_value)
                specs.add((method, n, auto, int(style.colour_scale)))
    return specs


def layer_expr_specs(configs: Dict):
    """Distinct (method, auto, colour_scale, fingerprint) combinations
    for single-expression layers/styles whose band algebra can take the
    fused paged epilogue (GSKY_EXPR_FUSE, ops/paged.py).  The
    fingerprint is the expression's normalized-AST identity — the
    static half of the fused jit key — so structurally identical
    expressions across layers collapse to one lattice point."""
    from ..ops.expr import fingerprint, parse_band_expressions
    from ..ops.scale import scale_params_auto
    specs = {}
    for cfg in configs.values():
        for lay in cfg.layers:
            for style in [lay] + list(lay.styles):
                exprs = style.rgb_products or lay.rgb_products
                if len(exprs) != 1:
                    continue
                try:
                    # config entries are `name = expr` (or bare band
                    # names) — the same split the request path applies
                    ce = parse_band_expressions(
                        list(exprs)).expressions[0]
                except Exception:
                    continue          # bad config expression: the
                    # request path reports it, prewarm just skips
                if ce._ast[0] == "var" or not ce.variables:
                    continue          # trivial: rides the byte path
                method = style.resample or lay.resample or "near"
                auto = scale_params_auto(style.offset_value,
                                         style.scale_value,
                                         style.clip_value)
                fp = fingerprint(ce)
                specs[(method, auto, int(style.colour_scale),
                       fp.hash)] = fp
    return [(m, a, cs, fp)
            for (m, a, cs, _h), fp in sorted(specs.items())]


def _ctrl_grid(height: int, width: int, bh: int, bw: int,
               step: int) -> np.ndarray:
    """(2, gh, gw) f32 control grid mapping the tile onto the scene —
    an identity-ish affine so the raced kernels exercise real gather
    paths (both racers see the same input, so the verdict is sound)."""
    gh = (height - 1 + step - 1) // step + 1
    gw = (width - 1 + step - 1) // step + 1
    c = np.arange(gw, dtype=np.float32) * step + 0.5
    r = np.arange(gh, dtype=np.float32) * step + 0.5
    C, R = np.meshgrid(c * (bw / max(1, width)),
                       r * (bh / max(1, height)))
    return np.stack([C, R]).astype(np.float32)


def _params(n: int, bh: int, bw: int, pad: Optional[int] = None,
            per_ns: bool = False) -> np.ndarray:
    """(pad or n, 11) f32 kernel param rows: inverse-affine identity,
    scene dims, NaN nodata, descending priority, ns id 0 (or one
    namespace per row for the bands path); rows past ``n`` carry ns id
    -1 (the padding convention of `executor._scene_groups`).  Values
    stay in-range: the raced entry points EXECUTE both implementations
    and compare, so garbage here could poison the ledger verdict."""
    B = pad or n
    p = np.zeros((B, 11), np.float32)
    p[:, 10] = -1.0
    for i in range(n):
        p[i, :6] = (0.0, 1.0, 0.0, 0.0, 0.0, 1.0)
        p[i, 6] = bh
        p[i, 7] = bw
        p[i, 8] = np.nan
        p[i, 9] = float(n - i)
        p[i, 10] = float(i) if per_ns else 0.0
    return p


def prewarm(configs: Dict,
            sizes: Optional[List[int]] = None,
            bucket: Optional[int] = None,
            max_scenes: Optional[int] = None) -> Dict:
    """Compile every bucketed render program the configured layers can
    hit, through the SAME entry points the executor dispatches.  Safe
    to call on a serving process (pure compile + one throwaway run per
    program).  Returns {"specs", "programs", "failures", "compiles",
    "seconds"}; raises `PrewarmError` when any program or pallas
    kernel failed."""
    import jax.numpy as jnp
    from ..ops.paged import (page_slots, paged_enabled, paged_vmem_ok,
                             render_byte_paged_raced,
                             warp_scored_paged_raced)
    from ..ops.pallas_tpu import (_FAILED, render_byte_raced,
                                  warp_scored_raced)
    from ..ops.warp import (render_rgba_ctrl, render_scenes_bands_ctrl,
                            render_scenes_ctrl, warp_scenes_ctrl_scored)
    from ..pipeline.executor import _bucket_pow2
    from .ows import anim_enabled

    anim_on = anim_enabled()
    install_compile_probe()
    t0 = time.perf_counter()
    c0 = compile_count()
    sizes = sizes or _env_list("GSKY_PREWARM_SIZES", "256")
    bucket = bucket or int(os.environ.get("GSKY_PREWARM_BUCKET", 512))
    max_scenes = max_scenes or int(
        os.environ.get("GSKY_PREWARM_MAX_SCENES", 2))
    step = 16
    specs = layer_specs(configs)
    programs = failures = 0
    failed0 = set(_FAILED)

    def run(fn, *args, **kw):
        nonlocal programs, failures
        try:
            out = fn(*args, **kw)
            for leaf in (out if isinstance(out, tuple) else (out,)):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
            programs += 1
        except Exception:
            failures += 1
            log.exception("prewarm %s", getattr(fn, "__name__", fn))

    for method, n_exprs, auto, colour_scale in sorted(specs):
        for hw in sizes:
            bh = bw = bucket
            ctrl = jnp.asarray(_ctrl_grid(hw, hw, bh, bw, step))
            sp = jnp.asarray(np.zeros(3, np.float32))
            batches = sorted({_bucket_pow2(b)
                              for b in range(1, max_scenes + 1)})
            if n_exprs == 1 and paged_enabled():
                # paged serving collapses the shape sweep: one program
                # per (statics, granule-pow2 T, page-slot-pow2 S,
                # wave-size-pow2 W) point serves EVERY tile/window
                # shape (ops/paged.py), so the sweep is a ragged-pad
                # lattice instead of a bucket zoo.  The leading dim W
                # is what the wave scheduler (pipeline/waves.py) pads
                # each wave to, so covering the pow2 ladder here means
                # no occupancy the ticker can assemble compiles on the
                # request path.  Tables stay all-null (slot 0): the
                # gather walks real NaN pages, so both race legs do
                # representative work.  The pool must be the RUNTIME
                # singleton — its (capacity, PR, PC) shape is part of
                # the compiled program.
                from ..ops.paged import (OutputRing, _stage_refresh_fn)
                from ..pipeline.pages import default_page_pool
                n_pad = _bucket_pow2(1)
                pool = default_page_pool()
                # the wave pipeline's ring/staging programs compile on
                # the SAME (W, shape, dtype) lattice: one throwaway
                # ring warms the donated put/take pair per lane, and
                # the staging refresh warms per input-stack shape
                ring = OutputRing()
                pr, pc = pool.page_rows, pool.page_cols
                scap = _bucket_pow2(page_slots())
                slot_sweep = [s for s in (1, 2, 4, 8)
                              if s <= scap and paged_vmem_ok(s, n_pad,
                                                             pr, pc)]
                waves = wave_size_lattice()
                for B in batches:
                    stack = jnp.full((B, bh, bw), jnp.nan, jnp.float32)
                    params = jnp.asarray(_params(B, bh, bw))
                    for S in slot_sweep:
                        p16 = np.zeros((B, 16), np.float32)
                        p16[:, :11] = np.asarray(_params(B, bh, bw))
                        p16[:, 13] = pr     # 1-page window extents:
                        p16[:, 14] = pc     # real gather work over the
                        p16[:, 15] = 1.0    # null page
                        # block-shape lattice axis: when the dataflow
                        # autoplanner's cost model picks a non-default
                        # Pallas block for this point, the planner-
                        # shaped program compiles here too — the first
                        # planned storm after a deploy must be as warm
                        # as the default-shaped one
                        try:
                            from ..pipeline.autoplan import plan_block
                            blk = plan_block(hw, hw, n_pad, method,
                                             T=B, S=S, pr=pr, pc=pc)
                        except Exception:
                            blk = None
                        for W in waves:
                            tables = jnp.zeros((W, B, S), jnp.int32)
                            p16w = jnp.asarray(np.tile(p16, (W, 1)))
                            ctrls = jnp.stack([ctrl] * W)
                            sps = jnp.stack([sp] * W)

                            def _xla_byte(stack=stack, params=params,
                                          W=W):
                                one = render_scenes_ctrl(
                                    stack, ctrl, params, sp, method,
                                    n_pad, (hw, hw), step, auto,
                                    colour_scale)
                                return jnp.stack([one] * W)

                            def _xla_scored(stack=stack,
                                            params=params, W=W):
                                c, b = warp_scenes_ctrl_scored(
                                    stack, ctrl, params, method,
                                    n_pad, (hw, hw), step)
                                return (jnp.stack([c] * W),
                                        jnp.stack([b] * W))

                            with pool.locked_pool() as parr:
                                run(render_byte_paged_raced, parr,
                                    tables, p16w, ctrls, sps, method,
                                    n_pad, (hw, hw), step, auto,
                                    colour_scale, _xla_byte)
                                run(warp_scored_paged_raced, parr,
                                    tables, p16w, ctrls, method,
                                    n_pad, (hw, hw), step,
                                    _xla_scored)
                                if blk is not None:
                                    run(render_byte_paged_raced, parr,
                                        tables, p16w, ctrls, sps,
                                        method, n_pad, (hw, hw), step,
                                        auto, colour_scale, _xla_byte,
                                        blk=blk)
                                    run(warp_scored_paged_raced, parr,
                                        tables, p16w, ctrls, method,
                                        n_pad, (hw, hw), step,
                                        _xla_scored, blk=blk)
                                # time-wave lattice axis (GSKY_ANIM,
                                # server/ows.py animation serving):
                                # temporal waves dispatch the
                                # superblock-broadcast program — G
                                # union tables shared by W frame lanes
                                # via sb_of — so the animation shape
                                # (consecutive frames resolving to the
                                # same timestep, ~4 lanes per
                                # superblock) compiles here, not on
                                # the first TIME-range GetMap after a
                                # deploy
                                if anim_on and W >= 4:
                                    G = max(1, W // 4)
                                    Gp = 1
                                    while Gp < G:
                                        Gp *= 2
                                    sb = jnp.asarray(
                                        (np.arange(W) * G // W)
                                        .astype(np.int32))
                                    sbt = jnp.zeros((Gp, B, S),
                                                    jnp.int32)
                                    run(render_byte_paged_raced, parr,
                                        sbt, p16w, ctrls, sps, method,
                                        n_pad, (hw, hw), step, auto,
                                        colour_scale, _xla_byte,
                                        sb_of=sb)
                            # output-ring lattice: the dispatcher
                            # pushes FULL pow2 result blocks through
                            # the donated ring, so put+take compile
                            # per (W, result shape, dtype) lane —
                            # cover byte, scored canvas and validity
                            run(lambda: ring.put(jnp.zeros(
                                (W, hw, hw), jnp.uint8)))
                            run(lambda: ring.put(jnp.zeros(
                                (W, n_pad, hw, hw), jnp.float32)))
                            run(lambda: ring.put(jnp.zeros(
                                (W, n_pad, hw, hw), bool)))
                            # the scored dispatch folds best ->
                            # validity on device; warm the fold too
                            run(lambda: jnp.zeros(
                                (W, n_pad, hw, hw), jnp.float32)
                                > -jnp.inf)
                            # staging-ring refresh: the assembly stage
                            # re-uploads each input stack through the
                            # donated refresh, one program per shape
                            for d in (tables, p16w, ctrls, sps):
                                h = np.asarray(d)
                                run(lambda h=h: _stage_refresh_fn()(
                                    jnp.asarray(h), h))
            elif n_exprs == 1:
                n_pad = _bucket_pow2(1)
                for B in batches:
                    stack = jnp.full((B, bh, bw), jnp.nan, jnp.float32)
                    params = jnp.asarray(_params(B, bh, bw))
                    run(render_byte_raced, stack, ctrl, params, sp,
                        method, n_pad, (hw, hw), step, auto,
                        colour_scale, win=None, win0_dev=None)
                    # the modular / mosaic fallback dispatches the
                    # scored warp at the same shapes
                    run(warp_scored_raced, stack, ctrl, params, method,
                        n_pad, (hw, hw), step, win=None, win0_dev=None)
            else:
                # one granule per namespace: the executor pads the
                # scene batch to pow2 (`_scene_groups`), so an RGB set
                # dispatches B=4 scene arrays, the first one twice
                n_pad = _bucket_pow2(n_exprs)
                B = _bucket_pow2(n_exprs)
                sel = jnp.asarray(np.arange(n_exprs, dtype=np.int32))
                scene = jnp.full((bh, bw), jnp.nan, jnp.float32)
                params = jnp.asarray(_params(n_exprs, bh, bw, pad=B,
                                             per_ns=True))
                run(render_scenes_bands_ctrl, (scene,) * B, ctrl, params,
                    sp, sel, method, n_pad, (hw, hw), step, auto,
                    colour_scale, win=None, win0=None)
                if n_exprs == 3:
                    run(render_rgba_ctrl, ((scene,) * 3,), ctrl,
                        jnp.asarray(_params(1, bh, bw)),
                        jnp.ones((1, 3), jnp.float32), sp, method,
                        (hw, hw), step, auto, colour_scale,
                        win=None, win0=None)

    expr_programs = 0
    if paged_enabled():
        # expression-fingerprint axis: every structurally distinct
        # band-algebra expression the configured layers can dispatch
        # compiles its fused paged program (gather + epilogue +
        # scale-to-byte, ops/paged.py) over the SAME wave-size lattice,
        # so the first NDVI storm after a deploy compiles nothing —
        # and the raced entry runs the pallas-vs-XLA race here, landing
        # the `ex1` ledger verdict off the request path too
        from ..ops.expr import expr_fuse_enabled
        from ..ops.paged import expr_epilogue, render_expr_paged_raced
        from ..ops.scale import scale_to_byte
        expr_specs = layer_expr_specs(configs) \
            if expr_fuse_enabled() else []
        if expr_specs:
            from ..pipeline.pages import default_page_pool
            pool = default_page_pool()
            pr, pc = pool.page_rows, pool.page_cols
            batches = sorted({_bucket_pow2(b)
                              for b in range(1, max_scenes + 1)})
            waves = wave_size_lattice()
            scap = _bucket_pow2(page_slots())
            for method, auto, colour_scale, fp in expr_specs:
                n_ns = _bucket_pow2(fp.n_slots)
                csts = fp.const_array()
                slot_sweep = [s for s in (1, 2, 4, 8)
                              if s <= scap
                              and paged_vmem_ok(s, n_ns, pr, pc)]
                for hw in sizes:
                    bh = bw = bucket
                    ctrl = jnp.asarray(
                        _ctrl_grid(hw, hw, bh, bw, step))
                    sp = jnp.asarray(np.zeros(3, np.float32))
                    stack = jnp.full((n_ns, bh, bw), jnp.nan,
                                     jnp.float32)
                    params = jnp.asarray(
                        _params(n_ns, bh, bw, per_ns=True))
                    for B in batches:
                        p16 = np.zeros((B, 16), np.float32)
                        p16[:, :11] = np.asarray(_params(B, bh, bw))
                        p16[:, 13] = pr
                        p16[:, 14] = pc
                        p16[:, 15] = 1.0
                        for S in slot_sweep:
                            for W in waves:
                                tables = jnp.zeros((W, B, S),
                                                   jnp.int32)
                                p16w = jnp.asarray(np.tile(p16,
                                                           (W, 1)))
                                ctrls = jnp.stack([ctrl] * W)
                                sps = jnp.stack([sp] * W)
                                constsW = jnp.asarray(
                                    np.tile(csts, (W, 1)))

                                def _xla_expr(stack=stack,
                                              params=params, fp=fp,
                                              csts=csts, W=W, hw=hw,
                                              ctrl=ctrl,
                                              method=method,
                                              n_ns=n_ns, auto=auto,
                                              cs=colour_scale):
                                    c, b = warp_scenes_ctrl_scored(
                                        stack, ctrl, params, method,
                                        n_ns, (hw, hw), step)
                                    plane, ok = expr_epilogue(
                                        c[None], b[None], fp.key,
                                        jnp.asarray(csts[None]))
                                    one = scale_to_byte(
                                        plane, ok, 0.0, 0.0, 0.0,
                                        cs, auto)[0]
                                    return jnp.stack([one] * W)

                                with pool.locked_pool() as parr:
                                    before = programs
                                    run(render_expr_paged_raced,
                                        parr, tables, p16w, ctrls,
                                        sps, constsW, method, n_ns,
                                        (hw, hw), step, auto,
                                        colour_scale, fp.key,
                                        fp.hash, _xla_expr)
                                    expr_programs += programs - before

    mesh_programs = 0
    if paged_enabled():
        # mesh-layout axis: when GSKY_MESH serving is live, the same
        # (method, granule, slot, wave-size) lattice also compiles the
        # granule-sharded wave programs + the time-sharded drill, so
        # the first multi-chip storm after a deploy rides warm programs
        try:
            from ..mesh.dispatch import default_mesh
            md = default_mesh()
        except Exception:
            md = None
        if md is not None:
            from ..pipeline.pages import default_page_pool
            pool = default_page_pool()
            batches = sorted({_bucket_pow2(b)
                              for b in range(1, max_scenes + 1)})
            scap = _bucket_pow2(page_slots())
            slot_sweep = [s for s in (1, 2, 4, 8)
                          if s <= scap
                          and paged_vmem_ok(s, _bucket_pow2(1),
                                            pool.page_rows,
                                            pool.page_cols)]
            try:
                mesh_programs = md.prewarm_programs(
                    pool, specs, sizes, batches, slot_sweep,
                    wave_size_lattice(), step)
                programs += mesh_programs
            except Exception:
                failures += 1
                log.exception("prewarm mesh lattice")

    out = {"specs": len(specs), "programs": programs,
           "mesh_programs": mesh_programs,
           "expr_programs": expr_programs,
           "failures": failures, "compiles": compile_count() - c0,
           "seconds": round(time.perf_counter() - t0, 3)}
    log.info("prewarm: %s", out)
    newly_failed = {k: v for k, v in _FAILED.items()
                    if k not in failed0}
    if failures or newly_failed:
        raise PrewarmError(
            f"{failures} program(s) failed, kernels failed in the "
            f"sweep {newly_failed}: {out}")
    return out
