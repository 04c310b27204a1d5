"""Ragged paged rendering: one fused warp-render program for every
tile shape.

The bucketed dispatch (`ops.pallas_tpu` + `pipeline.executor`) bounds
recompilation by padding every gather window up to `_WIN_BUCKETS` and
every batch to a power of two — each (window-bucket x batch-pow2)
combination is its own XLA program, pad waste inflates the expensive
host<->device pull.  Following Ragged Paged Attention (PAPERS.md),
which serves arbitrary ragged KV lengths from paged HBM pools with ONE
compiled kernel, this module replaces the shape axes with a page
indirection:

- gather windows live in fixed-size HBM pages (`GSKY_PAGE_SIZE`,
  default 128x512 f32; validity is NaN-encoded exactly like the scene
  cache) allocated from a shared pool (`pipeline.pages.PagePool`) —
  pages are content-keyed on (scene, page row, page col), so
  overlapping tiles share them;
- a per-tile page table (page slots + per-granule window origin/extent,
  rows of the same (B, 16) params block the bucketed kernel uses)
  drives the kernel: grid (tile, block_y, block_x, granule) with the
  granule axis innermost, so the pallas pipeline DMAs granule t+1's
  page list HBM->VMEM while granule t computes — the same
  double-buffered page walk paged attention does over ragged KV;
- the kernel body is the bucketed fused kernel's body op for op
  (affine -> true-extent oob NaN-poisoning -> page-table gather ->
  tap-side validity -> strictly-greater priority mosaic -> optional
  byte-scale epilogue), so parity transfers: nearest is bit-exact and
  interpolated methods are <= 2 ulp vs the XLA reference — for cubic,
  whose negative weights let the sum cancel, 2 ulp of the tap
  magnitude (tests/test_paged.py).

Shape axes that remain static are RAGGED-PADDED, not shape-bucketed:
the granule axis pads to the pow2 of the LARGEST tile in the dispatch
(padding rows carry ns_id -1 and a null page table) and the page-table
width to the pow2 of the largest page count — so one program per
(method, n_ns, out_hw, granule-pow2, slot-pow2) serves arbitrary
window shapes, and the program count is independent of traffic shape
diversity.  `GSKY_PAGED=0` restores the bucketed path byte-identically
(the paged branch sits strictly above the existing entry points).

Race verdicts for the paged kernels use a versioned token prefix
(`PAGED_TOKEN_VERSION`) so stale bucketed-era ledger lines never
replay onto them; see `ops.kernel_ledger.token_version_ok`.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_tpu import (_WARP_BLK, _WARP_VMEM_BUDGET, _note_lowered,
                         pallas_interpret, pltpu, run_with_fallback,
                         warp_pallas_enabled)

# token scheme version for paged-kernel ledger verdicts: bump when the
# paged program's meaning changes (page walk, params layout) so old
# verdicts are skipped instead of replayed onto a different kernel
PAGED_TOKEN_VERSION = "pg1"

# token scheme version for the fused expression-epilogue program
# (`render_expr_paged`): the token additionally carries the expression's
# structural fingerprint hash, so same-structure expressions share race
# verdicts and a grammar/normalization change invalidates them wholesale
EXPR_TOKEN_VERSION = "ex1"

# params row width: slots 0..10 are the bucketed kernel's contract
# (affine, true extent, nodata, priority, ns id), 11/12 the page-grid
# window origin, 13/14 the page-aligned window extent, 15 the page
# columns per page row (the table's row stride)
PARAMS_W = 16


def page_shape():
    """(page_rows, page_cols) from GSKY_PAGE_SIZE ("RxC", default
    128x512) — clamped to the f32 tile grid (rows multiple of 8, cols
    multiple of 128) so pages are always lane-aligned VMEM blocks."""
    v = os.environ.get("GSKY_PAGE_SIZE", "128x512").lower()
    try:
        r, c = v.split("x")
        pr, pc = int(r), int(c)
    except (ValueError, AttributeError):
        pr, pc = 128, 512
    pr = max(8, (pr // 8) * 8)
    pc = max(128, (pc // 128) * 128)
    return pr, pc


def page_slots() -> int:
    """Max page-table slots per granule (GSKY_PAGE_SLOTS, default 8):
    windows needing more pages than this fall back to the bucketed
    path — the knob bounds the kernel's per-granule VMEM residency."""
    try:
        s = int(os.environ.get("GSKY_PAGE_SLOTS", "8"))
    except ValueError:
        s = 8
    return max(1, min(64, s))


def paged_enabled() -> bool:
    """Paged dispatch gate: on wherever the paged pallas kernels are
    selectable (`warp_pallas_enabled` — today GSKY_PALLAS=interpret
    only, because Mosaic refuses their gather on a real TPU);
    GSKY_PAGED=0 restores the bucketed path byte-identically.  XLA
    serving (plain CPU, and the TPU until the gather is repaired) keeps
    buckets — the paged walk is a pallas formulation."""
    return os.environ.get("GSKY_PAGED", "1") != "0" \
        and warp_pallas_enabled()


def paged_vmem_ok(slots: int, n_ns: int, pr: int, pc: int,
                  blk=None) -> bool:
    """Eligibility gate, checked BEFORE the race: a page list too big
    for VMEM must go to the bucketed path, not through the kernel
    failure handler on a predictable over-allocation.  ``blk`` is the
    (block_h, block_w) output tile the cost model picked; None keeps
    the fixed `_WARP_BLK` square."""
    bh, bw = blk if blk is not None else (_WARP_BLK, _WARP_BLK)
    pages = slots * pr * pc * 4 * 2          # page block, x2 DMA
    acc = n_ns * bh * bw * 4 * 2 * 2         # canv+best
    grids = bh * bw * 4 * 2 * 2              # sx+sy, x2
    return pages + acc + grids <= _WARP_VMEM_BUDGET


# --- gathered-HBM-bytes accounting (module-level, eager-side only) ----
#
# The pool->VMEM gather in `_paged_scored` is jit-traced, so a counter
# inside it would tick once per COMPILE, not per dispatch.  The raced
# wrappers (and the mesh dispatcher) account the bytes of each dispatch
# they launch here, eagerly; the plan soak reads the total
# to measure what superblock compaction actually saved.
_GATHER_LOCK = __import__("threading").Lock()
_GATHER_BYTES = 0
_GATHER_CALLS = 0


def note_gather(nbytes: int) -> None:
    """Record one dispatch's pool->VMEM gather volume (bytes)."""
    global _GATHER_BYTES, _GATHER_CALLS
    with _GATHER_LOCK:
        _GATHER_BYTES += int(nbytes)
        _GATHER_CALLS += 1


def gather_bytes_total() -> int:
    with _GATHER_LOCK:
        return _GATHER_BYTES


def gather_stats() -> dict:
    with _GATHER_LOCK:
        return {"bytes": _GATHER_BYTES, "dispatches": _GATHER_CALLS}


def reset_gather_bytes() -> None:
    """Zero the gather accounting — bench/soak A/B legs only."""
    global _GATHER_BYTES, _GATHER_CALLS
    with _GATHER_LOCK:
        _GATHER_BYTES = 0
        _GATHER_CALLS = 0


def table_gather_bytes(tables, pr: int, pc: int) -> int:
    """Bytes the paged gather moves pool->VMEM for a (G, T, S) table
    block: every listed slot is one (pr, pc) f32 page pull.  With a
    superblock plan, G is the COMPACTED superblock count, so this is
    exactly what compaction saves vs the per-tile G = N."""
    g, t, s = (int(tables.shape[0]), int(tables.shape[1]),
               int(tables.shape[2]))
    return g * t * s * int(pr) * int(pc) * 4


def _paged_render_kernel(method: str, n_ns: int, T: int, S: int,
                         pr: int, pc: int):
    """Kernel-body closure.  Grid (n, by, bx, t), granule axis t
    INNERMOST: the pages BlockSpec indexes by (n, t), so the pallas
    pipeline stages tile n granule t+1's page list into VMEM while
    granule t computes — double-buffered ragged page walking.  The
    per-namespace accumulators stay VMEM-resident across the t sweep
    (initialised at t == 0).

    Per granule the body mirrors `pallas_tpu._warp_render_kernel` op
    for op; the only new arithmetic is the page indirection in `tap`:
    window-relative (ri, ci) -> (page row, page col) -> table slot ->
    flat offset into this granule's staged page block.  Window origins
    are page-aligned, so the rebase subtraction stays exact (integer
    <= 4096 off an f32 coordinate < 2^12) and tap values match the
    bucketed gather bit for bit."""
    page = pr * pc

    def kernel(params_ref, sx_ref, sy_ref, pages_ref, canv_ref,
               best_ref):
        n = pl.program_id(0)
        t = pl.program_id(3)

        @pl.when(t == 0)
        def _init():
            canv_ref[:] = jnp.zeros(canv_ref.shape, canv_ref.dtype)
            best_ref[:] = jnp.full(best_ref.shape, -jnp.inf,
                                   best_ref.dtype)

        def p(k):
            return params_ref[n * T + t, k]

        sx = sx_ref[0]
        sy = sy_ref[0]
        cols = (p(0) + p(1) * sx + p(2) * sy) - 0.5
        rows = (p(3) + p(4) * sx + p(5) * sy) - 0.5
        oob = (rows < -0.5) | (rows > p(6) - 0.5) \
            | (cols < -0.5) | (cols > p(7) - 0.5)
        rows = jnp.where(oob, jnp.nan, rows)
        rows = rows - p(11)     # page-aligned window-origin rebase
        cols = cols - p(12)     # (exact: int <= 4096 off f32 < 2^12)
        wri = p(13).astype(jnp.int32)   # page-aligned window extent
        wci = p(14).astype(jnp.int32)
        ppc = p(15).astype(jnp.int32)   # page cols per page row
        flat = pages_ref[0, 0].reshape(S * page)
        nd = p(8)

        def tap(ri, ci, inb):
            # page walk: window-relative index -> table slot -> flat
            # offset in this granule's staged pages.  Padding granules
            # have wri == wci == 0, so inb is False and the clipped
            # offset only needs to stay addressable.
            lp = (ri // pr) * ppc + (ci // pc)
            idx = lp * page + (ri % pr) * pc + (ci % pc)
            idx = jnp.clip(idx, 0, S * page - 1)
            v = flat[idx]
            ok = inb & jnp.isfinite(v) & (v != nd)
            return jnp.where(ok, v, 0.0), ok

        if method in ("near", "nearest"):
            ri = jnp.floor(rows + (0.5 + 1e-10)).astype(jnp.int32)
            ci = jnp.floor(cols + (0.5 + 1e-10)).astype(jnp.int32)
            inb = (ri >= 0) & (ri < wri) & (ci >= 0) & (ci < wci) \
                & jnp.isfinite(rows) & jnp.isfinite(cols)
            val, ok = tap(jnp.clip(ri, 0, wri - 1),
                          jnp.clip(ci, 0, wci - 1), inb)
        else:
            finite = jnp.isfinite(rows) & jnp.isfinite(cols)
            rows = jnp.where(finite, rows, -10.0)
            cols = jnp.where(finite, cols, -10.0)
            r0 = jnp.floor(rows)
            c0 = jnp.floor(cols)
            fr = rows - r0
            fc = cols - c0
            r0 = r0.astype(jnp.int32)
            c0 = c0.astype(jnp.int32)
            if method == "bilinear":
                taps = [(dr, dc,
                         (fr if dr else 1 - fr) * (fc if dc else 1 - fc))
                        for dr in (0, 1) for dc in (0, 1)]
                thresh = 1e-6
            else:               # cubic (Catmull-Rom)
                from .warp import _cubic_weights
                wr_ = _cubic_weights(fr)
                wc_ = _cubic_weights(fc)
                taps = [(dr - 1, dc - 1, wr_[dr] * wc_[dc])
                        for dr in range(4) for dc in range(4)]
                thresh = 0.05
            acc = jnp.zeros(rows.shape, jnp.float32)
            wacc = jnp.zeros(rows.shape, jnp.float32)
            for dr, dc, wt in taps:
                ri = r0 + dr
                ci = c0 + dc
                inb = (ri >= 0) & (ri < wri) & (ci >= 0) & (ci < wci)
                v, okt = tap(jnp.clip(ri, 0, wri - 1),
                             jnp.clip(ci, 0, wci - 1), inb)
                okf = okt.astype(jnp.float32)
                acc = acc + wt * okf * v
                wacc = wacc + wt * okf
            ok = finite & (wacc > thresh)
            val = acc / jnp.where(wacc > thresh, wacc, 1.0)

        prio = p(9)
        ns = p(10)
        for m in range(n_ns):   # static unroll (n_ns is pow2-bounded)
            member = ns == jnp.float32(m)
            s_m = jnp.where(member & ok, prio, -jnp.inf)
            b = best_ref[0, m, :, :]
            take = s_m > b      # strict: first-seen wins ties
            canv_ref[0, m, :, :] = jnp.where(take, val,
                                             canv_ref[0, m, :, :])
            best_ref[0, m, :, :] = jnp.where(take, s_m, b)

    return kernel


def _paged_scored(pool, tables, params, ctrls, method, n_ns, out_hw,
                  step, interpret, blk=None, sb_of=None):
    """Shared core: XLA prologue (page-table gather out of the pool +
    per-tile ctrl-grid upsample) feeding one fused pallas_call over
    every tile in the dispatch.  Returns (canv (N, n_ns, h, w) f32,
    best (N, n_ns, h, w) f32, -inf = invalid).

    The gather `pool[tables]` is the whole HBM data movement of the
    dispatch: exactly the staged pages, no pow2 window pad — the XLA
    gather is page-granular (contiguous (pr, pc) blocks), which is the
    coalesced access pattern the pool layout exists for.

    ``sb_of`` (N,) int32 activates superblock compaction: tables is
    then (G, T, S) with G <= N SHARED page regions (autoplan merged
    overlapping windows), the scattered pool gather runs once per
    superblock, and ``[sb_of]`` broadcasts each region to the output
    lanes that read it — a contiguous copy, not a second scattered
    gather.  The kernel body, BlockSpecs and every operand shape after
    the broadcast are unchanged, so parity with the per-tile path
    transfers unconditionally.  ``blk`` retiles the output grid from
    the cost model; None keeps the fixed `_WARP_BLK` square."""
    from .warp import _bilerp_grid
    _note_lowered("warp_scored_paged", interpret)
    bh, bw = blk if blk is not None else (_WARP_BLK, _WARP_BLK)
    h, w = out_hw
    T, S = int(tables.shape[1]), int(tables.shape[2])
    pr, pc = int(pool.shape[1]), int(pool.shape[2])
    if sb_of is None:
        N = int(tables.shape[0])
        pages = pool[tables.reshape(-1)].reshape(N, T, S * pr, pc)
    else:
        G = int(tables.shape[0])
        N = int(sb_of.shape[0])
        pages = pool[tables.reshape(-1)].reshape(G, T, S * pr,
                                                 pc)[sb_of]
    sx = jax.vmap(lambda c: _bilerp_grid(c[0], h, w, step))(ctrls)
    sy = jax.vmap(lambda c: _bilerp_grid(c[1], h, w, step))(ctrls)
    hp = -(-h // bh) * bh
    wp = -(-w // bw) * bw
    if (hp, wp) != (h, w):
        sx = jnp.pad(sx, ((0, 0), (0, hp - h), (0, wp - w)))
        sy = jnp.pad(sy, ((0, 0), (0, hp - h), (0, wp - w)))
    kernel = _paged_render_kernel(method, n_ns, T, S, pr, pc)
    if interpret:
        params_spec = pl.BlockSpec((N * T, PARAMS_W),
                                   lambda n, i, j, t: (0, 0))
    else:
        params_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    canv, best = pl.pallas_call(
        kernel,
        grid=(N, hp // bh, wp // bw, T),
        in_specs=[
            params_spec,
            pl.BlockSpec((1, bh, bw),
                         lambda n, i, j, t: (n, i, j)),
            pl.BlockSpec((1, bh, bw),
                         lambda n, i, j, t: (n, i, j)),
            pl.BlockSpec((1, 1, S * pr, pc),
                         lambda n, i, j, t: (n, t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_ns, bh, bw),
                         lambda n, i, j, t: (n, 0, i, j)),
            pl.BlockSpec((1, n_ns, bh, bw),
                         lambda n, i, j, t: (n, 0, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, n_ns, hp, wp), jnp.float32),
            jax.ShapeDtypeStruct((N, n_ns, hp, wp), jnp.float32),
        ],
        interpret=interpret,
    )(params, sx, sy, pages)
    return canv[:, :, :h, :w], best[:, :, :h, :w]


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "interpret", "blk"))
def warp_scored_paged(pool, tables, params, ctrls, method: str = "near",
                      n_ns: int = 1, out_hw=(256, 256), step: int = 16,
                      interpret: bool = False, blk=None, sb_of=None):
    """Paged counterpart of `ops.warp.warp_scenes_ctrl_scored`, over N
    tiles at once: pool (cap, pr, pc) f32, tables (N, T, S) int32 page
    slots (null slot 0 pads), params (N*T, 16) f32, ctrls (N, 2, gh,
    gw) f32.  Returns (canvases (N, n_ns, h, w), best (N, n_ns, h, w),
    -inf = invalid).  The jit key holds NO window shape: one program
    per (method, n_ns, out_hw, step, T, S) serves every tile shape.
    ``blk`` (static) retiles the output grid; ``sb_of`` (traced (N,)
    int32 or None) activates the superblock-compacted gather with
    tables (G, T, S)."""
    return _paged_scored(pool, tables, params, ctrls, method, n_ns,
                         tuple(out_hw), step, interpret, blk, sb_of)


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "auto", "colour_scale", "interpret",
                                    "blk"))
def render_byte_paged(pool, tables, params, ctrls, sps,
                      method: str = "near", n_ns: int = 1,
                      out_hw=(256, 256), step: int = 16,
                      auto: bool = True, colour_scale: int = 0,
                      interpret: bool = False, blk=None, sb_of=None):
    """Paged counterpart of `ops.warp.render_scenes_ctrl`: fused paged
    warp + mosaic, then the SAME composite/byte-scale epilogue per
    tile.  sps (N, 3) f32.  Returns PNG-ready uint8 (N, h, w) tiles."""
    from .warp import composite_scale
    canv, best = _paged_scored(pool, tables, params, ctrls, method,
                               n_ns, tuple(out_hw), step, interpret,
                               blk, sb_of)
    return jax.vmap(
        lambda c, b, sp: composite_scale(c, b > -jnp.inf, sp, auto,
                                         colour_scale))(canv, best, sps)


# --- fused expression epilogue (GSKY_EXPR_FUSE) -----------------------
#
# An expression lane carries MULTIPLE input namespaces per output pixel:
# slot i of the scored mosaic (canv[:, i] / best[:, i]) is expression
# variable i (ns_id rows were assigned in fingerprint slot order by the
# executor), so the epilogue is pure traced jnp on planes the paged
# program already holds — zero extra HBM round-trips between
# interpolation and scale-to-byte.  Lifted literals arrive as a traced
# (N, C) operand, so "nir > 0.3" and "nir > 0.7" are ONE program.

_EXPR_LOCK = __import__("threading").Lock()
_EXPR_FPS: set = set()
_EXPR_FUSED: dict = {}


def note_expr_program(fp_hash: str) -> None:
    """Record a fingerprint dispatched through the fused epilogue —
    `len` of the set is the gsky_expr_programs gauge (distinct
    structures, i.e. distinct compiled programs modulo shape axes)."""
    with _EXPR_LOCK:
        _EXPR_FPS.add(str(fp_hash))


def note_expr_fused(path: str) -> None:
    """Count one expression request routed through ``path`` (percall /
    wave / mesh / bucketed / unfused)."""
    with _EXPR_LOCK:
        _EXPR_FUSED[path] = _EXPR_FUSED.get(path, 0) + 1


def expr_fused_stats() -> dict:
    with _EXPR_LOCK:
        return {"programs": len(_EXPR_FPS), "paths": dict(_EXPR_FUSED)}


def reset_expr_fused_stats() -> None:
    """Zero the fused-path accounting — bench/soak A/B legs only."""
    with _EXPR_LOCK:
        _EXPR_FPS.clear()
        _EXPR_FUSED.clear()


def _fp_slot_ids(key) -> set:
    """Slot indices referenced by a normalized fingerprint key —
    contiguous 0..n-1 by construction (first-use numbering), but walked
    rather than assumed so validity never silently widens."""
    tag = key[0]
    if tag == "slot":
        return {key[1]}
    if tag == "const":
        return set()
    if tag == "un":
        return _fp_slot_ids(key[2])
    if tag == "bin":
        return _fp_slot_ids(key[2]) | _fp_slot_ids(key[3])
    if tag == "tern":
        out = set()
        for n in key[1:]:
            out |= _fp_slot_ids(n)
        return out
    if tag == "call":
        out = set()
        for n in key[2]:
            out |= _fp_slot_ids(n)
        return out
    raise ValueError(tag)


def expr_epilogue(canv, best, fp: tuple, consts):
    """The fused expression epilogue on a scored mosaic block: canv /
    best (N, n_ns, h, w) f32 (slot i of the mosaic is expression
    variable i), consts (N, C) f32 lifted literals -> (plane (N, h, w)
    f32, ok (N, h, w) bool).

    Evaluation reconstructs the `_emit` op sequence of the unfused
    `evaluate_expressions` leg (`ops.expr.eval_fingerprint`), so the
    f32 planes are bit-identical.  Nodata follows the merger: a pixel
    is valid iff valid in EVERY referenced slot and the result is
    finite (`CompiledExpr.eval_masked` semantics, op for op)."""
    from .expr import eval_fingerprint
    slot_ids = _fp_slot_ids(fp)
    n_slots = (max(slot_ids) + 1) if slot_ids else 0
    planes = [canv[:, i] for i in range(n_slots)]
    cbs = [consts[:, k][:, None, None] for k in range(consts.shape[1])]
    out = jnp.asarray(eval_fingerprint(fp, planes, cbs), jnp.float32)
    N, _, h, w = canv.shape
    out = jnp.broadcast_to(out, (N, h, w))
    ok = None
    for i in sorted(slot_ids):
        m = best[:, i] > -jnp.inf
        ok = m if ok is None else ok & m
    if ok is None:
        ok = jnp.ones((N, h, w), bool)
    ok = ok & jnp.isfinite(out)
    return jnp.where(ok, out, 0.0), ok


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "auto", "colour_scale", "fp",
                                    "interpret", "blk"))
def render_expr_paged(pool, tables, params, ctrls, sps, consts,
                      method: str = "near", n_ns: int = 1,
                      out_hw=(256, 256), step: int = 16,
                      auto: bool = True, colour_scale: int = 0,
                      fp: tuple = ("const", 0), interpret: bool = False,
                      blk=None, sb_of=None):
    """Fused paged warp + mosaic + EXPRESSION EPILOGUE + byte scale.

    Operands match `render_byte_paged` plus ``consts`` (N, C) f32 — the
    expression's lifted literals per lane (C may be 0).  ``fp`` (static)
    is the normalized fingerprint key from `ops.expr.fingerprint`; the
    jit key therefore holds the expression's STRUCTURE, never its
    source text or constants, so "nir > 0.3" and "nir > 0.7" are one
    program.  The byte tail is `scale_to_byte` per lane — exactly the
    call the unfused ows leg makes on `evaluate_expressions` output.
    Returns PNG-ready uint8 (N, h, w) tiles."""
    from .scale import scale_to_byte
    canv, best = _paged_scored(pool, tables, params, ctrls, method,
                               n_ns, tuple(out_hw), step, interpret,
                               blk, sb_of)
    plane, ok = expr_epilogue(canv, best, fp, consts)
    return jax.vmap(
        lambda d, o, sp: scale_to_byte(d, o, sp[0], sp[1], sp[2],
                                       colour_scale, auto))(plane, ok,
                                                            sps)


@jax.jit
def pool_inf_counts(pool):
    """Per-slot ±inf population of the page pool: (capacity,) int32.

    One on-device reduction + a capacity-sized readback — the cheap
    first pass of the pool integrity audit (pipeline/pages.py).  NaN is
    the legal validity encoding and saturates off-scene padding; inf is
    written by nothing in the staging path, so a nonzero count convicts
    the slot without reading its 256 KiB back."""
    return jnp.isinf(pool).sum(axis=(1, 2)).astype(jnp.int32)


def _paged_token(pool, tables, method, n_ns, out_hw, step, extra=()):
    """Versioned race token: leads with PAGED_TOKEN_VERSION so ledger
    replay can skip verdicts from other token schemes
    (`kernel_ledger.token_version_ok`).  Shape axes are the ragged
    pads (T, S) and the page geometry — NOT window shapes — so the
    token set stays a handful per method."""
    return (PAGED_TOKEN_VERSION, int(tables.shape[0]),
            int(tables.shape[1]), int(tables.shape[2]),
            int(pool.shape[1]), int(pool.shape[2]), str(method),
            int(n_ns), (int(out_hw[0]), int(out_hw[1])),
            int(step)) + tuple(extra)


def _plan_extras(pool, tables, blk, sb_of):
    """Token suffix for planner-shaped dispatches: appended ONLY when
    the dispatch deviates from the historical default, so existing
    pg1 ledger verdicts for the default path stay valid."""
    extra = ()
    if blk is not None and tuple(blk) != (_WARP_BLK, _WARP_BLK):
        extra += (("blk", int(blk[0]), int(blk[1])),)
    if sb_of is not None:
        extra += (("sb", int(sb_of.shape[0])),)
    return extra


def warp_scored_paged_raced(pool, tables, params, ctrls, method, n_ns,
                            out_hw, step, xla_thunk, blk=None,
                            sb_of=None):
    """(canvases (N, n_ns, h, w), best) — the paged kernel raced (via
    `run_with_fallback` + the durable ledger) against the caller's
    bucketed XLA closure, which must return the same (N, ...) shape."""
    note_gather(table_gather_bytes(tables, pool.shape[1],
                                   pool.shape[2]))

    def _pallas():
        return warp_scored_paged(pool, tables, params, ctrls, method,
                                 n_ns, out_hw, step,
                                 interpret=pallas_interpret(),
                                 blk=blk, sb_of=sb_of)

    return run_with_fallback(
        "warp_scored_paged", _pallas, xla_thunk,
        sync_token=_paged_token(pool, tables, method, n_ns, out_hw,
                                step,
                                extra=_plan_extras(pool, tables, blk,
                                                   sb_of)))


def render_byte_paged_raced(pool, tables, params, ctrls, sps, method,
                            n_ns, out_hw, step, auto, colour_scale,
                            xla_thunk, blk=None, sb_of=None):
    """uint8 (N, h, w) tiles — the fully fused paged warp+mosaic+scale
    raced against the caller's bucketed XLA closure (the GetMap hot
    path under GSKY_PAGED)."""
    note_gather(table_gather_bytes(tables, pool.shape[1],
                                   pool.shape[2]))

    def _pallas():
        return render_byte_paged(pool, tables, params, ctrls, sps,
                                 method, n_ns, out_hw, step, auto,
                                 colour_scale,
                                 interpret=pallas_interpret(),
                                 blk=blk, sb_of=sb_of)

    token = _paged_token(pool, tables, method, n_ns, out_hw, step,
                         extra=(bool(auto), int(colour_scale))
                         + _plan_extras(pool, tables, blk, sb_of))
    return run_with_fallback("warp_render_paged", _pallas, xla_thunk,
                             sync_token=token)


def _expr_token(pool, tables, method, n_ns, out_hw, step, auto,
                colour_scale, fp_hash, extra=()):
    """`ex1`-versioned race token for the fused expression program: the
    paged shape axes plus the scale statics and the expression's
    STRUCTURAL fingerprint hash — not its source text — so
    "nir > 0.3 ? 1 : 0" and "nir > 0.7 ? 1 : 0" share one verdict."""
    return (EXPR_TOKEN_VERSION, int(tables.shape[0]),
            int(tables.shape[1]), int(tables.shape[2]),
            int(pool.shape[1]), int(pool.shape[2]), str(method),
            int(n_ns), (int(out_hw[0]), int(out_hw[1])), int(step),
            bool(auto), int(colour_scale), str(fp_hash)) + tuple(extra)


def render_expr_paged_raced(pool, tables, params, ctrls, sps, consts,
                            method, n_ns, out_hw, step, auto,
                            colour_scale, fp, fp_hash, xla_thunk,
                            blk=None, sb_of=None):
    """uint8 (N, h, w) tiles — the fused paged warp+mosaic+expression+
    scale program raced against the caller's unfused XLA closure (which
    must produce byte-identical tiles via the per-band mosaic +
    `evaluate_expressions` + `scale_to_byte` reference)."""
    note_gather(table_gather_bytes(tables, pool.shape[1],
                                   pool.shape[2]))
    note_expr_program(fp_hash)

    def _pallas():
        return render_expr_paged(pool, tables, params, ctrls, sps,
                                 consts, method, n_ns, out_hw, step,
                                 auto, colour_scale, fp,
                                 interpret=pallas_interpret(),
                                 blk=blk, sb_of=sb_of)

    token = _expr_token(pool, tables, method, n_ns, out_hw, step, auto,
                        colour_scale, fp_hash,
                        extra=_plan_extras(pool, tables, blk, sb_of))
    return run_with_fallback("render_expr_paged", _pallas, xla_thunk,
                             sync_token=token)


# ---------------------------------------------------------------------------
# wave-level serving: output ring + stacked drill reduction
# ---------------------------------------------------------------------------
#
# The wave dispatcher (pipeline/waves.py) coalesces every eligible
# request of a scheduler tick into ONE paged program invocation.  Two
# device-side pieces live here next to the kernels they feed:
#
# - `OutputRing`: a persistent on-device output buffer per result lane
#   ((h, w) uint8 tiles, (n_ns, h, w) f32 canvases, ...).  Each wave's
#   result block is written into the ring with a DONATED
#   dynamic_update_slice (the previous ring buffer's storage is reused
#   in place, so steady-state waves allocate nothing), and the rows
#   just written are sliced back out as the device handle the readback
#   queue drains asynchronously.  Ordering is safe without host
#   synchronisation because take(k) enqueues on the same device stream
#   BEFORE the next put: by the time a later wave's donated write
#   lands, the slice that reads the old rows has already executed.
# - `wave_drill_stats`: the drill reduction over a stacked (K, B, N)
#   wave — per-row independent (axis=-1 masked mean), so a wave of K
#   drill requests is bit-identical to K per-call dispatches.


def wave_ring_rows() -> int:
    """Output-ring capacity in result rows (GSKY_WAVE_RING, default
    64): must cover at least one max-size wave; blocks larger than the
    ring bypass it (fresh allocation, correct but unamortised)."""
    try:
        r = int(os.environ.get("GSKY_WAVE_RING", "64"))
    except ValueError:
        r = 64
    return max(2, min(1024, r))


@functools.lru_cache(maxsize=1)
def _ring_put_fn():
    """Donated ring write: buf[base:base+n] = blk, reusing buf's
    storage in place.  Donation is skipped on the CPU backend (XLA:CPU
    ignores aliasing hints and warns on every call)."""
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(
        lambda buf, blk, base: jax.lax.dynamic_update_slice_in_dim(
            buf, blk, base, axis=0),
        donate_argnums=donate)


@functools.lru_cache(maxsize=1)
def _stage_refresh_fn():
    """Donated input-staging refresh: upload ``fresh`` into the HBM
    pages of a retired staging slot.  The slot buffer is donated (so
    the allocator reuses its storage instead of growing the arena per
    wave) and the device stream's WAR ordering guarantees the overwrite
    waits for the program still reading the old generation — the same
    ordering contract `_ring_put_fn` relies on.  Donation is skipped on
    the CPU backend (XLA:CPU ignores aliasing hints and warns)."""
    donate = (0,) if jax.default_backend() != "cpu" else ()

    def _refresh(slot, fresh):
        del slot     # donated: its storage backs the fresh upload
        return fresh

    return jax.jit(_refresh, donate_argnums=donate)


@functools.partial(jax.jit, static_argnames=("n",))
def _ring_take(buf, base, n: int):
    """Slice the n rows just written back out of the ring — enqueued
    on the device stream before any later put, so the donated
    overwrite can never clobber rows a reader still needs."""
    return jax.lax.dynamic_slice_in_dim(buf, base, n, axis=0)


class OutputRing:
    """Per-lane on-device output ring for wave results.

    A lane is one (tail shape, dtype) — e.g. every (256, 256) uint8
    tile wave shares a lane regardless of wave size.  `put(block)`
    writes block's rows at the cursor (wrapping to 0 when the block
    would run off the end — rows are never split) and returns the
    device slice holding exactly those rows.  Thread-safe; the wave
    scheduler calls it from the ticker thread only, but `stats()` is
    read from scrape threads."""

    def __init__(self, rows: int | None = None):
        self.rows = int(rows) if rows else wave_ring_rows()
        self._bufs = {}      # (tail_shape, dtype str) -> device buf
        self._cursor = {}    # same key -> next free row
        self._lock = __import__("threading").Lock()
        self.writes = 0
        self.bypassed = 0

    def put(self, block):
        """block (n, ...) on device -> device array of the same shape,
        backed by ring storage (or block itself when n > rows)."""
        n = int(block.shape[0])
        tail = tuple(int(d) for d in block.shape[1:])
        key = (tail, str(block.dtype))
        with self._lock:
            if n > self.rows:
                self.bypassed += 1
                return block
            buf = self._bufs.get(key)
            if buf is None:
                buf = jnp.zeros((self.rows,) + tail, block.dtype)
                self._cursor[key] = 0
            base = self._cursor[key]
            if base + n > self.rows:
                base = 0
            self._cursor[key] = base + n
            out = _ring_put_fn()(buf, block, jnp.int32(base))
            self._bufs[key] = out
            self.writes += 1
            return _ring_take(out, jnp.int32(base), n)

    def stats(self):
        with self._lock:
            return {"rows": self.rows, "lanes": len(self._bufs),
                    "writes": self.writes, "bypassed": self.bypassed}


@functools.partial(jax.jit, static_argnames=("pixel_count",))
def wave_drill_stats(data, valid, clip_lower=-3.0e38, clip_upper=3.0e38,
                     pixel_count: bool = False):
    """Stacked drill reduction: data/valid (K, B, N) -> (vals (K, B)
    f32, counts (K, B) int32).  The masked mean reduces over axis=-1
    only, so each wave row is independent and the stacked program is
    bit-identical to K per-call `masked_mean` dispatches."""
    from .drill import masked_mean_impl
    return masked_mean_impl(data, valid, clip_lower, clip_upper,
                            pixel_count, jnp)
