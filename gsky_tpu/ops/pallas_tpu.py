"""Pallas TPU kernels for the bandwidth-bound reduction hot ops.

Two of the pipeline's hot loops are pure streaming reductions — the
temporal mosaic (`processor/tile_merger.go:38-225`) and the drill masked
statistics (`worker/gdalprocess/drill.go:128-220`).  XLA already fuses
these well, but hand-tiled Pallas kernels keep every intermediate in
VMEM (no materialised `where` temporaries in HBM) and give explicit
control over block shapes, which matters once granule stacks grow to
hundreds of timesteps:

- `mosaic_first_valid_pallas`: first-valid-wins scan over the (priority
  sorted) granule axis, one VMEM-resident spatial block at a time.
- `masked_stats_pallas`: per-band masked + clipped sum/count over the
  flattened polygon window, accumulated across pixel chunks in VMEM.

Both match their XLA counterparts bit-for-bit (see
`tests/test_pallas.py`, which runs them in interpreter mode on CPU);
`use_pallas()` gates dispatch to real TPU backends (ops use the jnp
implementations elsewhere).

The fused warp kernels further down (and their paged forms in
`ops.paged`) are a different case: Mosaic refuses their gather, so
`warp_pallas_enabled()` keeps them off a real TPU — see its docstring.
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger("gsky.pallas")

# spatial block for the mosaic scan (f32 min tile is (8, 128))
_BLK_H = 128
_BLK_W = 128
# granule-axis bound for the mosaic kernel's VMEM budget: the block holds
# (T, 128, 128) f32 + int8 = T * 80 KiB; keep well under the 16 MiB limit
_MOSAIC_T_MAX = 128
# pixel chunk / row block for the stats accumulation.  Per-block VMEM:
# inputs (128, 2048) f32+i8 = 1.25 MiB (x2 for double buffering) plus
# accumulators (128, 2048) f32+i32 = 2 MiB -> ~4.5 MiB, independent of B.
_CHUNK = 2048
_ROWS = 128


def tpu_like_backend() -> bool:
    """True when the default backend is a TPU — the ONE place the
    backend name is tested; kernel form selection
    (`ops.warp._use_tapside`), the gather-window default and the pallas
    gate below all key off it."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """True when GSKY_PALLAS=interpret: run every pallas kernel in
    interpreter mode on whatever backend is present.  The CI/parity
    mode — CPU tier-1 drives the REAL dispatch paths (executor, drill)
    through the pallas kernels and checks answers, without a TPU."""
    return os.environ.get("GSKY_PALLAS", "1").lower() == "interpret"


def use_pallas() -> bool:
    """True when the pallas kernels should run (real TPU backend, or
    forced interpreter mode) and not disabled via GSKY_PALLAS=0."""
    v = os.environ.get("GSKY_PALLAS", "1")
    if v == "0":
        return False
    if pallas_interpret():
        return True
    return tpu_like_backend()


def warp_pallas_enabled() -> bool:
    """Gate for the gather-form fused warp kernels: bucketed
    `warp_scored` / `warp_render` below and the paged
    `warp_scored_paged` / `warp_render_paged` / `render_expr_paged` in
    `ops.paged`.  Their tap is a free-form gather out of a flattened
    VMEM block (``flat[idx]``).  Mosaic in the installed jax lowers ONE
    gather form — a 2-D ``take_along_axis`` whose operand, indices and
    result share a shape — and refuses these with "Only 2D gather is
    supported" (PERF.md "Bring-up").  So they are selectable in
    interpret mode only (the parity tests); on a real TPU the gate is
    False by what the code knows, not by a caught exception at request
    time, and the XLA bucketed leg serves.  A gather Mosaic accepts
    reopens this gate."""
    return os.environ.get("GSKY_PALLAS", "1") != "0" \
        and pallas_interpret()


# kernel name -> error text, for kernels whose pallas thunk raised this
# process (or whose ledger verdict is ``failed``): they stop being
# retried, and the entry is what /debug, prewarm and chip_smoke.py read
# to make the failure loud
_FAILED: dict = {}
# kernel name -> lowering modes ("mosaic" / "interpret") traced this
# process; chip_smoke.py fails on any "interpret" entry
_LOWERED: dict = {}
# (name, token) -> successful-dispatch count: proven pairs skip the
# materialising sync on most calls (see run_with_fallback)
_PROVEN: dict = {}
# every Nth dispatch of a proven (name, token) re-materialises inside
# the guard: a load-dependent runtime fault (HBM pressure) surfacing
# downstream of async dispatches would otherwise never reach `_FAILED`
# and every later request would re-dispatch the faulting kernel — this
# bounds that failure window to < _RESYNC requests
_RESYNC = 64
# (name, token) pairs whose pallas kernel MEASURED slower than the XLA
# fallback in the first-call race: a Pallas kernel that compiles and
# answers correctly can still lose to XLA's lowering at a given shape
# (grid/tiling mismatch), and "works" must not beat "faster"
_SLOW: set = set()
# demote only on a clear loss: both race legs carry the same dispatch
# overhead, so small kernel-time differences disappear into it and the
# default stays pallas
_RACE_MARGIN = 1.3


def _note_lowered(name: str, interpret: bool) -> None:
    """Trace-time record of how a kernel was lowered (the body of a
    jitted wrapper runs once per compile, which is exactly when the
    mode is decided)."""
    _LOWERED.setdefault(name, set()).add(
        "interpret" if interpret else "mosaic")


def kernel_state() -> dict:
    """The in-process kernel selection, by name: what /debug's
    ``kernels`` block and chip_smoke.py report."""
    return {
        "failed": dict(_FAILED),
        "demoted": sorted({n for n, _ in _SLOW}),
        "promoted": sorted({n for n, _ in _PROVEN}),
        "lowered": {n: sorted(m) for n, m in sorted(_LOWERED.items())},
        "warp_pallas_enabled": warp_pallas_enabled()}


def _proven_put(name, token, cnt):
    """Bounded insert: WMS/WCS request sizes are arbitrary, so a
    long-lived server would otherwise grow the map forever."""
    while len(_PROVEN) >= 4096:
        _PROVEN.pop(next(iter(_PROVEN)))
    _PROVEN[(name, token)] = cnt


def _timed_best(thunk, n=2):
    """(result, best seconds over ``n`` timed runs after one warm-up
    run) — the warm-up pays jit compilation, and min-of-n keeps a
    one-off stall (host scheduling) from mis-deciding the race with a
    false demotion."""
    import time as _time
    r = jax.block_until_ready(thunk())
    best = float("inf")
    for _ in range(n):
        t0 = _time.perf_counter()
        r = jax.block_until_ready(thunk())
        best = min(best, _time.perf_counter() - t0)
    return r, best


def _ledger_record(name, token, verdict, tp_ms=None, tx_ms=None,
                   reason=None):
    """Durable verdict append — guarded: the ledger is an optimisation
    and must never fail a dispatch."""
    try:
        from . import kernel_ledger
        kernel_ledger.record(name, token, verdict, tp_ms, tx_ms,
                             reason=reason)
    except Exception:  # noqa: BLE001
        pass


def _device_incident(e) -> bool:
    """True when an exception out of a pallas thunk convicts the DEVICE
    (OOM / runtime crash / hang), not the kernel.  Such failures must
    re-raise into the device guard instead of blacklisting the kernel:
    a ledger ``failed`` verdict written during a device incident would
    quarantine a perfectly good kernel until an operator deletes the
    file."""
    try:
        from ..device_guard import classify
        return classify(e) is not None
    except Exception:  # noqa: BLE001
        return False


def _kernel_failed(name, token, e) -> None:
    """Make a kernel failure loud and durable (see run_with_fallback)."""
    _FAILED[name] = f"{type(e).__name__}: {str(e)[:300]}"
    _ledger_record(name, token, "failed", reason="compile")
    log.error("pallas kernel %r failed at %s; answering from XLA and "
              "not retrying it", name, token, exc_info=e)


def reload_ledger() -> int:
    """Replay the persistent race ledger (`ops.kernel_ledger`) into the
    in-process race state, last-verdict-wins: ``demoted`` pre-populates
    `_SLOW` (the kernel is never re-raced at that token), ``promoted``
    pre-populates `_PROVEN` with count 0 (the first dispatch still
    materialises once, but skips the race), ``failed`` blacklists the
    kernel name.  Returns the number of records applied.  Deleting the
    ledger file and calling this (or restarting) re-races everything."""
    applied = 0
    try:
        from . import kernel_ledger
        for (name, tok), rec in kernel_ledger.entries().items():
            verdict = rec.get("verdict")
            if verdict == "failed":
                _FAILED.setdefault(name, "ledger verdict: failed")
                applied += 1
                continue
            token = kernel_ledger.decode_token(tok)
            if token is None:
                continue
            if not kernel_ledger.token_version_ok(name, token):
                # stale token scheme (e.g. a bucketed-era verdict in a
                # file now shared with the paged kernels): skip, the
                # kernel re-races under its current scheme
                continue
            if verdict == "demoted":
                while len(_SLOW) >= 4096:
                    _SLOW.pop()
                _SLOW.add((name, token))
                applied += 1
            elif verdict == "promoted":
                if (name, token) not in _PROVEN:
                    _proven_put(name, token, 0)
                applied += 1
    except Exception:  # noqa: BLE001 - a bad ledger must never wedge
        pass           # import (delete-file recovers)
    return applied


def run_with_fallback(name, pallas_thunk, xla_thunk, sync_token=None):
    """Run `pallas_thunk()` when the Pallas path is enabled and healthy,
    else `xla_thunk()`.  A Pallas failure that is not a device incident
    (VMEM over-allocation, a Mosaic lowering refusal) is LOUD: logged
    as an error with its traceback, kept by name in `_FAILED` (read by
    /debug, fatal in prewarm and chip_smoke.py) and written to the
    ledger as ``failed``; the request in hand is then answered from
    `xla_thunk()` and the kernel is not retried.  A kernel known not to
    compile must be kept off the serving path by its gate
    (`warp_pallas_enabled`), not by this handler.

    ``sync_token`` (e.g. the input shape): when given, the pallas result
    is materialised (block_until_ready) inside the guard on the FIRST
    call per (name, token) — a runtime fault on a new shape falls back
    here rather than surfacing downstream of the async dispatch — and on
    every ``_RESYNC``-th call thereafter, so a kernel that starts
    faulting under load still reaches the blacklist; in between,
    dispatches stay async so the pipeline doesn't serialise on a host
    sync per call.  The first call also RACES the two implementations
    (second-invocation timings, so compilation doesn't bias it) and
    demotes the pallas kernel at that (name, token) when it loses by
    more than ``_RACE_MARGIN`` — correctness-equivalent paths should
    compete on speed, not default on provenance.

    Race verdicts are durable: demotions/promotions append to the
    kernel ledger (`ops.kernel_ledger`, loaded at import), so a fresh
    worker process inherits every decided race instead of re-paying it
    (the r5 1.45 s warm-drill outlier was a per-process re-race).
    ``GSKY_PALLAS=interpret`` bypasses the race entirely — interpreter
    timings are meaningless and must not poison the ledger."""
    if name in _FAILED or not use_pallas():
        return xla_thunk()
    if pallas_interpret():
        # parity mode: always run the pallas kernel, materialised so a
        # kernel bug surfaces here (and falls back) instead of
        # downstream; no race and no TIMING ledger writes (interpreter
        # timings are meaningless) — but a kernel whose compile/lowering
        # RAISES is quarantined durably, exactly as in race mode: the
        # verdict is timing-independent and must survive a restart
        try:
            return jax.block_until_ready(pallas_thunk())
        except Exception as e:  # noqa: BLE001
            if _device_incident(e):
                raise       # the device guard owns this, not the kernel
            _kernel_failed(name, sync_token, e)
            return xla_thunk()
    if sync_token is not None and (name, sync_token) in _SLOW:
        return xla_thunk()
    try:
        if sync_token is not None \
                and (name, sync_token) not in _PROVEN:
            # first call per (kernel, shape): materialising correctness
            # sync AND a speed race against the XLA fallback — a pallas
            # kernel that measures clearly slower (tiling mismatch at
            # this shape) is demoted for the process, because the
            # fallback exists to give callers the best correct answer,
            # not to prefer pallas unconditionally.  Callers pass
            # BUCKETED shapes as tokens (padded pow2 batch x shape
            # buckets), so the race runs a bounded number of times, not
            # per request
            r, tp = _timed_best(pallas_thunk)
            _proven_put(name, sync_token, 2)
            try:
                rx, tx = _timed_best(xla_thunk)
            except Exception:  # noqa: BLE001 - race leg only
                return r       # XLA leg failing never demotes pallas
            if tp > tx * _RACE_MARGIN:
                # drop the _PROVEN entry: if _SLOW ever evicts this
                # key, the next call re-races instead of finding a
                # "proven" entry and dispatching the slow kernel async
                _PROVEN.pop((name, sync_token), None)
                while len(_SLOW) >= 4096:
                    _SLOW.pop()
                _SLOW.add((name, sync_token))
                _ledger_record(name, sync_token, "demoted",
                               tp * 1e3, tx * 1e3)
                import warnings
                warnings.warn(
                    f"pallas kernel {name!r} measured {tp * 1e3:.1f} ms"
                    f" vs XLA {tx * 1e3:.1f} ms at {sync_token}; using"
                    " XLA for this shape", stacklevel=2)
                return rx
            _ledger_record(name, sync_token, "promoted",
                           tp * 1e3, tx * 1e3)
            return r
        r = pallas_thunk()
        if sync_token is not None:
            cnt = _PROVEN.get((name, sync_token), 0)
            if cnt % _RESYNC == 0:
                r = jax.block_until_ready(r)
            _proven_put(name, sync_token, cnt + 1)
        return r
    except Exception as e:  # noqa: BLE001 - any compile/runtime failure
        if _device_incident(e):
            raise           # device incident: classify + recover above,
            # and never let it masquerade as a kernel compile failure
        _kernel_failed(name, sync_token, e)
        return xla_thunk()


# ---------------------------------------------------------------------------
# mosaic: first valid along the (priority-sorted) granule axis
# ---------------------------------------------------------------------------

def _mosaic_kernel(stack_ref, valid_ref, out_ref, ok_ref):
    # T is a static block dim -> unrolled scan (dynamic leading-axis
    # indexing inside fori_loop trips the Mosaic compiler on v5e)
    T = stack_ref.shape[0]
    out = jnp.zeros(out_ref.shape, out_ref.dtype)
    done = jnp.zeros(out_ref.shape, jnp.bool_)
    for t in range(T):
        x = stack_ref[t]
        v = valid_ref[t] != 0
        take = v & ~done
        out = jnp.where(take, x, out)
        done = done | v
    out_ref[:] = out
    ok_ref[:] = done.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mosaic_first_valid_pallas(stack, valid, interpret: bool = False):
    """stack (T, H, W) f32 in priority order, valid (T, H, W) bool/int8.
    Returns (out (H, W) f32, ok (H, W) bool) — same contract as
    `ops.mosaic.mosaic_first_valid` for 2D canvases.  H and W are padded
    to block multiples internally."""
    _note_lowered("mosaic_first_valid", interpret)
    T, H, W = stack.shape
    Hp = -(-H // _BLK_H) * _BLK_H
    Wp = -(-W // _BLK_W) * _BLK_W
    stack = jnp.pad(stack.astype(jnp.float32),
                    ((0, 0), (0, Hp - H), (0, Wp - W)))
    valid8 = jnp.pad(valid.astype(jnp.int8),
                     ((0, 0), (0, Hp - H), (0, Wp - W)))
    grid = (Hp // _BLK_H, Wp // _BLK_W)
    out, ok = pl.pallas_call(
        _mosaic_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, _BLK_H, _BLK_W), lambda i, j: (0, i, j)),
            pl.BlockSpec((T, _BLK_H, _BLK_W), lambda i, j: (0, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((_BLK_H, _BLK_W), lambda i, j: (i, j)),
            pl.BlockSpec((_BLK_H, _BLK_W), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Hp, Wp), jnp.float32),
            jax.ShapeDtypeStruct((Hp, Wp), jnp.int8),
        ],
        interpret=interpret,
    )(stack, valid8)
    return out[:H, :W], ok[:H, :W] != 0


# ---------------------------------------------------------------------------
# drill: masked + clipped per-band sum/count
# ---------------------------------------------------------------------------

def _stats_kernel(data_ref, valid_ref, clip_ref, sum_ref, cnt_ref):
    j = pl.program_id(1)
    x = data_ref[:]
    v = valid_ref[:] != 0
    inclip = v & (x >= clip_ref[0]) & (x <= clip_ref[1])

    @pl.when(j == 0)
    def _init():
        sum_ref[:] = jnp.zeros(sum_ref.shape, sum_ref.dtype)
        cnt_ref[:] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)

    sum_ref[:] += jnp.where(inclip, x, 0.0)
    cnt_ref[:] += inclip.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_stats_pallas(data, valid, clip_lower=-3.0e38, clip_upper=3.0e38,
                        interpret: bool = False):
    """data (B, N) f32, valid (B, N) bool -> (sums (B,), counts (B,)) of
    valid pixels within [clip_lower, clip_upper].  Both axes are tiled:
    the pixel axis streams through VMEM in `_CHUNK` columns and the band/
    timestep axis in `_ROWS`-row blocks, so per-block VMEM is a constant
    ~4.5 MiB regardless of B (holding the full (B, chunk) accumulator
    for B=1000 asked for 19.5 MB of a 16 MB VMEM).  The (Bp, chunk)
    partial accumulator lives in HBM between grid steps and is reduced at
    the end (one tiny XLA sum)."""
    _note_lowered("masked_stats", interpret)
    B, N = data.shape
    Np = -(-N // _CHUNK) * _CHUNK
    Bp = -(-B // _ROWS) * _ROWS
    data = jnp.pad(data.astype(jnp.float32),
                   ((0, Bp - B), (0, Np - N)))
    valid8 = jnp.pad(valid.astype(jnp.int8),
                     ((0, Bp - B), (0, Np - N)))
    clip = jnp.asarray([clip_lower, clip_upper], jnp.float32)
    psum, pcnt = pl.pallas_call(
        _stats_kernel,
        grid=(Bp // _ROWS, Np // _CHUNK),
        in_specs=[
            pl.BlockSpec((_ROWS, _CHUNK), lambda b, j: (b, j)),
            pl.BlockSpec((_ROWS, _CHUNK), lambda b, j: (b, j)),
            pl.BlockSpec((2,), lambda b, j: (0,)) if interpret else
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((_ROWS, _CHUNK), lambda b, j: (b, 0)),
            pl.BlockSpec((_ROWS, _CHUNK), lambda b, j: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, _CHUNK), jnp.float32),
            jax.ShapeDtypeStruct((Bp, _CHUNK), jnp.int32),
        ],
        interpret=interpret,
    )(data, valid8, clip)
    return jnp.sum(psum, axis=-1)[:B], jnp.sum(pcnt, axis=-1)[:B]


# ---------------------------------------------------------------------------
# fused warp-render: windowed gather + interpolate + mosaic, one kernel
# ---------------------------------------------------------------------------

# output tile block (f32 min tile is (8, 128); 128x128 balances VMEM
# against grid overhead for 256-px tiles)
_WARP_BLK = 128
# VMEM ceiling for one grid step's working set: the windowed granule
# block (double-buffered by the pipeline) + the per-namespace
# accumulators + the coordinate blocks must stay well inside the
# ~16 MiB per-core VMEM
_WARP_VMEM_BUDGET = 10 * 1024 * 1024


def _warp_vmem_bytes(wr: int, wc: int, n_ns: int, blk=None) -> int:
    bh, bw = blk if blk is not None else (_WARP_BLK, _WARP_BLK)
    wrp = -(-wr // 8) * 8
    wcp = -(-wc // 128) * 128
    src = wrp * wcp * 4 * 2                 # (1, WRp, WCp) f32, x2 DMA
    acc = n_ns * bh * bw * 4 * 2 * 2        # canv+best, x2
    grids = bh * bw * 4 * 2 * 2             # sx+sy, x2
    return src + acc + grids


def warp_pallas_ok(wr: int, wc: int, n_ns: int, blk=None) -> bool:
    """Eligibility gate for the fused warp kernel, checked BEFORE
    `run_with_fallback`: a kernel Mosaic refuses
    (`warp_pallas_enabled`) or an over-budget gather window goes
    straight to XLA rather than through the failure handler.  ``blk``
    is the (block_h, block_w) output tile the cost model picked; None
    keeps the historical fixed `_WARP_BLK` square."""
    if not warp_pallas_enabled():
        return False
    return _warp_vmem_bytes(int(wr), int(wc), int(n_ns), blk) \
        <= _WARP_VMEM_BUDGET


def _warp_render_kernel(method: str, n_ns: int, WR: int, WC: int,
                        WRp: int, WCp: int):
    """Kernel-body closure over the static config.  Grid (by, bx, t)
    with the granule axis t INNERMOST: the stack BlockSpec indexes by t,
    so the pallas pipeline DMAs granule t+1's gather window HBM->VMEM
    while granule t computes — double-buffered overlapped-tile staging
    (the model-based warp-tiling discipline), with the per-namespace
    canvas/priority accumulators VMEM-resident across the whole t sweep
    (initialised at t == 0, the `_stats_kernel` pattern).

    Per granule the body mirrors `ops.warp._warp_scenes_scored` op for
    op: full-frame affine coords -> true-extent oob NaN-poisoning ->
    window rebase -> taps with tap-side validity (finite and != nodata)
    -> running strictly-greater priority mosaic (identical winners to
    XLA's argmax because priorities are strictly unique by contract)."""

    def kernel(params_ref, sx_ref, sy_ref, stack_ref, canv_ref, best_ref):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            canv_ref[:] = jnp.zeros(canv_ref.shape, canv_ref.dtype)
            best_ref[:] = jnp.full(best_ref.shape, -jnp.inf,
                                   best_ref.dtype)

        def p(k):
            return params_ref[t, k]

        sx = sx_ref[:]
        sy = sy_ref[:]
        cols = (p(0) + p(1) * sx + p(2) * sy) - 0.5
        rows = (p(3) + p(4) * sx + p(5) * sy) - 0.5
        oob = (rows < -0.5) | (rows > p(6) - 0.5) \
            | (cols < -0.5) | (cols > p(7) - 0.5)
        rows = jnp.where(oob, jnp.nan, rows)
        rows = rows - p(11)     # window-origin rebase (exact: int <=
        cols = cols - p(12)     # 4096 off an f32 coord < 2^12)
        flat = stack_ref[0].reshape(WRp * WCp)
        nd = p(8)

        def tap(ri, ci, inb):
            # flat index with the PADDED row stride addresses the same
            # element as the unpadded (WR, WC) window for every clipped
            # index, so values match `_gather2d` bit for bit
            v = flat[ri * WCp + ci]
            ok = inb & jnp.isfinite(v) & (v != nd)
            return jnp.where(ok, v, 0.0), ok

        if method in ("near", "nearest"):
            ri = jnp.floor(rows + (0.5 + 1e-10)).astype(jnp.int32)
            ci = jnp.floor(cols + (0.5 + 1e-10)).astype(jnp.int32)
            inb = (ri >= 0) & (ri < WR) & (ci >= 0) & (ci < WC) \
                & jnp.isfinite(rows) & jnp.isfinite(cols)
            val, ok = tap(jnp.clip(ri, 0, WR - 1),
                          jnp.clip(ci, 0, WC - 1), inb)
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
                inb = (ri >= 0) & (ri < WR) & (ci >= 0) & (ci < WC)
                v, okt = tap(jnp.clip(ri, 0, WR - 1),
                             jnp.clip(ci, 0, WC - 1), inb)
                okf = okt.astype(jnp.float32)
                acc = acc + wt * okf * v
                wacc = wacc + wt * okf
            ok = finite & (wacc > thresh)
            val = acc / jnp.where(wacc > thresh, wacc, 1.0)

        prio = p(9)
        ns = p(10)
        for n in range(n_ns):   # static unroll (n_ns is pow2-bounded)
            member = ns == jnp.float32(n)
            s_n = jnp.where(member & ok, prio, -jnp.inf)
            b = best_ref[n, :, :]
            take = s_n > b      # strict: first-seen wins ties, matching
            canv_ref[n, :, :] = jnp.where(take, val,    # argmax order
                                          canv_ref[n, :, :])
            best_ref[n, :, :] = jnp.where(take, s_n, b)

    return kernel


def _warp_scored_pallas(stack, ctrl, params, method, n_ns, out_hw, step,
                        win, win0, interpret, blk=None):
    """Shared core: XLA prologue (ctrl-grid upsample, window slice,
    f32 + lane-alignment padding) feeding one fused pallas_call.
    Returns (canv (n_ns, h, w) f32, best (n_ns, h, w) f32, -inf =
    invalid) — the `warp_scenes_ctrl_scored` contract.  ``blk`` is the
    (block_h, block_w) output tile (cost-model chosen, mult-of-8 x
    mult-of-128); None keeps the fixed `_WARP_BLK` square."""
    from .warp import _bilerp_grid, _window_slice
    _note_lowered("warp_scored", interpret)
    bh, bw = blk if blk is not None else (_WARP_BLK, _WARP_BLK)
    h, w = out_hw
    sx = _bilerp_grid(ctrl[0], h, w, step)
    sy = _bilerp_grid(ctrl[1], h, w, step)
    if win is not None:
        stack, r0f, c0f = _window_slice(stack, win, win0, axis=1)
        WR, WC = int(win[0]), int(win[1])
    else:
        WR, WC = int(stack.shape[1]), int(stack.shape[2])
        r0f = c0f = jnp.float32(0.0)
    B = int(stack.shape[0])
    WRp = -(-WR // 8) * 8
    WCp = -(-WC // 128) * 128
    stackf = stack.astype(jnp.float32)
    if (WRp, WCp) != (WR, WC):
        stackf = jnp.pad(stackf, ((0, 0), (0, WRp - WR), (0, WCp - WC)))
    Hp = -(-h // bh) * bh
    Wp = -(-w // bw) * bw
    if (Hp, Wp) != (h, w):
        sx = jnp.pad(sx, ((0, Hp - h), (0, Wp - w)))
        sy = jnp.pad(sy, ((0, Hp - h), (0, Wp - w)))
    # params slots 11/12 carry the window origins so the kernel's only
    # traced per-granule state is one SMEM row
    pp = jnp.zeros((B, 16), jnp.float32)
    pp = pp.at[:, :11].set(params[:, :11].astype(jnp.float32))
    pp = pp.at[:, 11].set(r0f)
    pp = pp.at[:, 12].set(c0f)
    kernel = _warp_render_kernel(method, n_ns, WR, WC, WRp, WCp)
    if interpret:
        params_spec = pl.BlockSpec((B, 16), lambda i, j, t: (0, 0))
    else:
        params_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    canv, best = pl.pallas_call(
        kernel,
        grid=(Hp // bh, Wp // bw, B),
        in_specs=[
            params_spec,
            pl.BlockSpec((bh, bw), lambda i, j, t: (i, j)),
            pl.BlockSpec((bh, bw), lambda i, j, t: (i, j)),
            pl.BlockSpec((1, WRp, WCp), lambda i, j, t: (t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_ns, bh, bw),
                         lambda i, j, t: (0, i, j)),
            pl.BlockSpec((n_ns, bh, bw),
                         lambda i, j, t: (0, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_ns, Hp, Wp), jnp.float32),
            jax.ShapeDtypeStruct((n_ns, Hp, Wp), jnp.float32),
        ],
        interpret=interpret,
    )(pp, sx, sy, stackf)
    return canv[:, :h, :w], best[:, :h, :w]


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "win", "interpret", "blk"))
def warp_scenes_scored_pallas(stack, ctrl, params, method: str = "near",
                              n_ns: int = 1, out_hw=(256, 256),
                              step: int = 16, win=None, win0=None,
                              interpret: bool = False, blk=None):
    """Pallas counterpart of `ops.warp.warp_scenes_ctrl_scored`: the
    fused warp-gather replacing XLA's gather lowering on the mosaic hot
    path.  Same signature contract (stack (B, sh, sw) native, ctrl
    (2, gh, gw) f32, params (B, 11) f32, optional static win + traced
    win0) and same outputs (canvases, best-priority, -inf = invalid);
    parity is tested bit-exact for nearest and <= 2 ulp for
    interpolated methods (tests/test_warp_pallas.py).  ``blk``
    (static (bh, bw) or None) retiles the output grid; the kernel body
    is block-shape-agnostic so results are identical for any blk."""
    return _warp_scored_pallas(stack, ctrl, params, method, n_ns,
                               tuple(out_hw), step, win, win0, interpret,
                               blk)


@functools.partial(jax.jit,
                   static_argnames=("method", "n_ns", "out_hw", "step",
                                    "auto", "colour_scale", "win",
                                    "interpret", "blk"))
def render_scenes_pallas(stack, ctrl, params, scale_params,
                         method: str = "near", n_ns: int = 1,
                         out_hw=(256, 256), step: int = 16,
                         auto: bool = True, colour_scale: int = 0,
                         win=None, win0=None, interpret: bool = False,
                         blk=None):
    """Pallas counterpart of `ops.warp.render_scenes_ctrl`: fused warp +
    mosaic in the kernel, then the SAME composite/byte-scale epilogue
    the XLA render uses (`ops.warp.composite_scale` on the 64 KB
    canvases — cross-block min/max doesn't fit a one-pass grid, and at
    canvas size the epilogue is noise).  Returns the PNG-ready uint8
    (h, w) tile."""
    from .warp import composite_scale
    canv, best = _warp_scored_pallas(stack, ctrl, params, method, n_ns,
                                     tuple(out_hw), step, win, win0,
                                     interpret, blk)
    return composite_scale(canv, best > -jnp.inf, scale_params, auto,
                           colour_scale)


def _warp_token(stack, win, out_hw, method, n_ns, step, blk=None):
    """Bucketed race token: stacks arrive bucket-padded and windows
    bucket-sized, so the token set — and with it the race count and the
    ledger cardinality — is bounded.  Plain ints/strs/tuples only (the
    ledger round-trips tokens through repr/literal_eval).  A
    cost-model block shape appends a ("blk", bh, bw) suffix ONLY when
    non-default, so historical default-path verdicts stay valid."""
    tok = (tuple(int(d) for d in stack.shape), str(stack.dtype),
           None if win is None else (int(win[0]), int(win[1])),
           (int(out_hw[0]), int(out_hw[1])), str(method), int(n_ns),
           int(step))
    if blk is not None and tuple(blk) != (_WARP_BLK, _WARP_BLK):
        tok = tok + (("blk", int(blk[0]), int(blk[1])),)
    return tok


def _plan_blk(out_hw, win, method, n_ns, T=1):
    """Cost-model block shape for a bucketed-window dispatch, consulted
    lazily so ops never import the pipeline at module load.  The model
    keys on the OUTPUT extent (what the grid tiles) and gates VMEM on
    the WINDOW extent (what each step resident-loads).  Returns None
    (= fixed `_WARP_BLK` square, today's behaviour) whenever the
    planner is off or unavailable — the import is guarded because the
    block shape is an optimisation, never a correctness dependency."""
    if not warp_pallas_enabled():
        return None     # XLA-only serving: no pallas grid to shape
    try:
        from ..pipeline import autoplan
        if not autoplan.plan_enabled():
            return None
        return autoplan.plan_block(
            int(out_hw[0]), int(out_hw[1]), int(n_ns), str(method),
            T=int(T), S=0, win=(int(win[0]), int(win[1])))
    except Exception:  # noqa: BLE001 - planner unavailable: default blk
        return None


def warp_scored_raced(stack, ctrl_dev, params_dev, method, n_ns, out_hw,
                      step, win=None, win0_dev=None, blk=None):
    """(canvases, best) — the fused pallas warp raced (via
    `run_with_fallback` + the durable ledger) against
    `ops.warp.warp_scenes_ctrl_scored`.  The executor's scene and
    decoded-window mosaic paths dispatch here."""
    from .warp import warp_scenes_ctrl_scored

    def _xla():
        return warp_scenes_ctrl_scored(stack, ctrl_dev, params_dev,
                                       method, n_ns, out_hw, step,
                                       win=win, win0=win0_dev)

    wr, wc = win if win is not None else stack.shape[1:3]
    if blk is None:
        blk = _plan_blk(out_hw, (wr, wc), method, n_ns,
                        T=int(stack.shape[0]))
    if not warp_pallas_ok(wr, wc, n_ns, blk):
        return _xla()

    def _pallas():
        return warp_scenes_scored_pallas(
            stack, ctrl_dev, params_dev, method, n_ns, out_hw, step,
            win=win, win0=win0_dev, interpret=pallas_interpret(),
            blk=blk)

    return run_with_fallback(
        "warp_scored", _pallas, _xla,
        sync_token=_warp_token(stack, win, out_hw, method, n_ns, step,
                               blk))


def render_byte_raced(stack, ctrl_dev, params_dev, sp_dev, method, n_ns,
                      out_hw, step, auto, colour_scale, win=None,
                      win0_dev=None, blk=None):
    """uint8 tile — the fully fused pallas warp+mosaic+scale raced
    against `ops.warp.render_scenes_ctrl` (the GetMap hot path)."""
    from .warp import render_scenes_ctrl

    def _xla():
        return render_scenes_ctrl(stack, ctrl_dev, params_dev, sp_dev,
                                  method, n_ns, out_hw, step, auto,
                                  colour_scale, win=win, win0=win0_dev)

    wr, wc = win if win is not None else stack.shape[1:3]
    if blk is None:
        blk = _plan_blk(out_hw, (wr, wc), method, n_ns,
                        T=int(stack.shape[0]))
    if not warp_pallas_ok(wr, wc, n_ns, blk):
        return _xla()

    def _pallas():
        return render_scenes_pallas(stack, ctrl_dev, params_dev, sp_dev,
                                    method, n_ns, out_hw, step, auto,
                                    colour_scale, win=win, win0=win0_dev,
                                    interpret=pallas_interpret(),
                                    blk=blk)

    token = _warp_token(stack, win, out_hw, method, n_ns, step, blk) \
        + (bool(auto), int(colour_scale))
    return run_with_fallback("warp_render", _pallas, _xla,
                             sync_token=token)


# durable race verdicts from previous processes apply from the first
# dispatch of this one (delete the ledger file to re-race everything;
# see ops/kernel_ledger.py for path resolution and format)
reload_ledger()
