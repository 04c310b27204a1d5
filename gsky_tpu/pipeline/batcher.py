"""Cross-request render batching — SURVEY §2.8 P1's "async server in
front of a batching TPU executor", realised.

A fused single-tile render costs ~5 serial device-stream operations
(uploads, execution, pull); request concurrency cannot overlap them
because the device stream is one queue.  This batcher coalesces concurrent tile renders that share a
scene stack + static config into ONE vmapped dispatch
(`ops.warp.render_scenes_ctrl_many`), amortising the round trips N ways.

A request waits at most ``max_wait_s`` (default 3 ms) for companions.
Batches are padded to the next power of two (clamped to ``max_batch``,
which should itself be a power of two), so a key compiles at most
log2(max_batch)+1 specialisations while half-full batches don't pull
double their bytes.

**Default OFF** (`GSKY_RENDER_BATCH=1` enables): batching trades
transfer granularity for round-trip count, which wins when the
host<->device link is latency-bound and loses when it is
bandwidth-bound (a padded 16-tile pull moves more bytes than the tiles
it serves).  Which side a directly attached v5e falls on: not measured.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from ..obs import span as obs_span
from ..obs.metrics import BATCH_FLUSHES
from ..ops.warp import render_scenes_ctrl_many

_MAX_BATCH = 16

# EMA weight of the newest per-tile latency sample; ~5 samples to
# converge, enough inertia to ride out scheduler noise
_EMA_ALPHA = 0.3
# a padded size is past the knee when its per-tile latency exceeds the
# best smaller size by this factor (on a bandwidth-bound link x8
# batches once measured 2.26x the single-tile per-tile cost)
_KNEE_RATIO = 1.25


def batching_enabled() -> bool:
    return os.environ.get("GSKY_RENDER_BATCH", "0") == "1"


def _knee_cap() -> int:
    """Static coalesce cap (GSKY_RENDER_BATCH_MAX): operators who have
    already measured their link can pin the knee instead of waiting for
    the adaptive ratchet to find it."""
    try:
        v = int(os.environ.get("GSKY_RENDER_BATCH_MAX", _MAX_BATCH))
    except ValueError:
        return _MAX_BATCH
    return max(1, min(_MAX_BATCH, v))


class RenderBatcher:
    def __init__(self, max_batch: int = _MAX_BATCH,
                 max_wait_s: float = 0.003):
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self._lock = threading.Lock()
        # key -> (stack, [(ctrl, params, sp, win_raw, Future), ...], Timer)
        self._groups: Dict[tuple, Tuple[object, List, object]] = {}
        # batches dispatched with / without a union gather window
        # (engagement telemetry, mirroring WarpExecutor.win_engaged)
        self.win_batches = 0
        self.full_batches = 0
        # ragged paged flushes (GSKY_PAGED batching path) and the
        # running padding bill: bytes moved (uploads + pull + staged
        # gather source) that served pow2/bucket padding instead of
        # payload.  The paged path exists to shrink this figure; the
        # split is surfaced in /debug and as Prometheus gauges
        self.paged_batches = 0
        self.pad_waste_bytes = 0
        # adaptive throughput knee: coalescing amortises device round
        # trips, but past some batch size the padded pull's BYTES cost
        # more than the round trips saved (a bandwidth-bound link once
        # measured 9.29 ms/tile batched vs 4.10 single).  Per
        # padded-size EMAs of measured per-tile
        # latency feed a ratchet that caps the flush threshold at the
        # largest size still pulling its weight.
        self.knee = min(max_batch, _knee_cap())
        self._tile_ms: Dict[int, float] = {}   # padded size -> EMA ms
        self._tile_n: Dict[int, int] = {}      # samples per size
        from ..obs import tsan
        if tsan.enabled():
            # lockset tracking across flush timers / request threads
            # (docs/ANALYSIS.md "Race sanitizer")
            tsan.track(self, "RenderBatcher")

    def _observe(self, np_size: int, n_tiles: int, ms: float) -> None:
        """Fold one executed batch's per-tile latency into the EMA for
        its padded size and ratchet the knee down when this size
        measures slower than a smaller one.  The FIRST sample at each
        size is discarded: it carries the jit compile."""
        with self._lock:
            seen = self._tile_n.get(np_size, 0)
            self._tile_n[np_size] = seen + 1
            if seen == 0:
                return
            per_tile = ms / max(1, n_tiles)
            ema = self._tile_ms.get(np_size)
            self._tile_ms[np_size] = per_tile if ema is None else \
                (1 - _EMA_ALPHA) * ema + _EMA_ALPHA * per_tile
            if np_size <= 1:
                return
            smaller = [v for k, v in self._tile_ms.items()
                       if k < np_size]
            if smaller and self._tile_ms[np_size] > \
                    _KNEE_RATIO * min(smaller):
                self.knee = min(self.knee, max(1, np_size // 2))

    def note_oom(self) -> None:
        """Device-guard OOM relief hook (device_guard.register_oom_hook):
        halve the coalesce knee so the post-relief retry — and every
        later wave — dispatches smaller batches.  Like the latency
        ratchet this only moves down: a device that has proven it can
        exhaust HBM at a batch size should not be offered it again."""
        with self._lock:
            self.knee = max(1, self.knee // 2)

    def stats(self) -> Dict:
        """/debug `gather_window` payload: where the knee sits, the
        evidence (per padded-size per-tile EMA ms) behind it, batch
        engagement counters, and the cumulative padding bill."""
        with self._lock:
            return {"batch_knee": self.knee,
                    "tile_ms": {k: round(v, 3)
                                for k, v in sorted(self._tile_ms.items())},
                    "win_batches": self.win_batches,
                    "full_batches": self.full_batches,
                    "paged_batches": self.paged_batches,
                    "pad_waste_bytes": self.pad_waste_bytes}

    @staticmethod
    def _wait(fut: Future):
        """Block on a batch future, cancellation-aware: a request whose
        client disconnected stops waiting within one poll tick and
        unwinds (releasing its admission permit / stage slot) while the
        batch itself still executes for its surviving companions —
        cancelling one tile must never fail a shared flush."""
        from ..resilience import current_token
        tok = current_token()
        if tok is None:
            return fut.result()
        while True:
            try:
                return fut.result(timeout=0.05)
            except _FutTimeout:
                tok.check("batch")

    def render(self, key: tuple, stack, ctrl, params, sp,
               statics: tuple, win_raw=None) -> np.ndarray:
        """Submit one tile; blocks until its batch executes.  ``key``
        must capture everything that makes tiles batchable together:
        the scene-stack identity plus all static kernel parameters.
        win_raw: this tile's RAW footprint bounds (r_lo, r_hi, c_lo,
        c_hi) from `executor._gather_window` (or None); the flush
        unions them into one batch-wide bucketed window when every tile
        has bounds.  Returns the uint8 (H, W) tile as host numpy."""
        fut: Future = Future()
        flush_now = None
        with self._lock:
            entry = self._groups.get(key)
            if entry is None:
                timer = threading.Timer(self.max_wait_s,
                                        self._flush_key, (key, statics))
                timer.daemon = True
                self._groups[key] = (stack,
                                     [(ctrl, params, sp, win_raw, fut)],
                                     timer)
                timer.start()
            else:
                entry[1].append((ctrl, params, sp, win_raw, fut))
                if len(entry[1]) >= min(self.max_batch, self.knee):
                    flush_now = self._groups.pop(key)
        if flush_now is not None:
            # the pending wait timer would still fire, take the lock and
            # pop nothing — cancel it with the batch already claimed
            flush_now[2].cancel()
            self._execute(flush_now, statics, trigger="size")
        return self._wait(fut)

    def _union_window(self, items, stack):
        """One (win, win0) covering every tile's RAW footprint bounds,
        bucketed once — or (None, None) when any tile has no bounds or
        the union grows to the whole stack.  Coalesced tiles come from
        one map view, so the union is normally barely larger than a
        single tile's footprint."""
        if any(it[3] is None for it in items):
            return None, None
        from .executor import finish_window   # lazy: avoids cycle
        made = finish_window(
            min(it[3][0] for it in items),
            max(it[3][1] for it in items),
            min(it[3][2] for it in items),
            max(it[3][3] for it in items),
            int(stack.shape[1]), int(stack.shape[2]))
        return (None, None) if made is None else made

    def _flush_key(self, key: tuple, statics: tuple):
        with self._lock:
            entry = self._groups.pop(key, None)
        if entry is not None:
            self._execute(entry, statics, trigger="timer")

    def _execute(self, entry, statics: tuple, trigger: str = "size"):
        stack, items = entry[0], entry[1]
        method, n_ns, out_hw, step, auto, colour_scale = statics
        try:
            N = len(items)
            # pad to the next power of two (<= max_batch): bounded jit
            # specialisations per key (log2(max_batch) of them) while
            # keeping the padded PULL close to the real batch — padding
            # always to max_batch doubles transfer bytes for half-full
            # batches, and the pull is the expensive part of the link
            Np = 1
            while Np < N:
                Np *= 2
            Np = min(Np, self.max_batch)
            ctrls = np.stack([it[0] for it in items]
                             + [items[0][0]] * (Np - N))
            params = np.stack([it[1] for it in items]
                              + [items[0][1]] * (Np - N))
            sps = np.stack([it[2] for it in items]
                           + [items[0][2]] * (Np - N))
            win, win0 = self._union_window(items, stack)
            # padding bill (approximate, documented in docs/KERNELS.md):
            # pow2 batch-pad replicas of the uploads + the padded uint8
            # pull, plus the window-bucket overshoot of the gathered
            # source over the raw union footprint
            h, w = out_hw
            waste = (Np - N) * (h * w + ctrls[0].nbytes
                                + params[0].nbytes + sps[0].nbytes)
            if win is not None:
                raw = (max(it[3][1] for it in items)
                       - min(it[3][0] for it in items)) * \
                      (max(it[3][3] for it in items)
                       - min(it[3][2] for it in items))
                waste += max(0, win[0] * win[1] - raw) * 4 \
                    * int(stack.shape[0])
            with self._lock:
                if win is not None:
                    self.win_batches += 1
                else:
                    self.full_batches += 1
                self.pad_waste_bytes += int(waste)
            try:
                BATCH_FLUSHES.labels(
                    kind="windowed" if win is not None else "full").inc()
            except Exception:  # prom counter is telemetry only
                pass
            t0 = time.perf_counter()
            # traced only when flushed from a request thread (the timer
            # thread carries no request context — counters still count)
            with obs_span("batch.flush", trigger=trigger) as bsp:
                out = np.asarray(render_scenes_ctrl_many(
                    stack, jnp.asarray(ctrls), jnp.asarray(params),
                    jnp.asarray(sps), method, n_ns, out_hw, step, auto,
                    colour_scale, win=win,
                    win0=None if win is None else jnp.asarray(win0)))
                bsp.set(tiles=N, padded=Np, windowed=win is not None)
            self._observe(Np, N, (time.perf_counter() - t0) * 1e3)
            for i, it in enumerate(items):
                it[4].set_result(out[i])
        except Exception as e:  # pragma: no cover - propagate to callers
            for it in items:
                if not it[4].done():
                    it[4].set_exception(e)

    # -- ragged paged batching (GSKY_PAGED, ops/paged.py) -------------

    def render_paged(self, key: tuple, pool, tables, params16, ctrl,
                     sp, statics: tuple, real_pages: int,
                     fallback) -> np.ndarray:
        """Submit one tile whose gather windows are already staged in
        the page pool; blocks until its batch executes.  Unlike
        `render`, ``key`` carries NO scene-stack or window-shape
        identity — only the statics — so HETEROGENEOUS concurrent
        tiles (different scene sets, scene counts and window sizes)
        coalesce into one ragged dispatch; the flush pads the granule
        and page-slot axes to the batch maxima instead of shape
        buckets.  ``tables`` arrives PINNED (executor's
        `_paged_from_group`); the flush unpins after enqueue.
        ``fallback`` is (stack, params11, win, win0) for the race's
        per-tile bucketed XLA leg.

        Wave subsumption (GSKY_WAVES, pipeline/waves.py): when the
        wave scheduler is live, batcher flushes are subsumed by wave
        ticks — the executor routes eligible tiles to the wave path
        before the batching check, and a direct caller landing here
        joins the current wave instead of opening a batcher group
        (same ragged stacking, same unpin contract, plus the wave's
        cross-KIND coalescing and async readback)."""
        from .waves import active_waves, waves_enabled
        w = active_waves() if waves_enabled() else None
        if w is not None:
            def _percall():
                from .. import device_guard
                from ..ops.warp import render_scenes_ctrl
                from .executor import _dev_win0    # lazy: avoids cycle
                stack, bparams, bwin, bwin0 = fallback
                return np.asarray(device_guard.run(
                    "dispatch.bucketed",
                    lambda: render_scenes_ctrl(
                        stack, jnp.asarray(ctrl), jnp.asarray(bparams),
                        jnp.asarray(sp), *statics, win=bwin,
                        win0=_dev_win0(bwin0))))

            return w.render_byte(pool, tables, params16, ctrl, sp,
                                 statics, fallback, _percall)
        fut: Future = Future()
        item = (pool, tables, params16, ctrl, sp, int(real_pages),
                fallback, fut)
        flush_now = None
        with self._lock:
            entry = self._groups.get(key)
            if entry is None:
                timer = threading.Timer(self.max_wait_s,
                                        self._flush_key_paged,
                                        (key, statics))
                timer.daemon = True
                self._groups[key] = (None, [item], timer)
                timer.start()
            else:
                entry[1].append(item)
                if len(entry[1]) >= min(self.max_batch, self.knee):
                    flush_now = self._groups.pop(key)
        if flush_now is not None:
            flush_now[2].cancel()
            self._execute_paged(flush_now[1], statics, trigger="size")
        return self._wait(fut)

    def _flush_key_paged(self, key: tuple, statics: tuple):
        with self._lock:
            entry = self._groups.pop(key, None)
        if entry is not None:
            self._execute_paged(entry[1], statics, trigger="timer")

    def _execute_paged(self, items, statics: tuple,
                       trigger: str = "size"):
        method, n_ns, out_hw, step, auto, colour_scale = statics
        h, w = out_hw
        pool = items[0][0]
        try:
            from ..ops.paged import PARAMS_W, render_byte_paged_raced
            N = len(items)
            Np = 1
            while Np < N:
                Np *= 2
            Np = min(Np, self.max_batch)
            # ragged pad: granule axis to the batch's LARGEST tile
            # (per-item T is already pow2, so the max is too), page
            # slots likewise — no shape buckets, one compiled program
            # per (statics, T, S) point regardless of window shapes
            T = max(it[1].shape[0] for it in items)
            S = max(it[1].shape[1] for it in items)
            tables = np.zeros((Np, T, S), np.int32)
            params = np.zeros((Np, T, PARAMS_W), np.float32)
            params[:, :, 10] = -1.0     # ns_id: padding rows
            for i, it in enumerate(items):
                ti, si = it[1].shape
                tables[i, :ti, :si] = it[1]
                params[i, :ti] = it[2]
            ctrls = np.stack([it[3] for it in items]
                             + [items[0][3]] * (Np - N))
            sps = np.stack([it[4] for it in items]
                           + [items[0][4]] * (Np - N))
            real_pages = sum(it[5] for it in items)
            page_bytes = pool.page_rows * pool.page_cols * 4
            waste = (Np - N) * (h * w + ctrls[0].nbytes
                                + T * PARAMS_W * 4 + sps[0].nbytes) \
                + (Np * T * S - real_pages) * page_bytes
            with self._lock:
                self.paged_batches += 1
                self.pad_waste_bytes += int(waste)
            try:
                BATCH_FLUSHES.labels(kind="paged").inc()
            except Exception:  # prom counter is telemetry only
                pass

            def _xla():
                # per-tile bucketed XLA legs, stacked to the paged
                # output contract (runs only when racing or demoted)
                from ..ops.warp import render_scenes_ctrl
                from .executor import _dev_win0    # lazy: avoids cycle
                outs = []
                for it in items:
                    stack, bparams, bwin, bwin0 = it[6]
                    outs.append(render_scenes_ctrl(
                        stack, jnp.asarray(it[3]), jnp.asarray(bparams),
                        jnp.asarray(it[4]), method, n_ns, out_hw, step,
                        auto, colour_scale, win=bwin,
                        win0=_dev_win0(bwin0)))
                outs += [outs[0]] * (Np - N)
                return jnp.stack(outs)

            t0 = time.perf_counter()
            with obs_span("batch.flush", trigger=trigger) as bsp:
                with pool.locked_pool() as parr:
                    dev = render_byte_paged_raced(
                        parr, jnp.asarray(tables),
                        jnp.asarray(params.reshape(Np * T, PARAMS_W)),
                        jnp.asarray(ctrls), jnp.asarray(sps), method,
                        n_ns, out_hw, step, auto, colour_scale, _xla)
                # slice off the batch pad BEFORE the pull: the padded
                # tiles never cross the link
                out = np.asarray(dev[:N])
                bsp.set(tiles=N, padded=Np, paged=True)
            self._observe(Np, N, (time.perf_counter() - t0) * 1e3)
            for i, it in enumerate(items):
                it[7].set_result(out[i])
        except Exception as e:  # pragma: no cover - propagate to callers
            for it in items:
                if not it[7].done():
                    it[7].set_exception(e)
        finally:
            for it in items:
                try:
                    pool.unpin(it[1])
                except Exception:   # pragma: no cover
                    pass
