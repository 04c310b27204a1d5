"""Wave-level device serving: amortise the per-dispatch host tax
across whole admission waves.

Per-call serving pays a host-side dispatch tax (upload enqueue,
program launch, sync) PER CALL while the device sits idle; what that
tax is on a directly attached v5e is not measured (root PERF.md).  The
ragged paged kernels (ops/paged.py) already serve any tile shape from
one program, so nothing but the call convention forces tax-per-tile.

This module stops dispatching per tile/drill.  Every scheduler tick,
everything currently eligible — WMS tile renders, drill reductions,
WCS export blocks, mixed — is coalesced into one paged program
invocation per result kind:

- requests enqueue a wave entry (payload + per-request completion
  future) and block on the future, cancellation-aware;
- the ASSEMBLY stage waits ``GSKY_WAVE_TICK_MS`` for companions, then
  drains up to ``GSKY_WAVE_MAX`` entries (clamped by the brownout
  level under pressure), drops cancelled entries at assembly, groups
  by (kind, statics, pool), runs the dataflow planner
  (`autoplan.plan_wave_group`), stacks page tables and param rows on
  a pow2 leading dim — padding rows carrying
  ns_id -1 so every real row is bit-independent of its wave
  companions — and uploads the stacks into a persistent
  double-buffered input `_StagingRing` (two donated staging slots per
  (kind, statics) program family);
- the DISPATCH stage pops staged waves off a host-written wave queue
  and enqueues the device programs back-to-back, so wave N+1 plans,
  stacks, and uploads while wave N executes, closing the inter-wave
  host gap (docs/PERF.md "Continuous device occupancy");
- results land in an on-device `OutputRing` (donated in/out buffers,
  ops/paged.py) that persists ACROSS waves — pow2-padded result
  blocks reuse the same ring lanes wave after wave — and a readback
  queue drains them asynchronously on a third thread with ONE batched
  `device_guard.guarded_readback` per wave (the integrity probe runs
  once on the stacked output), so consumers in `tile_stages` /
  `export` / `drill` never block the NEXT wave's dispatch;
- every staged upload runs under `device_guard.run("wave.stage")` and
  every group dispatch under `device_guard.run("dispatch.wave")`; the
  watchdog supervises both in-flight waves and attributes a
  staging-side hang to the EXECUTING wave (supervisor.execution_window
  — a device_put queued behind a wedged kernel is not the staging
  wave's fault).  An incident fails the wave's requests over
  INDIVIDUALLY (each entry re-renders through its per-call bucketed
  closure), never as a wave.

A tick that carries both tiles and drills dispatches one program per
(kind, statics) group — the mixed wave amortises the tick, admission
and readback machinery; kinds cannot share one XLA program without a
mega-kernel.  ``GSKY_WAVES=0`` restores per-call dispatch
byte-identically, and ``GSKY_WAVE_PIPELINE=0`` restores the
synchronous ticker (assemble + dispatch on one thread) byte-identically
— the pipelined path reuses the exact same stacking and kernel code,
only the thread it runs on changes — see tests/test_waves.py.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import CancelledError, Future
from concurrent.futures import TimeoutError as _FutTimeout
from queue import Empty, Queue
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from .. import device_guard
from ..obs.metrics import (WAVE_ASSEMBLY_MS, WAVE_DISPATCHES,
                           WAVE_GAP_MS, WAVE_OCCUPANCY, WAVE_STAGED)


def waves_enabled() -> bool:
    """Wave dispatch gate: on by default wherever the paged kernels
    serve (GSKY_PAGED + pallas available); GSKY_WAVES=0 restores
    per-call dispatch byte-identically.  Plain-CPU XLA serving keeps
    per-call dispatch — the wave stacking rides the paged programs."""
    from ..ops.paged import paged_enabled
    return os.environ.get("GSKY_WAVES", "1") != "0" and paged_enabled()


def wave_max() -> int:
    """Hard cap on entries per wave (GSKY_WAVE_MAX, default 16) —
    bounds the stacked program's memory footprint and the blast radius
    of one device incident."""
    try:
        v = int(os.environ.get("GSKY_WAVE_MAX", "16"))
    except ValueError:
        v = 16
    return max(1, min(64, v))


def wave_tick_ms() -> float:
    """Coalescing window (GSKY_WAVE_TICK_MS, default 2 ms): how long
    the ticker waits for companions after the first entry arrives.
    Zero dispatches back-to-back (still coalescing whatever queued
    while the previous wave ran)."""
    try:
        v = float(os.environ.get("GSKY_WAVE_TICK_MS", "2"))
    except ValueError:
        v = 2.0
    return max(0.0, min(100.0, v))


def wave_pipeline_enabled() -> bool:
    """Two-stage pipeline gate (GSKY_WAVE_PIPELINE, default on):
    assembly stages wave N+1's plan/stack/uploads while wave N
    executes.  ``0`` restores the synchronous ticker byte-identically
    — same stacking, same kernels, one thread.  Read per tick so tests
    and operators can flip it live."""
    return os.environ.get("GSKY_WAVE_PIPELINE", "1") != "0"


def wave_queue_depth() -> int:
    """Staged waves the assembly stage may run AHEAD of dispatch
    (GSKY_WAVE_QUEUE, default 1, clamp 1..4): 1 is classic double
    buffering — one wave executing, one staged.  Brownout clamps the
    effective depth to 1 (pressure applies to the queue, the same
    lever `_effective_max` applies to occupancy)."""
    try:
        v = int(os.environ.get("GSKY_WAVE_QUEUE", "1"))
    except ValueError:
        v = 1
    return max(1, min(4, v))


def wave_stage_slots() -> int:
    """Donated staging slots per (kind, statics) program family
    (GSKY_WAVE_STAGE_SLOTS, default 2, clamp 2..4).  A slot holds one
    wave's uploaded input stacks from stage-time until its program is
    enqueued; two slots let wave N+1 upload while wave N's inputs are
    still feeding the device."""
    try:
        v = int(os.environ.get("GSKY_WAVE_STAGE_SLOTS", "2"))
    except ValueError:
        v = 2
    return max(2, min(4, v))


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class _Entry:
    __slots__ = ("kind", "key", "payload", "fallback", "future",
                 "token", "cleanup", "_cleaned", "t_enq")

    def __init__(self, kind, key, payload, fallback, token, cleanup):
        self.kind = kind
        self.key = key
        self.payload = payload
        self.fallback = fallback
        self.future: Future = Future()
        self.token = token
        self.cleanup = cleanup
        self._cleaned = cleanup is None
        self.t_enq = time.perf_counter()

    def cleanup_once(self):
        if not self._cleaned:
            self._cleaned = True
            try:
                self.cleanup()
            except Exception:   # pragma: no cover - unpin best-effort
                pass


class _StageSlot:
    __slots__ = ("bufs", "busy")

    def __init__(self):
        self.bufs: Dict = {}     # name -> previous device generation
        self.busy = False


class _StagingRing:
    """Double-buffered device input slots, one ring per (kind,
    statics) program family.

    ``acquire`` takes the family's next free slot (host-side wait —
    never under the device watchdog); ``upload`` refreshes the slot's
    device buffers from the new wave's host stacks, donating the
    previous generation when shape and dtype match
    (`ops.paged._stage_refresh_fn`) so the staging arena stays two
    buffers per family instead of growing per wave; ``release`` (at
    dispatch enqueue) frees the slot for wave N+2.  The device
    stream's WAR ordering makes donating a slot the PREVIOUS program
    is still reading safe — the overwrite queues behind it, the same
    contract the OutputRing's donated writes rely on."""

    def __init__(self, slots: Optional[int] = None):
        self._slots_n = slots
        self._fams: Dict[tuple, List[_StageSlot]] = {}
        self._cursor: Dict[tuple, int] = {}
        self._cv = threading.Condition()
        # counters (under _cv)
        self.staged = 0
        self.reused = 0

    def _n(self) -> int:
        return self._slots_n if self._slots_n else wave_stage_slots()

    def acquire(self, family: tuple, should_stop=None) -> tuple:
        """Block until a slot of ``family`` frees up; returns the slot
        token.  ``should_stop`` (callable) aborts the wait — shutdown
        must not strand the assembly thread on a dead dispatcher."""
        with self._cv:
            slots = self._fams.get(family)
            if slots is None or len(slots) != self._n():
                slots = [_StageSlot() for _ in range(self._n())]
                self._fams[family] = slots
                self._cursor[family] = 0
            while True:
                n = len(slots)
                start = self._cursor[family]
                for k in range(n):
                    i = (start + k) % n
                    if not slots[i].busy:
                        slots[i].busy = True
                        self._cursor[family] = (i + 1) % n
                        return (family, i)
                if should_stop is not None and should_stop():
                    raise RuntimeError("staging ring shut down")
                self._cv.wait(timeout=0.1)

    def upload(self, token: tuple, host: Dict) -> Dict:
        """Upload the wave's host stacks into the acquired slot.
        Values already on device (drill stacks) pass through; host
        arrays refresh the slot's previous buffer in place when the
        shape matches, else allocate fresh."""
        from ..ops.paged import _stage_refresh_fn
        family, i = token
        with self._cv:
            slot = self._fams[family][i]
        dev: Dict = {}
        reused = 0
        for name, arr in host.items():
            if arr is None:
                continue
            prev = slot.bufs.get(name)
            if (isinstance(arr, np.ndarray) and prev is not None
                    and tuple(prev.shape) == tuple(arr.shape)
                    and str(prev.dtype) == str(arr.dtype)):
                dev[name] = _stage_refresh_fn()(prev, arr)
                reused += 1
            else:
                dev[name] = jnp.asarray(arr)
        slot.bufs = dev
        with self._cv:
            self.staged += 1
            self.reused += reused
        return dev

    def release(self, token: Optional[tuple]):
        if token is None:
            return
        family, i = token
        with self._cv:
            fam = self._fams.get(family)
            if fam is not None and i < len(fam):
                fam[i].busy = False
            self._cv.notify_all()

    def stats(self) -> Dict:
        with self._cv:
            return {"families": len(self._fams),
                    "slots_per_family": self._n(),
                    "staged": self.staged,
                    "slot_reuse": self.reused}


class _StagedWave:
    """One assembled wave group parked on the host-written wave queue:
    entries + plan + pre-uploaded device inputs, waiting for the
    dispatch stage."""
    __slots__ = ("kind", "key", "entries", "plan", "dev", "slot",
                 "mesh", "pool_gen", "t_staged")

    def __init__(self, kind, key, entries, plan=None, dev=None,
                 slot=None, mesh=None, pool_gen=None):
        self.kind = kind
        self.key = key
        self.entries = entries
        self.plan = plan
        self.dev = dev
        self.slot = slot
        self.mesh = mesh
        self.pool_gen = pool_gen
        self.t_staged = time.perf_counter()


class WaveScheduler:
    """Two-stage wave pipeline over the paged kernels.

    Threads start lazily on first submit (a server that never enables
    waves never pays for them) and are daemons: process exit never
    hangs on a drained queue.  With GSKY_WAVE_PIPELINE=1 (default) the
    ticker thread is the ASSEMBLY stage and a dispatcher thread drains
    the staged-wave queue; with 0 the ticker assembles AND dispatches
    synchronously (the pre-pipeline behaviour, byte-identical)."""

    def __init__(self, max_entries: Optional[int] = None,
                 tick_ms: Optional[float] = None,
                 ring_rows: Optional[int] = None,
                 manual_dispatch: bool = False):
        from ..ops.paged import OutputRing
        self._max = max_entries
        self._tick_ms = tick_ms
        self.ring = OutputRing(ring_rows)
        self.staging = _StagingRing()
        self._lock = threading.Lock()
        self._pending: List[_Entry] = []
        self._kick = threading.Event()
        self._stop = threading.Event()
        self._readback_q: Queue = Queue()
        # host-written wave queue: assembly appends staged waves, the
        # dispatch stage pops them back-to-back
        self._staged_q: deque = deque()
        self._q_cv = threading.Condition()
        self._ticker: Optional[threading.Thread] = None
        self._drainer: Optional[threading.Thread] = None
        self._dispatcher: Optional[threading.Thread] = None
        # tests drive dispatch_once() deterministically
        self._manual_dispatch = bool(manual_dispatch)
        # counters (under _lock)
        self.dispatches = 0          # device program invocations
        self.waves = 0               # scheduler ticks that dispatched
        self.requests = 0            # entries submitted
        self.fallbacks = 0           # entries served via per-call leg
        self.cancelled = 0           # entries dropped for cancellation
        self.occupancy: Dict[int, int] = {}   # group size -> count
        self.readback_depth_max = 0
        self.assembly_ms_last = 0.0
        self.stage_ms_last = 0.0
        self.staged_waves = 0        # groups staged ahead of dispatch
        # inter-wave dispatch gap accounting (under _lock)
        self._t_dispatch_end: Optional[float] = None
        self._gap_ms: List[float] = []
        self.gap_total_ms = 0.0
        self.busy_total_ms = 0.0
        from ..obs import tsan
        if tsan.enabled():
            # lockset tracking across the assembly/dispatch/drainer/
            # request threads (docs/ANALYSIS.md "Race sanitizer")
            tsan.track(self, "WaveScheduler")

    # -- knobs ---------------------------------------------------------

    def _wave_max(self) -> int:
        return self._max if self._max else wave_max()

    def _tick_s(self) -> float:
        ms = self._tick_ms if self._tick_ms is not None \
            else wave_tick_ms()
        return ms / 1e3

    def _effective_max(self) -> int:
        """Brownout/pressure clamp: a degraded device gets smaller
        waves."""
        m = self._wave_max()
        try:
            from ..resilience.pressure import brownout_level
            lv = brownout_level()
        except Exception:   # pragma: no cover - pressure optional
            lv = 0
        if lv >= 2:
            return max(1, m // 4)
        if lv == 1:
            return max(1, m // 2)
        return m

    def _effective_queue_depth(self) -> int:
        """Pressure clamp on assembly run-ahead: under brownout the
        pipeline degrades to strict double buffering (depth 1)."""
        d = wave_queue_depth()
        try:
            from ..resilience.pressure import brownout_level
            if brownout_level() >= 1:
                return 1
        except Exception:   # pragma: no cover - pressure optional
            pass
        return d

    # -- submission ----------------------------------------------------

    def _submit(self, entry: _Entry) -> _Entry:
        self._ensure_threads()
        with self._lock:
            self._pending.append(entry)
            self.requests += 1
        self._kick.set()
        return entry

    @staticmethod
    def _wait(entry: _Entry):
        """Block on the entry's future, cancellation-aware: a request
        whose client disconnected stops waiting within one poll tick
        while its wave still executes for the surviving companions."""
        while True:
            try:
                return entry.future.result(timeout=0.05)
            except _FutTimeout:
                if entry.token is not None:
                    entry.token.check("wave")
            except CancelledError:
                if entry.token is not None:
                    entry.token.check("wave")
                raise

    # -- threads -------------------------------------------------------

    def _ensure_threads(self):
        if self._ticker is not None and self._ticker.is_alive():
            return
        with self._lock:
            if self._ticker is None or not self._ticker.is_alive():
                self._stop.clear()
                self._ticker = threading.Thread(
                    target=self._ticker_loop, name="gsky-wave-ticker",
                    daemon=True)
                self._ticker.start()
            if self._drainer is None or not self._drainer.is_alive():
                self._drainer = threading.Thread(
                    target=self._drain_loop, name="gsky-wave-readback",
                    daemon=True)
                self._drainer.start()
            if (not self._manual_dispatch
                    and (self._dispatcher is None
                         or not self._dispatcher.is_alive())):
                self._dispatcher = threading.Thread(
                    target=self._dispatch_loop,
                    name="gsky-wave-dispatch", daemon=True)
                self._dispatcher.start()

    def _ticker_loop(self):
        while not self._stop.is_set():
            self._kick.wait(timeout=0.25)
            if self._stop.is_set():
                return
            with self._lock:
                if not self._pending:
                    self._kick.clear()
                    continue
            tick = self._tick_s()
            if tick > 0:
                time.sleep(tick)
            try:
                if wave_pipeline_enabled():
                    self.assemble_once()
                else:
                    self.run_wave()
            except Exception:   # pragma: no cover - keep ticking
                pass

    def _dispatch_loop(self):
        while True:
            sg = self._q_get(timeout=0.25)
            if sg is None:
                if self._stop.is_set():
                    return
                continue
            try:
                self._dispatch_staged(sg)
            except Exception:   # pragma: no cover - keep dispatching
                pass

    def _drain_loop(self):
        while True:
            try:
                item = self._readback_q.get(timeout=0.25)
            except Empty:
                if self._stop.is_set():
                    return
                continue
            if item is None:
                return
            # one batched guarded_readback per WAVE: every group's
            # result blocks pull in a single supervised sync and the
            # integrity probe runs once over the stacked outputs —
            # per-entry failover preserved on incident
            groups = item
            for _kind, _es, devs, obs in groups:
                if obs is not None:
                    # mesh wave: per-chip shard probe BEFORE the
                    # gather — records readiness skew on this (async)
                    # thread so dispatch never blocks on a straggler
                    obs(devs)
            flat = [d for _k, _e, devs, _o in groups for d in devs]
            try:
                host = device_guard.guarded_readback(
                    "wave.readback",
                    lambda: tuple(np.asarray(d) for d in flat))
            except Exception as exc:
                for _kind, entries, _d, _o in groups:
                    self._failover(entries, exc)
                continue
            i0 = 0
            for _kind, entries, devs, _obs in groups:
                lanes = host[i0:i0 + len(devs)]
                i0 += len(devs)
                for i, e in enumerate(entries):
                    if e.token is not None and e.token.cancelled():
                        with self._lock:
                            self.cancelled += 1
                        e.future.cancel()
                        continue
                    res = lanes[0][i] if len(lanes) == 1 \
                        else tuple(h[i] for h in lanes)
                    if not e.future.cancelled():
                        e.future.set_result(res)

    # -- staged-wave queue ---------------------------------------------

    def _q_put(self, sg: _StagedWave):
        with self._q_cv:
            self._staged_q.append(sg)
            self._q_cv.notify_all()

    def _q_get(self, timeout: float = 0.0) -> Optional[_StagedWave]:
        deadline = time.monotonic() + timeout
        with self._q_cv:
            while not self._staged_q:
                left = deadline - time.monotonic()
                if left <= 0 or self._stop.is_set():
                    return None
                self._q_cv.wait(timeout=left)
            sg = self._staged_q.popleft()
            self._q_cv.notify_all()
            return sg

    def _q_wait_space(self):
        """Assembly backpressure: block while the wave queue is at its
        (pressure-clamped) depth — the queue is the run-ahead bound."""
        with self._q_cv:
            while (len(self._staged_q) >= self._effective_queue_depth()
                   and not self._stop.is_set()):
                self._q_cv.wait(timeout=0.1)

    # -- wave assembly -------------------------------------------------

    def _drain_groups(self) -> Dict[tuple, List[_Entry]]:
        """Shared front half of both legs: drain up to the effective
        cap, drop cancelled entries (releasing their pins NOW — a dead
        request must not ride the wave nor hold pins), group by
        (kind, statics)."""
        with self._lock:
            cap = self._effective_max()
            take = self._pending[:cap]
            del self._pending[:cap]
            leftover = bool(self._pending)
        if leftover:
            self._kick.set()
        live: List[_Entry] = []
        for e in take:
            if e.token is not None and e.token.cancelled():
                e.cleanup_once()
                e.future.cancel()
                with self._lock:
                    self.cancelled += 1
            else:
                live.append(e)
        groups: Dict[tuple, List[_Entry]] = {}
        for e in live:
            groups.setdefault((e.kind, e.key), []).append(e)
        return groups

    @staticmethod
    def _mesh():
        # mesh serving (GSKY_MESH=1): every group consults the
        # partition rules; disabled, md is None and the single-chip
        # dispatch runs byte-identically
        try:
            from ..mesh.dispatch import default_mesh
            return default_mesh()
        except Exception:   # pragma: no cover - mesh boot failure
            return None

    def run_wave(self) -> int:
        """Assemble and dispatch one wave SYNCHRONOUSLY (the
        GSKY_WAVE_PIPELINE=0 leg, and the deterministic step tests and
        bench call directly).  Returns the number of entries
        dispatched."""
        t0 = time.perf_counter()
        groups = self._drain_groups()
        if not groups:
            return 0
        dispatched = 0
        md = self._mesh()
        readback = []
        for (kind, _key), es in groups.items():
            try:
                if md is not None:
                    devs = self._timed_dispatch(
                        lambda m=md, k=kind, g=es:
                        m.dispatch_wave(self, k, g))
                else:
                    # dataflow autoplanner (GSKY_PLAN): superblock the
                    # group's gathers / pick block shapes BEFORE the
                    # device guard so a planner defect degrades to the
                    # unplanned dispatch, never to a device incident
                    plan = None
                    try:
                        from . import autoplan
                        plan = autoplan.plan_wave_group(kind, es)
                    except Exception:   # planning is an optimisation
                        plan = None
                    devs = self._timed_dispatch(
                        lambda k=kind, g=es, p=plan:
                        self._dispatch_group(k, g, p))
            except Exception as exc:
                # device incident mid-wave: the wave never fails as a
                # unit — each request re-renders per-call
                self._failover(es, exc)
                continue
            dispatched += len(es)
            self._note_dispatched(kind, es)
            readback.append(
                (kind, es, devs,
                 md.observe_shards if md is not None else None))
        if readback:
            self._readback_q.put(readback)
            with self._lock:
                self.readback_depth_max = max(
                    self.readback_depth_max, self._readback_q.qsize())
        if dispatched:
            with self._lock:
                self.waves += 1
                self.assembly_ms_last = (time.perf_counter() - t0) * 1e3
            try:
                WAVE_ASSEMBLY_MS.observe(
                    (time.perf_counter() - t0) * 1e3)
            except Exception:  # prom telemetry only
                pass
        return dispatched

    def assemble_once(self) -> int:
        """The pipelined ASSEMBLY stage: drain, plan, stack, upload
        into the staging ring, and park the staged wave on the
        dispatch queue.  Returns the number of entries staged.  Runs
        on the ticker thread; the dispatch stage runs concurrently."""
        t0 = time.perf_counter()
        groups = self._drain_groups()
        if not groups:
            return 0
        staged_n = 0
        md = self._mesh()
        for (kind, key), es in groups.items():
            self._q_wait_space()
            if self._stop.is_set():
                self._failover(es, RuntimeError(
                    "wave scheduler shut down"))
                continue
            try:
                sg = self._stage_group(kind, key, es, md)
            except Exception as exc:
                self._failover(es, exc)
                continue
            staged_n += len(es)
            with self._lock:
                self.staged_waves += 1
                self.stage_ms_last = (time.perf_counter() - t0) * 1e3
            try:
                WAVE_STAGED.inc()
            except Exception:  # prom telemetry only
                pass
            self._q_put(sg)
        if staged_n:
            with self._lock:
                self.assembly_ms_last = (time.perf_counter() - t0) * 1e3
            try:
                WAVE_ASSEMBLY_MS.observe(
                    (time.perf_counter() - t0) * 1e3)
            except Exception:  # prom telemetry only
                pass
        return staged_n

    def _stage_group(self, kind: str, key: tuple, es: List[_Entry],
                     md=None) -> _StagedWave:
        """Plan + stack + upload one group's inputs ahead of dispatch.
        The host stacks are built exactly as the synchronous dispatch
        would build them (same values, same dtypes), then uploaded
        under ``device_guard.run("wave.stage")`` — a staging-class
        site, so a hang here is attributed to the EXECUTING wave."""
        plan = None
        pool_gen = None
        if md is not None:
            dev = device_guard.run(
                "mesh.stage",
                lambda: md.stage_wave(self, kind, es))
            return _StagedWave(kind, key, es, mesh=md, dev=dev)
        if kind in ("byte", "scored", "expr"):
            try:
                from . import autoplan
                plan = autoplan.plan_wave_group(kind, es,
                                                stage="assembly")
            except Exception:   # planning is an optimisation
                plan = None
            pool = es[0].payload["pool"]
            pool_gen = pool.handoff()
            if plan is not None and plan.route == "bucketed":
                # the bucketed leg re-renders from each entry's own
                # XLA payload at dispatch — nothing to pre-upload
                return _StagedWave(kind, key, es, plan=plan,
                                   pool_gen=pool_gen)
            N = len(es)
            Np = _pow2(N)
            host: Dict = {
                "ctrls": np.stack([e.payload["ctrl"] for e in es]
                                  + [es[0].payload["ctrl"]] * (Np - N))
            }
            if kind in ("byte", "expr"):
                host["sps"] = np.stack(
                    [e.payload["sp"] for e in es]
                    + [es[0].payload["sp"]] * (Np - N))
            if kind == "expr":
                host["consts"] = np.stack(
                    [e.payload["consts"] for e in es]
                    + [es[0].payload["consts"]] * (Np - N))
            if plan is not None and plan.route == "superblock":
                host["tables"] = np.asarray(plan.tables)
                host["params"] = np.asarray(plan.params)
                host["sb_of"] = np.asarray(plan.sb_of)
            else:
                host["tables"], host["params"] = \
                    self._stack_tables(es, Np)
        elif kind == "drill":
            host = {
                "data": jnp.stack(
                    [jnp.asarray(e.payload["data"]) for e in es]
                    + [jnp.asarray(es[0].payload["data"])]
                    * (_pow2(len(es)) - len(es))),
                "valid": jnp.stack(
                    [jnp.asarray(e.payload["valid"]) for e in es]
                    + [jnp.asarray(es[0].payload["valid"])]
                    * (_pow2(len(es)) - len(es))),
            }
        else:
            raise ValueError(f"unknown wave kind {kind!r}")
        slot = self.staging.acquire((kind, key),
                                    should_stop=self._stop.is_set)
        try:
            dev = device_guard.run(
                "wave.stage",
                lambda: self.staging.upload(slot, host))
        except Exception:
            self.staging.release(slot)
            raise
        return _StagedWave(kind, key, es, plan=plan, dev=dev,
                           slot=slot, pool_gen=pool_gen)

    def dispatch_once(self, timeout: float = 0.0) -> int:
        """Pop one staged wave and dispatch it (the pipelined DISPATCH
        stage; tests call this directly to step deterministically).
        Returns entries dispatched, 0 when the queue stayed empty."""
        sg = self._q_get(timeout=timeout)
        if sg is None:
            return 0
        return self._dispatch_staged(sg)

    def _dispatch_staged(self, sg: _StagedWave) -> int:
        es = sg.entries
        cancelled = [e for e in es
                     if e.token is not None and e.token.cancelled()]
        if len(cancelled) == len(es):
            # the whole staged wave died while queued: skip the device
            # program entirely, release pins AND the staging slot
            self.staging.release(sg.slot)
            for e in es:
                e.cleanup_once()
                e.future.cancel()
            with self._lock:
                self.cancelled += len(es)
            return 0
        # partially-cancelled waves still dispatch: the dead lanes are
        # already baked into the staged stacks and are discarded at
        # readback (the drainer's token check)
        if sg.pool_gen is not None:
            pool = es[0].payload["pool"]
            if not pool.handoff_ok(sg.pool_gen):
                self.staging.release(sg.slot)
                self._failover(es, RuntimeError(
                    "page pool torn down between wave assembly and"
                    " dispatch"))
                return 0
        try:
            if sg.mesh is not None:
                devs = self._timed_dispatch(
                    lambda: sg.mesh.dispatch_wave(
                        self, sg.kind, es, staged=sg.dev))
            else:
                devs = self._timed_dispatch(
                    lambda: self._dispatch_group(
                        sg.kind, es, sg.plan, staged=sg.dev))
        except Exception as exc:
            self._failover(es, exc)
            return 0
        finally:
            # program enqueued (or failed): the slot may be donated by
            # wave N+2 — the device stream serialises the overwrite
            self.staging.release(sg.slot)
        self._note_dispatched(sg.kind, es)
        with self._lock:
            self.waves += 1
        self._readback_q.put(
            [(sg.kind, es, devs,
              sg.mesh.observe_shards if sg.mesh is not None
              else None)])
        with self._lock:
            self.readback_depth_max = max(
                self.readback_depth_max, self._readback_q.qsize())
        return len(es)

    # -- dispatch accounting -------------------------------------------

    def _timed_dispatch(self, thunk):
        """Run one group dispatch under the device guard, recording
        the host-side inter-wave gap (idle time since the previous
        dispatch enqueue finished) and the busy window."""
        t0 = time.perf_counter()
        gap_ms = None
        with self._lock:
            if self._t_dispatch_end is not None:
                gap_ms = (t0 - self._t_dispatch_end) * 1e3
        try:
            return device_guard.run("dispatch.wave", thunk)
        finally:
            t1 = time.perf_counter()
            with self._lock:
                if gap_ms is not None:
                    self._gap_ms.append(gap_ms)
                    if len(self._gap_ms) > 2048:
                        del self._gap_ms[:1024]
                    self.gap_total_ms += gap_ms
                self.busy_total_ms += (t1 - t0) * 1e3
                self._t_dispatch_end = t1
            if gap_ms is not None:
                try:
                    WAVE_GAP_MS.observe(gap_ms)
                except Exception:  # prom telemetry only
                    pass

    def _note_dispatched(self, kind: str, es: List[_Entry]):
        with self._lock:
            self.dispatches += 1
            n = len(es)
            self.occupancy[n] = self.occupancy.get(n, 0) + 1
        try:
            WAVE_DISPATCHES.labels(kind=kind).inc()
            WAVE_OCCUPANCY.observe(float(len(es)))
        except Exception:  # prom telemetry only
            pass

    def _failover(self, entries: List[_Entry], exc: Exception):
        for e in entries:
            e.cleanup_once()
            if e.future.cancelled():
                continue
            if e.fallback is None:
                e.future.set_exception(exc)
                continue
            with self._lock:
                self.fallbacks += 1
            try:
                e.future.set_result(e.fallback())
            except Exception as fe:   # pragma: no cover
                if not e.future.done():
                    e.future.set_exception(fe)

    # -- per-kind dispatch ---------------------------------------------

    def _dispatch_group(self, kind: str, es: List[_Entry], plan=None,
                        staged=None):
        if kind == "byte":
            return self._dispatch_byte(es, plan, staged)
        if kind == "scored":
            return self._dispatch_scored(es, plan, staged)
        if kind == "expr":
            return self._dispatch_expr(es, plan, staged)
        if kind == "drill":
            return self._dispatch_drill(es, staged)
        raise ValueError(f"unknown wave kind {kind!r}")

    def _stack_tables(self, es: List[_Entry], Np: int):
        """Shared ragged stacking: granule axis to the wave's LARGEST
        tile, page slots likewise; padding rows carry ns_id -1 + a
        null page table, so they gather nothing and every real row is
        bit-independent of its companions (the parity property the
        GSKY_WAVES=0 escape hatch is tested against).  Returns HOST
        arrays — the sync leg uploads them at dispatch, the pipelined
        leg through the staging ring one wave ahead."""
        from ..ops.paged import PARAMS_W
        T = max(e.payload["tables"].shape[0] for e in es)
        S = max(e.payload["tables"].shape[1] for e in es)
        tables = np.zeros((Np, T, S), np.int32)
        params = np.zeros((Np, T, PARAMS_W), np.float32)
        params[:, :, 10] = -1.0     # ns_id: padding rows
        for i, e in enumerate(es):
            ti, si = e.payload["tables"].shape
            tables[i, :ti, :si] = e.payload["tables"]
            params[i, :ti] = e.payload["params16"]
        return tables, params.reshape(Np * T, PARAMS_W)

    def _dispatch_byte(self, es: List[_Entry], plan=None, staged=None):
        from ..ops import paged
        from ..ops.paged import render_byte_paged_raced
        pool = es[0].payload["pool"]
        method, n_ns, out_hw, step, auto, colour_scale = es[0].key[0]
        try:
            N = len(es)
            Np = _pow2(N)

            def _xla():
                # per-tile bucketed XLA legs stacked to the wave
                # contract (runs when racing, demoted, or when the
                # planner's byte estimator routed the group here)
                from ..ops.warp import render_scenes_ctrl
                from .executor import _dev_win0    # lazy: avoids cycle
                outs = []
                for e in es:
                    stack, bparams, bwin, bwin0 = e.payload["xla"]
                    outs.append(render_scenes_ctrl(
                        stack, jnp.asarray(e.payload["ctrl"]),
                        jnp.asarray(bparams),
                        jnp.asarray(e.payload["sp"]), method, n_ns,
                        out_hw, step, auto, colour_scale, win=bwin,
                        win0=_dev_win0(bwin0)))
                outs += [outs[0]] * (Np - N)
                return jnp.stack(outs)

            if plan is not None and plan.route == "bucketed":
                # scattered mix: the ragged slot pad would move more
                # HBM bytes than the per-tile pulls (the PR 8 caveat)
                paged.note_gather(plan.bucketed_bytes)
                dev = _xla()
                return (self.ring.put(dev),)
            blk = plan.blk if plan is not None else None
            sb_of = None
            if staged is not None:
                tables = staged["tables"]
                params = staged["params"]
                ctrls = staged["ctrls"]
                sps = staged["sps"]
                sb_of = staged.get("sb_of")
            else:
                ctrls = jnp.asarray(np.stack(
                    [e.payload["ctrl"] for e in es]
                    + [es[0].payload["ctrl"]] * (Np - N)))
                sps = jnp.asarray(np.stack(
                    [e.payload["sp"] for e in es]
                    + [es[0].payload["sp"]] * (Np - N)))
                if plan is not None and plan.route == "superblock":
                    tables = jnp.asarray(plan.tables)
                    params = jnp.asarray(plan.params)
                    sb_of = jnp.asarray(plan.sb_of)
                else:
                    t_h, p_h = self._stack_tables(es, Np)
                    tables, params = jnp.asarray(t_h), jnp.asarray(p_h)
            with pool.locked_pool() as parr:
                dev = render_byte_paged_raced(
                    parr, tables, params, ctrls, sps, method, n_ns,
                    out_hw, step, auto, colour_scale, _xla, blk=blk,
                    sb_of=sb_of)
            # the full pow2 block goes through the ring (one compile
            # per lattice point — prewarm covers it); the wave pad is
            # discarded host-side at readback and never reaches a link
            return (self.ring.put(dev),)
        finally:
            for e in es:
                e.cleanup_once()

    def _dispatch_scored(self, es: List[_Entry], plan=None,
                         staged=None):
        from ..ops import paged
        from ..ops.paged import warp_scored_paged_raced
        pool = es[0].payload["pool"]
        method, n_ns, out_hw, step = es[0].key[0]
        try:
            N = len(es)
            Np = _pow2(N)

            def _xla():
                from ..ops.warp import warp_scenes_ctrl_scored
                from .executor import _dev_win0    # lazy: avoids cycle
                cs, bs = [], []
                for e in es:
                    stack, bparams, bwin, bwin0 = e.payload["xla"]
                    c, b = warp_scenes_ctrl_scored(
                        stack, jnp.asarray(e.payload["ctrl"]),
                        jnp.asarray(bparams), method, n_ns, out_hw,
                        step, win=bwin, win0=_dev_win0(bwin0))
                    cs.append(c)
                    bs.append(b)
                cs += [cs[0]] * (Np - N)
                bs += [bs[0]] * (Np - N)
                return jnp.stack(cs), jnp.stack(bs)

            if plan is not None and plan.route == "bucketed":
                paged.note_gather(plan.bucketed_bytes)
                canv, best = _xla()
                valid = best > -jnp.inf
                return (self.ring.put(canv),
                        self.ring.put(valid))
            blk = plan.blk if plan is not None else None
            sb_of = None
            if staged is not None:
                tables = staged["tables"]
                params = staged["params"]
                ctrls = staged["ctrls"]
                sb_of = staged.get("sb_of")
            else:
                ctrls = jnp.asarray(np.stack(
                    [e.payload["ctrl"] for e in es]
                    + [es[0].payload["ctrl"]] * (Np - N)))
                if plan is not None and plan.route == "superblock":
                    tables = jnp.asarray(plan.tables)
                    params = jnp.asarray(plan.params)
                    sb_of = jnp.asarray(plan.sb_of)
                else:
                    t_h, p_h = self._stack_tables(es, Np)
                    tables, params = jnp.asarray(t_h), jnp.asarray(p_h)
            with pool.locked_pool() as parr:
                canv, best = warp_scored_paged_raced(
                    parr, tables, params, ctrls, method,
                    n_ns, out_hw, step, _xla, blk=blk, sb_of=sb_of)
            # fold best -> validity ON DEVICE: the -inf invalid marker
            # must not reach guarded_readback (the integrity probe
            # treats inf as DMA corruption — correctly, everywhere
            # else), and the consumer only ever wants the mask
            valid = best > -jnp.inf
            return (self.ring.put(canv), self.ring.put(valid))
        finally:
            for e in es:
                e.cleanup_once()

    def _dispatch_expr(self, es: List[_Entry], plan=None, staged=None):
        """Expression wave: every lane shares one fused paged program
        (the group key carries the fingerprint, so all lanes evaluate
        the same STRUCTURE; constants ride as a traced (Np, C) row).
        The body mirrors `_dispatch_byte` — same planner routes, same
        ring discipline — with `render_expr_paged_raced` at the
        bottom."""
        from ..ops import paged
        from ..ops.expr import fingerprint_hash
        from ..ops.paged import render_expr_paged_raced
        pool = es[0].payload["pool"]
        (method, n_ns, out_hw, step, auto, colour_scale,
         fp) = es[0].key[0]
        try:
            N = len(es)
            Np = _pow2(N)

            def _xla():
                # per-tile unfused legs (bucketed scored mosaic + the
                # same epilogue + scale) stacked to the wave contract
                from ..ops.paged import expr_epilogue
                from ..ops.scale import scale_to_byte
                from ..ops.warp import warp_scenes_ctrl_scored
                from .executor import _dev_win0    # lazy: avoids cycle
                outs = []
                for e in es:
                    stack, bparams, bwin, bwin0 = e.payload["xla"]
                    c, b = warp_scenes_ctrl_scored(
                        stack, jnp.asarray(e.payload["ctrl"]),
                        jnp.asarray(bparams), method, n_ns, out_hw,
                        step, win=bwin, win0=_dev_win0(bwin0))
                    plane, ok = expr_epilogue(
                        c[None], b[None], fp,
                        jnp.asarray(e.payload["consts"][None]))
                    sp = e.payload["sp"]
                    outs.append(scale_to_byte(
                        plane[0], ok[0], float(sp[0]), float(sp[1]),
                        float(sp[2]), colour_scale, auto))
                outs += [outs[0]] * (Np - N)
                return jnp.stack(outs)

            if plan is not None and plan.route == "bucketed":
                paged.note_gather(plan.bucketed_bytes)
                dev = _xla()
                return (self.ring.put(dev),)
            blk = plan.blk if plan is not None else None
            sb_of = None
            if staged is not None:
                tables = staged["tables"]
                params = staged["params"]
                ctrls = staged["ctrls"]
                sps = staged["sps"]
                consts = staged["consts"]
                sb_of = staged.get("sb_of")
            else:
                ctrls = jnp.asarray(np.stack(
                    [e.payload["ctrl"] for e in es]
                    + [es[0].payload["ctrl"]] * (Np - N)))
                sps = jnp.asarray(np.stack(
                    [e.payload["sp"] for e in es]
                    + [es[0].payload["sp"]] * (Np - N)))
                consts = jnp.asarray(np.stack(
                    [e.payload["consts"] for e in es]
                    + [es[0].payload["consts"]] * (Np - N)))
                if plan is not None and plan.route == "superblock":
                    tables = jnp.asarray(plan.tables)
                    params = jnp.asarray(plan.params)
                    sb_of = jnp.asarray(plan.sb_of)
                else:
                    t_h, p_h = self._stack_tables(es, Np)
                    tables, params = jnp.asarray(t_h), jnp.asarray(p_h)
            with pool.locked_pool() as parr:
                dev = render_expr_paged_raced(
                    parr, tables, params, ctrls, sps, consts, method,
                    n_ns, out_hw, step, auto, colour_scale, fp,
                    fingerprint_hash(fp), _xla, blk=blk, sb_of=sb_of)
            return (self.ring.put(dev),)
        finally:
            for e in es:
                e.cleanup_once()

    def _dispatch_drill(self, es: List[_Entry], staged=None):
        from ..ops.paged import wave_drill_stats
        clip_lo, clip_hi, pix = es[0].key[1:]
        K = len(es)
        Kp = _pow2(K)
        if staged is not None:
            data, valid = staged["data"], staged["valid"]
        else:
            # jnp.stack keeps device-resident drill windows on device —
            # the stacked reduction never pulls pixels to host
            data = jnp.stack(
                [jnp.asarray(e.payload["data"]) for e in es]
                + [jnp.asarray(es[0].payload["data"])] * (Kp - K))
            valid = jnp.stack(
                [jnp.asarray(e.payload["valid"]) for e in es]
                + [jnp.asarray(es[0].payload["valid"])] * (Kp - K))
        vals, counts = wave_drill_stats(data, valid, clip_lo, clip_hi,
                                        pixel_count=pix)
        return (self.ring.put(vals), self.ring.put(counts))

    # -- public enqueue API --------------------------------------------

    def render_byte(self, pool, tables, params16, ctrl, sp,
                    statics: tuple, xla_item, percall,
                    serials=None) -> np.ndarray:
        """Submit one byte-tile render (windows already staged in the
        page pool, ``tables`` PINNED — the wave unpins after enqueue).
        ``xla_item`` is (stack, params11, win, win0) for the race's
        stacked bucketed leg; ``percall`` re-renders this tile alone
        (incident failover).  ``serials`` is the lane's scene-content
        identity (the executor's scene-serial key): the autoplanner
        only superblock-merges lanes whose serials match, so temporal
        waves carrying DIFFERENT timesteps of one layer — identical
        params, different page content — never share a union gather
        table.  Blocks; returns host uint8 (H, W)."""
        from ..resilience import current_token
        e = _Entry("byte", (tuple(statics), id(pool)),
                   {"pool": pool, "tables": np.asarray(tables),
                    "params16": np.asarray(params16),
                    "ctrl": np.asarray(ctrl), "sp": np.asarray(sp),
                    "xla": xla_item,
                    "serials": tuple(serials) if serials else None},
                   percall, current_token(),
                   cleanup=lambda: pool.unpin(tables))
        return self._wait(self._submit(e))

    def render_expr(self, pool, tables, params16, ctrl, sp, consts,
                    statics: tuple, xla_item, percall,
                    serials=None) -> np.ndarray:
        """Submit one fused expression render (`render_byte` contract
        plus ``consts``, the lane's lifted literals (C,) f32).  The
        group key includes the fingerprint (statics[-1]), so lanes
        coalesce exactly when they share structure — mixed expression
        storms still wave within each structure.  Blocks; returns host
        uint8 (H, W)."""
        from ..resilience import current_token
        e = _Entry("expr", (tuple(statics), id(pool)),
                   {"pool": pool, "tables": np.asarray(tables),
                    "params16": np.asarray(params16),
                    "ctrl": np.asarray(ctrl), "sp": np.asarray(sp),
                    "consts": np.asarray(consts, np.float32),
                    "xla": xla_item,
                    "serials": tuple(serials) if serials else None},
                   percall, current_token(),
                   cleanup=lambda: pool.unpin(tables))
        return self._wait(self._submit(e))

    def warp_scored(self, pool, tables, params16, ctrl,
                    statics: tuple, xla_item, percall, serials=None):
        """Submit one scored mosaic (the warp_mosaic_scenes paged
        contract).  Blocks; returns host (canv (n_ns, h, w) f32,
        valid (n_ns, h, w) bool) — the -inf best plane is folded to
        its validity mask on device before readback."""
        from ..resilience import current_token
        e = _Entry("scored", (tuple(statics), id(pool)),
                   {"pool": pool, "tables": np.asarray(tables),
                    "params16": np.asarray(params16),
                    "ctrl": np.asarray(ctrl), "xla": xla_item,
                    "serials": tuple(serials) if serials else None},
                   percall, current_token(),
                   cleanup=lambda: pool.unpin(tables))
        return self._wait(self._submit(e))

    def drill_stats(self, data, valid, clip_lower: float,
                    clip_upper: float, pixel_count: bool, percall):
        """Submit one drill reduction: data/valid (B, N).  Requests
        sharing (shape, clips, mode) stack into one (K, B, N) device
        reduction.  Blocks; returns (vals (B,) f32, counts (B,))."""
        from ..resilience import current_token
        e = _Entry("drill",
                   (tuple(int(d) for d in data.shape),
                    float(clip_lower), float(clip_upper),
                    bool(pixel_count)),
                   {"data": data, "valid": valid},
                   percall, current_token(), cleanup=None)
        return self._wait(self._submit(e))

    # -- lifecycle / introspection -------------------------------------

    def shutdown(self):
        """Stop the threads; leftover pending entries AND staged-but-
        undispatched waves fail over to their per-call legs so no
        request is stranded."""
        with self._lock:
            leftover = self._pending[:]
            self._pending.clear()
        if leftover:
            self._failover(leftover,
                           RuntimeError("wave scheduler shut down"))
        self._stop.set()
        with self._q_cv:
            staged = list(self._staged_q)
            self._staged_q.clear()
            self._q_cv.notify_all()
        for sg in staged:
            self.staging.release(sg.slot)
            self._failover(sg.entries,
                           RuntimeError("wave scheduler shut down"))
        self._kick.set()
        self._readback_q.put(None)
        for t in (self._ticker, self._dispatcher, self._drainer):
            if t is not None and t.is_alive():
                t.join(timeout=2.0)

    def _gap_percentiles(self):  # gskylint: holds-lock
        if not self._gap_ms:
            return 0.0, 0.0
        arr = np.asarray(self._gap_ms)
        return (float(np.percentile(arr, 50)),
                float(np.percentile(arr, 99)))

    def stats(self) -> Dict:
        with self._lock:
            occ = dict(sorted(self.occupancy.items()))
            p50, p99 = self._gap_percentiles()
            busy = self.busy_total_ms
            gap = self.gap_total_ms
            idle = gap / (gap + busy) if (gap + busy) > 0 else 0.0
            out = {"enabled": True,
                   "pipeline": wave_pipeline_enabled(),
                   "wave_max": self._wave_max(),
                   "tick_ms": self._tick_ms if self._tick_ms
                   is not None else wave_tick_ms(),
                   "queue_depth": wave_queue_depth(),
                   "dispatches": self.dispatches,
                   "waves": self.waves,
                   "requests": self.requests,
                   "fallbacks": self.fallbacks,
                   "cancelled": self.cancelled,
                   "occupancy": occ,
                   "assembly_ms_last": round(self.assembly_ms_last,
                                             3),
                   "stage_ms_last": round(self.stage_ms_last, 3),
                   "staged_waves": self.staged_waves,
                   "staged_queue_depth": len(self._staged_q),
                   "gap_ms_p50": round(p50, 3),
                   "gap_ms_p99": round(p99, 3),
                   "gap_samples": len(self._gap_ms),
                   "device_idle_fraction": round(idle, 4),
                   "readback_queue_depth": self._readback_q.qsize(),
                   "readback_depth_max": self.readback_depth_max}
        out["staging"] = self.staging.stats()
        out["ring"] = self.ring.stats()
        return out


# -- module singleton ---------------------------------------------------

_default: Optional[WaveScheduler] = None
_default_lock = threading.Lock()


def default_waves() -> WaveScheduler:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = WaveScheduler()
    return _default


def wave_stats() -> Dict:
    """Scrape-safe stats: {} until the first wave request."""
    return {} if _default is None else _default.stats()


def reset_waves():
    """Tear down the singleton (tests / config reload)."""
    global _default
    with _default_lock:
        w = _default
        _default = None
    if w is not None:
        w.shutdown()
