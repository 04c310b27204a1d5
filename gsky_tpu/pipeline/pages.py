"""Paged gather-window pool: the HBM residency layer behind ragged
paged rendering (`ops.paged`, docs/KERNELS.md).

Scenes are cut into a fixed grid of (page_rows, page_cols) f32 pages
(page (pi, pj) covers scene rows [pi*PR, (pi+1)*PR), cols [pj*PC,
(pj+1)*PC); validity stays NaN-encoded, exactly the scene-cache
convention).  Pages live in ONE preallocated device pool array of
shape (capacity, PR, PC) and are content-keyed on (scene serial, pi,
pj): a window is staged into pages at most once per residency, and
overlapping tiles — adjacent GetMap tiles over the same granule, the
common WMS pattern — share the staged pages instead of re-pulling
overlapping gather windows, which is where the bucketed path paid its
padded-pull byte cost.

Slot 0 is a reserved all-NaN null page used to pad page tables (and
backs the zero-extent padding granules of a ragged batch): a kernel
tap through slot 0 is always invalid, never garbage.

Staging runs under `jax.jit` with the pool buffer DONATED, so each
stage is an in-place page write, not a pool-sized copy.  Donation
invalidates the previous Python reference, so the coherence rule is
strict: every pool-array access — staging in `table_for` AND the
dispatch enqueue that consumes a snapshot — happens under `self.lock`
(use `locked_pool()` around the kernel call).  Once a dispatch is
enqueued the device stream owns the value (jax arrays are immutable
values; later donation copies if the buffer is still held), so the
lock only needs to cover the enqueue, not the execution.

Eviction is LRU over page keys with one hard rule: slots PINNED by a
built-but-not-yet-dispatched table are never evicted (`table_for`
returns None instead — the caller falls back to the bucketed path).
Pins are taken by `table_for` and must be released with `unpin` after
the dispatch is enqueued; without the rule a concurrent request could
recycle a queued wave entry's pages between enqueue and dispatch.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import warnings
import zlib
from collections import OrderedDict
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged import page_shape


def _pool_capacity(pr: int, pc: int) -> int:
    """Pool page count from GSKY_PAGE_POOL_MB (default 64 MiB): at the
    default 128x512 f32 page (256 KiB) that is 256 pages — dozens of
    concurrent 1-4 page windows plus sharing headroom."""
    try:
        mb = int(os.environ.get("GSKY_PAGE_POOL_MB", "64"))
    except ValueError:
        mb = 64
    page_bytes = pr * pc * 4
    return max(2, (max(1, mb) << 20) // page_bytes)


@functools.partial(jax.jit, donate_argnums=(0,))
def _stage(pool, scene, ij, slot):
    """Write scene page (ij[0], ij[1]) into pool[slot] in place.  The
    scene is NaN-padded up to page multiples BEFORE the dynamic_slice
    (slice sizes larger than a dim are an error, and the pad is the
    validity encoding for the off-scene region anyway)."""
    pr, pc = pool.shape[1], pool.shape[2]
    sh, sw = scene.shape
    ph = -(-sh // pr) * pr
    pw = -(-sw // pc) * pc
    sp = jnp.pad(scene.astype(jnp.float32),
                 ((0, ph - sh), (0, pw - sw)),
                 constant_values=jnp.nan)
    page = jax.lax.dynamic_slice(sp, (ij[0] * pr, ij[1] * pc), (pr, pc))
    zero = jnp.zeros((), slot.dtype)    # match index dtypes under x64
    return jax.lax.dynamic_update_slice(pool, page[None],
                                        (slot, zero, zero))


@functools.partial(jax.jit, donate_argnums=(0,))
def _stage_ready(pool, page, slot):
    """Write an already-cut (PR, PC) page into pool[slot] in place —
    the fabric peer-fill path, where the page arrives as bytes and
    there is no host scene to slice from."""
    zero = jnp.zeros((), slot.dtype)
    return jax.lax.dynamic_update_slice(
        pool, page.astype(jnp.float32)[None], (slot, zero, zero))


def _note_fill(source: str) -> None:
    """gsky_fabric_page_fills_total{source=peer|cold} breadcrumb."""
    try:
        from ..obs.metrics import FABRIC_PAGE_FILLS
        FABRIC_PAGE_FILLS.labels(source=source).inc()
    except Exception:  # metrics are best-effort on the staging path
        pass


class PagePool:
    """Device-resident page pool + LRU page table.  Thread-safe; see
    the module docstring for the lock/pin coherence rules."""

    def __init__(self, capacity: int | None = None,
                 page_rows: int | None = None,
                 page_cols: int | None = None):
        pr, pc = page_shape()
        self.page_rows = int(page_rows or pr)
        self.page_cols = int(page_cols or pc)
        if capacity is None:
            capacity = _pool_capacity(self.page_rows, self.page_cols)
        self.capacity = max(2, int(capacity))
        self.lock = threading.RLock()
        self._pool = None            # lazy: first use allocates
        self._slots = OrderedDict()  # (serial, pi, pj) -> slot, LRU
        self._free = list(range(self.capacity - 1, 0, -1))
        self._pins: Dict[int, int] = {}   # slot -> pin count
        self._heat: Dict[tuple, int] = {}  # key -> hits since staged
        # stage-time page CRCs, kept only under GSKY_POOL_AUDIT=1
        self._checksums: Dict[tuple, int] = {}
        # audited-poisoned slots still pinned by an in-flight dispatch:
        # unpin() returns them to the free list once the pin drops
        self._quarantine_pins: set = set()
        # stats (under lock)
        self.staged = 0
        self.hits = 0
        self.evictions = 0
        self.declined = 0
        self.teardowns = 0
        self.trimmed = 0
        self.rehydrated = 0
        self.quarantined = 0
        self.peer_filled = 0   # pages staged from fabric peers
        # async-staging handoff generation: bumped by teardown so a
        # wave staged against this pool BEFORE a device incident
        # refuses to dispatch against the rebuilt pool (its pinned
        # slot indices no longer name the pages its tables meant)
        self._handoff_gen = 0
        from ..obs import tsan
        if tsan.enabled():
            # lockset tracking across staging / dispatch / teardown
            # threads (docs/ANALYSIS.md "Race sanitizer")
            tsan.track(self, "PagePool")

    # owning-chip index (mesh serving): None on the shared pool; a
    # ChipPagePool (mesh/pools.py) sets it and journal lines carry it
    chip = None

    # -- internals (hold self.lock) -----------------------------------

    def _ensure_pool(self):  # gskylint: holds-lock
        if self._pool is None:
            # slot 0 (and every unstaged slot) is all-NaN: a tap into
            # an unstaged page is invalid, never stale garbage
            self._pool = jnp.full(
                (self.capacity, self.page_rows, self.page_cols),
                jnp.nan, jnp.float32)

    def _place(self, dev):  # gskylint: holds-lock
        """Placement hook for the staged scene array: the shared pool
        leaves uploads wherever the scene cache put them; a per-chip
        pool overrides this to `device_put` onto its owning chip."""
        return dev

    def _take_slot(self):  # gskylint: holds-lock
        if self._free:
            return self._free.pop()
        for key in self._slots:    # LRU order: oldest first
            slot = self._slots[key]
            if self._pins.get(slot):
                continue
            del self._slots[key]
            self._heat.pop(key, None)
            self._checksums.pop(key, None)
            self.evictions += 1
            return slot
        return None                 # everything pinned: caller declines

    def _stage_locked(self, dev, serial: int, pi: int, pj: int):
        key = (int(serial), int(pi), int(pj))
        slot = self._slots.get(key)
        if slot is not None:
            self._slots.move_to_end(key)
            self.hits += 1
            self._heat[key] = self._heat.get(key, 0) + 1
            return slot
        slot = self._take_slot()
        if slot is None:
            return None
        self._ensure_pool()
        with warnings.catch_warnings():
            # donating a CPU-backed buffer warns; the fallback copy is
            # still correct, just not in-place
            warnings.simplefilter("ignore")
            self._pool = _stage(self._pool, self._place(dev),
                                jnp.asarray((pi, pj), jnp.int32),
                                jnp.int32(slot))
        self._slots[key] = slot
        self.staged += 1
        _note_fill("cold")
        from ..device_guard import (guard_enabled, journal,
                                    pool_audit_enabled)
        if guard_enabled():
            # warm-recovery breadcrumb: cold stages only, so the write
            # rate tracks decode churn, not the (much hotter) hit rate
            journal.record_stage(*key, chip=self.chip)
            if pool_audit_enabled():
                # stage-time CRC for the corruption audit: one page
                # readback per cold stage — the documented cost of
                # GSKY_POOL_AUDIT=1
                self._checksums[key] = zlib.crc32(
                    np.asarray(self._pool[slot]).tobytes())
        return slot

    # -- public --------------------------------------------------------

    def table_for(self, dev, serial: int, i0: int, i1: int,
                  j0: int, j1: int):
        """Stage pages (i0..i1) x (j0..j1) of scene `dev` and return
        their slots row-major as (npages,) int32, PINNED — or None when
        the pool can't hold the request's working set (caller falls
        back to the bucketed path; partial pins are rolled back).  The
        caller owns the pins and must `unpin` the returned slots once
        its dispatch is enqueued (or abandoned)."""
        from ..device_guard import staging_ok
        from ..resilience.pressure import staging_allowed
        if not staging_allowed() or not staging_ok():
            # critical memory pressure, or the device supervisor is
            # anything but healthy: growing HBM residency now risks the
            # whole process (or stages into a pool about to be torn
            # down) — decline and let the caller fall back to the
            # bucketed dispatch path
            with self.lock:
                self.declined += 1
            return None
        slots = []
        with self.lock:
            for pi in range(int(i0), int(i1) + 1):
                for pj in range(int(j0), int(j1) + 1):
                    s = self._stage_locked(dev, serial, pi, pj)
                    if s is None:
                        self.declined += 1
                        for t in slots:   # roll back partial pins
                            self._pins[t] -= 1
                            if not self._pins[t]:
                                del self._pins[t]
                        return None
                    self._pins[s] = self._pins.get(s, 0) + 1
                    slots.append(s)
        return np.asarray(slots, np.int32)

    def stage_page(self, serial: int, pi: int, pj: int, page) -> bool:
        """Stage one already-cut page delivered by a fabric peer
        (`fabric/pagerpc.py`): no host scene involved, the bytes ARE
        the page.  Shape must match the pool's page grid exactly —
        content keys only make sense between pools cut the same way.
        Returns False on shape mismatch or a full/pinned pool."""
        arr = np.asarray(page, np.float32)
        if arr.shape != (self.page_rows, self.page_cols):
            return False
        key = (int(serial), int(pi), int(pj))
        with self.lock:
            if key in self._slots:
                return True          # already resident: nothing to do
            slot = self._take_slot()
            if slot is None:
                self.declined += 1
                return False
            self._ensure_pool()
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                self._pool = _stage_ready(self._pool, jnp.asarray(arr),
                                          jnp.int32(slot))
            self._slots[key] = slot
            self.staged += 1
            self.peer_filled += 1
            from ..device_guard import (guard_enabled, journal,
                                        pool_audit_enabled)
            if guard_enabled():
                journal.record_stage(*key, chip=self.chip)
                if pool_audit_enabled():
                    self._checksums[key] = zlib.crc32(
                        np.asarray(self._pool[slot]).tobytes())
        _note_fill("peer")
        return True

    def has_page(self, serial: int, pi: int, pj: int) -> bool:
        """Residency probe (no LRU touch, no heat)."""
        with self.lock:
            return (int(serial), int(pi), int(pj)) in self._slots

    def read_page(self, serial: int, pi: int, pj: int):
        """Read a resident page back to host for a peer (the serving
        half of the page-fetch RPC).  Passive: no LRU touch, no heat —
        a peer's warm-up must not distort local eviction order.
        Returns a (PR, PC) float32 ndarray or None when not resident."""
        key = (int(serial), int(pi), int(pj))
        with self.lock:
            slot = self._slots.get(key)
            if slot is None or self._pool is None:
                return None
            return np.asarray(self._pool[slot])

    def prewarm(self, dev, serial: int, i0: int, i1: int,
                j0: int, j1: int) -> bool:
        """Prefetch hook: stage a page window without keeping pins —
        the planner warms pages it predicts a request will touch, and
        the request's own `table_for` then hits.  Best-effort: declines
        (pool full / pressure) are fine, the real request just stages
        as usual."""
        slots = self.table_for(dev, serial, i0, i1, j0, j1)
        if slots is None:
            return False
        self.unpin(slots)
        return True

    def unpin(self, slots) -> None:
        """Release pins taken by `table_for` (idempotence is the
        caller's job: once per returned table)."""
        with self.lock:
            for s in np.asarray(slots).reshape(-1).tolist():
                n = self._pins.get(int(s), 0) - 1
                if n > 0:
                    self._pins[int(s)] = n
                else:
                    self._pins.pop(int(s), None)
                    if int(s) in self._quarantine_pins:
                        # audited-poisoned while a dispatch held it:
                        # now that the pin is gone, recycle the slot
                        self._quarantine_pins.discard(int(s))
                        self._free.append(int(s))

    @contextlib.contextmanager
    def locked_pool(self):
        """The pool array to dispatch against, with staging locked out
        for the duration — enqueue the kernel call INSIDE the block so
        no concurrent stage donates the buffer between read and use."""
        with self.lock:
            self._ensure_pool()
            yield self._pool

    # -- async-staging handoff (pipelined waves) -----------------------

    def handoff(self) -> int:
        """Capture the staging generation at wave-assembly time.  The
        pipelined wave scheduler stages uploads one wave AHEAD of
        dispatch; the token pins the meaning of its slot indices."""
        with self.lock:
            return self._handoff_gen

    def handoff_ok(self, gen: int) -> bool:
        """True while a :meth:`handoff` token is still dispatchable —
        no teardown has recycled the slot namespace since assembly.
        (LRU eviction cannot invalidate a staged wave: its table slots
        stay pinned across the handoff.)"""
        with self.lock:
            return self._handoff_gen == int(gen)

    def drop_scene(self, serial: int):
        """Free every unpinned page of a scene (cache eviction hook);
        pinned pages stay resident until their dispatch retires them
        through normal LRU."""
        with self.lock:
            dead = [k for k, s in self._slots.items()
                    if k[0] == int(serial) and not self._pins.get(s)]
            for k in dead:
                self._free.append(self._slots.pop(k))
                self._heat.pop(k, None)
                self._checksums.pop(k, None)
        from ..device_guard import guard_enabled, journal
        if guard_enabled():
            # void the scene's journal entries: its pages can no longer
            # be re-staged, so a rebuild must not chase them
            journal.record_drop(serial)

    # -- device-guard lifecycle (docs/RESILIENCE.md) -------------------

    def teardown(self) -> None:
        """Device-incident teardown: journal the hot set, then drop the
        device array and every piece of residency bookkeeping.

        The supervisor runs this with the *host* process alive — only
        the device state is suspect — so the exact pre-incident hot set
        with in-memory hit counts is available and dumped as ``heat``
        journal lines for :meth:`rehydrate`.  Pins are cleared: every
        dispatch that held one has already failed through the
        supervisor by the time a teardown runs."""
        from ..device_guard import guard_enabled, journal
        with self.lock:
            if guard_enabled():
                for key in self._slots:
                    journal.record_heat(*key, hits=self._heat.get(key, 0),
                                        chip=self.chip)
            self._pool = None
            self._slots.clear()
            self._pins.clear()
            self._heat.clear()
            self._checksums.clear()
            self._quarantine_pins.clear()
            self._free = list(range(self.capacity - 1, 0, -1))
            self.teardowns += 1
            self._handoff_gen += 1

    def rehydrate(self) -> int:
        """Warm recovery: re-stage the journal's hottest pages from
        scenes still resident in the host scene cache, hottest first,
        until the journal or the pool runs out.  Entries whose serial
        is no longer resident (or whose page coordinates fall outside
        the scene's page grid — a stale journal against a reloaded
        world) are skipped.  Returns the number of pages restored."""
        from ..device_guard import journal
        entries = journal.replay()
        if not entries:
            return 0
        restored = 0
        try:
            from .. import fabric
            if fabric.pages_enabled():
                # ask ring-adjacent peers for the hot set first: peer
                # HBM/host memory beats re-decoding from storage, and
                # whatever peers can't serve falls through to the
                # scene-cache loop below
                from ..fabric import pagerpc
                restored += pagerpc.fill_from_peers(self, entries)
        except Exception:  # fabric is best-effort; recovery continues
            pass
        try:
            from .scene_cache import default_scene_cache as sc
            with sc._lock:
                scenes = {s.serial: s.dev for s in sc._scenes.values()}
        except Exception:
            with self.lock:
                self.rehydrated += restored
            return restored
        for serial, pi, pj in entries:
            with self.lock:
                if (serial, pi, pj) in self._slots:
                    continue        # already peer-filled above
            dev = scenes.get(serial)
            if dev is None:
                continue            # stale: scene evicted since
            gh = -(-int(dev.shape[0]) // self.page_rows)
            gw = -(-int(dev.shape[1]) // self.page_cols)
            if pi >= gh or pj >= gw:
                continue            # stale: outside the scene's grid
            with self.lock:
                if not self._free and (serial, pi, pj) not in self._slots:
                    break   # pool full: never LRU-evict warmth we just
                    # restored to make room for colder journal entries
                if self._stage_locked(dev, serial, pi, pj) is not None:
                    restored += 1
        with self.lock:
            self.rehydrated += restored
        return restored

    def trim(self, frac: float = 0.5) -> int:
        """OOM relief: release the coldest ``frac`` of unpinned pages
        so staging churn stops competing for HBM while the pressure
        monitor's cache relief frees the real bytes.  Returns the
        number of pages released."""
        with self.lock:
            victims = [k for k in self._slots
                       if not self._pins.get(self._slots[k])]
            victims = victims[:int(len(victims) * max(0.0, min(1.0, frac)))]
            for k in victims:
                self._free.append(self._slots.pop(k))
                self._heat.pop(k, None)
                self._checksums.pop(k, None)
            self.trimmed += len(victims)
            return len(victims)

    def audit(self) -> int:
        """Integrity audit: convict and quarantine poisoned resident
        pages.  Two passes — a cheap on-device ±inf scan
        (`ops.paged.pool_inf_counts`; inf is written by nothing in the
        staging path), then, under ``GSKY_POOL_AUDIT=1``, a CRC sweep
        against stage-time checksums.  Quarantined slots leave the page
        table immediately (future lookups miss and re-stage from the
        scene cache); a quarantined slot still pinned by an in-flight
        dispatch is recycled when its pin drops.  Returns the number of
        pages quarantined."""
        from ..ops.paged import pool_inf_counts
        with self.lock:
            if self._pool is None or not self._slots:
                return 0
            bad = []
            try:
                infs = np.asarray(pool_inf_counts(self._pool))
            except Exception:
                infs = None
            host = None
            if self._checksums:
                host = np.asarray(self._pool)
            for key, slot in list(self._slots.items()):
                poisoned = bool(infs is not None and infs[slot] > 0)
                if not poisoned and host is not None:
                    want = self._checksums.get(key)
                    if want is not None and \
                            zlib.crc32(host[slot].tobytes()) != want:
                        poisoned = True
                if not poisoned:
                    continue
                bad.append(key)
                self._slots.pop(key)
                self._heat.pop(key, None)
                self._checksums.pop(key, None)
                if self._pins.get(slot):
                    self._quarantine_pins.add(slot)
                else:
                    self._free.append(slot)
            self.quarantined += len(bad)
            return len(bad)

    def stats(self):
        with self.lock:
            return {
                "capacity": self.capacity,
                "page_shape": [self.page_rows, self.page_cols],
                "resident": len(self._slots),
                "pinned": len(self._pins),
                "staged": self.staged,
                "hits": self.hits,
                "evictions": self.evictions,
                "declined": self.declined,
                "teardowns": self.teardowns,
                "trimmed": self.trimmed,
                "rehydrated": self.rehydrated,
                "quarantined": self.quarantined,
                "peer_filled": self.peer_filled,
                "pool_bytes": (self.capacity * self.page_rows
                               * self.page_cols * 4),
            }


def union_table(members, i0: int, i1: int, j0: int, j1: int):
    """Halo-aware multi-tile page table: merge member slot rows into
    ONE row-major table over the union page rect (i0..i1) x (j0..j1).

    ``members`` is a list of (slots, mi0, mi1, mj0, mj1) where
    ``slots`` is the member's row-major (npages,) table over its own
    rect — exactly what `table_for` returned for it.  Pages are
    content-keyed, so members covering the same (pi, pj) agree on the
    slot; positions no member covers (halo gaps) keep slot 0, the
    reserved all-NaN null page, so a stray tap through a gap is
    invalid, never garbage.  No new pins and no staging: the union
    reuses the members' already-pinned slots (the autoplan superblock
    gather, docs/PERF.md "Dataflow planning")."""
    nj = int(j1) - int(j0) + 1
    ni = int(i1) - int(i0) + 1
    out = np.zeros(ni * nj, np.int32)
    for slots, mi0, mi1, mj0, mj1 in members:
        row = np.asarray(slots, np.int32).reshape(-1)
        mnj = int(mj1) - int(mj0) + 1
        for pi in range(int(mi0), int(mi1) + 1):
            for pj in range(int(mj0), int(mj1) + 1):
                out[(pi - int(i0)) * nj + (pj - int(j0))] = \
                    row[(pi - int(mi0)) * mnj + (pj - int(mj0))]
    return out


_default = None
_default_lock = threading.Lock()


def default_page_pool() -> PagePool:
    global _default
    with _default_lock:
        if _default is None:
            _default = PagePool()
        return _default


def reset_default_pool():
    """Test hook: drop the singleton so the next caller re-reads the
    GSKY_PAGE_* knobs."""
    global _default
    with _default_lock:
        _default = None
