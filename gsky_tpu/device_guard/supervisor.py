"""Device supervisor: every TPU dispatch supervised, every failure
survivable (docs/RESILIENCE.md "Device failures").

PRs 3/6/9 made the *fleet* survive faults; this module supervises the
*device*.  Four failure shapes are classified and routed:

- **hang** — a device sync (readback / block_until_ready) runs under a
  monitored deadline (``GSKY_DEVICE_HANG_S``, :func:`supervised_sync`);
  exceeding it raises :class:`DeviceHang` and marks the device suspect.
- **crash** — an ``XlaRuntimeError`` (or any INTERNAL-status runtime
  failure) out of a dispatch marks the device suspect; the request
  fails retryably (:class:`DeviceGuardError` subclasses
  ``BackendUnavailable``, so the gateway answers 503 + Retry-After and
  the worker client fails over without a breaker penalty).
- **oom** — ``RESOURCE_EXHAUSTED`` triggers the one-shot relief
  protocol (pool trim + pressure escalation) and a single retry
  before failing (:func:`run`).
- **corruption** — the readback integrity probe
  (:func:`integrity_check`; ±inf is never a legal output value — the
  pipeline encodes validity as NaN) quarantines poisoned pages via the
  pool audit when ``GSKY_POOL_AUDIT=1``, else falls back to a full
  rebuild.

State machine::

    healthy --incident--> suspect --backoff elapsed--> reinitializing
       ^                                                  |       |
       +------------------- rebuild ok -------------------+       |
                                          repeated rebuild failure v
                                                                 dead

A suspect device admits no dispatches until its jittered exponential
backoff (``GSKY_DEVICE_REINIT_BACKOFF`` = "base,cap" seconds) elapses;
the first dispatch past the deadline performs the rebuild inline —
teardown the page pool, probe the backend with a trivial synced op,
then warm-rehydrate the pool from the residency journal
(device_guard/journal.py).  Requests arriving mid-backoff get
:class:`DeviceReinitializing` with ``retry_after`` set to the remaining
wait, so the router routes around the node instead of queueing into it.

``GSKY_DEVICE_GUARD=0`` is the escape hatch: read per call, every
entry point returns to the exact pre-guard code path (asserted
byte-identical in tier-1).
"""

from __future__ import annotations

import os
import random
import threading
import time
from collections import deque
from typing import Callable, Optional

import numpy as np

from ..resilience.breaker import BackendUnavailable

HEALTHY, SUSPECT, REINITIALIZING, DEAD = 0, 1, 2, 3
STATE_NAMES = {HEALTHY: "healthy", SUSPECT: "suspect",
               REINITIALIZING: "reinitializing", DEAD: "dead"}

# consecutive failed rebuilds before the node declares itself dead and
# reports fatal through the fleet handshake
MAX_REINIT_FAILURES = 6


class DeviceGuardError(BackendUnavailable):
    """A supervised device failure.  Subclasses ``BackendUnavailable``
    so the gateway's existing handler answers 503 + Retry-After, and
    carries ``retryable`` so retry policies treat it like a transport
    fault rather than a caller bug."""

    retryable = True


class DeviceHang(DeviceGuardError):
    """A device sync exceeded its watchdog deadline."""


class DeviceCorruption(DeviceGuardError):
    """The output-integrity probe rejected a readback."""


class DeviceReinitializing(DeviceGuardError):
    """The device is mid-backoff or mid-rebuild; retry elsewhere."""


class DeviceDead(DeviceGuardError):
    """Rebuilds keep failing; only operator intervention recovers."""

    retryable = False


def guard_enabled() -> bool:
    """Escape hatch, read per call so it is live-tunable — the
    GSKY_PAGED idiom."""
    return os.environ.get("GSKY_DEVICE_GUARD", "1") != "0"


def hang_deadline_s() -> float:
    try:
        return float(os.environ.get("GSKY_DEVICE_HANG_S", "30"))
    except ValueError:
        return 30.0


def pool_audit_enabled() -> bool:
    return os.environ.get("GSKY_POOL_AUDIT", "") == "1"


def _backoff_spec() -> tuple:
    """GSKY_DEVICE_REINIT_BACKOFF = "base,cap" seconds (default
    "0.5,8"): attempt N waits min(cap, base * 2**N), jittered."""
    raw = os.environ.get("GSKY_DEVICE_REINIT_BACKOFF", "0.5,8")
    try:
        parts = [float(x) for x in raw.split(",")]
        base = max(0.01, parts[0])
        cap = max(base, parts[1]) if len(parts) > 1 else max(base, 8.0)
        return base, cap
    except (ValueError, IndexError):
        return 0.5, 8.0


def classify(exc: BaseException) -> Optional[str]:
    """Map an exception out of a device dispatch to an incident kind
    ("hang" / "oom" / "crash" / "corrupt"), or None for errors that are
    not the device's fault.  Matching is on status strings / type
    names, not jaxlib imports, so injected faults and real
    ``XlaRuntimeError`` failures ride the identical path."""
    if isinstance(exc, DeviceHang):
        return "hang"
    if isinstance(exc, DeviceCorruption):
        return "corrupt"
    msg = f"{type(exc).__name__}: {exc}"
    if "RESOURCE_EXHAUSTED" in msg or "Resource exhausted" in msg:
        return "oom"
    if type(exc).__name__ == "XlaRuntimeError" or "INTERNAL:" in msg:
        return "crash"
    return None


class DeviceSupervisor:
    """The per-process device state machine.  Thread-safe; the clock is
    injectable for tests (the PressureMonitor pattern)."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.RLock()
        self._rng = random.Random(0xD06)
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._state = HEALTHY
            self._since = self._clock()
            self._incident = ""     # kind that took the device out
            self._next_attempt = 0.0
            self._failures = 0      # consecutive failed rebuilds
            self.reinits = 0
            self.hangs = 0
            self.crashes = 0
            self.ooms = 0
            self.oom_retries = 0
            self.corruptions = 0
            self.quarantined_pages = 0
            self.rehydrated_pages = 0
            self.last_error = ""
            self.incidents: deque = deque(maxlen=32)

    # -- state ---------------------------------------------------------

    def state(self) -> int:
        with self._lock:
            return self._state

    def state_name(self) -> str:
        return STATE_NAMES[self.state()]

    def staging_ok(self) -> bool:
        """Page staging grows device residency — decline it the moment
        the device is anything but healthy (pages.table_for hook)."""
        return not guard_enabled() or self.state() == HEALTHY

    def _note(self, kind: str, site: str, exc=None) -> None:  # gskylint: holds-lock
        self.incidents.append({
            "kind": kind, "site": site, "t": round(self._clock(), 3),
            "error": str(exc)[:200] if exc is not None else ""})
        if exc is not None:
            self.last_error = f"{type(exc).__name__}: {exc}"[:200]

    def _mark_suspect(self, kind: str) -> None:  # gskylint: holds-lock
        # holds self._lock
        if self._state in (DEAD, REINITIALIZING):
            return
        self._incident = kind
        if self._state != SUSPECT:
            self._state = SUSPECT
            self._since = self._clock()
        base, cap = _backoff_spec()
        delay = min(cap, base * (2.0 ** self._failures))
        delay *= 0.5 + self._rng.random()       # jitter 0.5x .. 1.5x
        self._next_attempt = self._clock() + delay

    # -- incident recording --------------------------------------------

    def record_hang(self, site: str, exc=None) -> None:
        with self._lock:
            self.hangs += 1
            self._note("hang", site, exc)
            self._mark_suspect("hang")

    def record_crash(self, site: str, exc=None) -> None:
        with self._lock:
            self.crashes += 1
            self._note("crash", site, exc)
            self._mark_suspect("crash")

    def record_oom(self, site: str, exc=None, fatal: bool = False) -> None:
        """A RESOURCE_EXHAUSTED.  Non-fatal OOMs ride the relief+retry
        protocol and do NOT suspect the device; a fatal one (the retry
        also exhausted) does."""
        with self._lock:
            self.ooms += 1
            self._note("oom", site, exc)
            if fatal:
                self._mark_suspect("oom")

    def record_corruption(self, site: str, exc=None) -> None:
        """A poisoned readback.  With GSKY_POOL_AUDIT=1 the pool's
        checksum audit runs first: if it finds and quarantines the
        poisoned pages, the device stays in service (re-staging heals
        it); otherwise fall back to a full suspect->rebuild cycle."""
        with self._lock:
            self.corruptions += 1
            self._note("corrupt", site, exc)
        quarantined = 0
        if pool_audit_enabled():
            try:
                from ..pipeline import pages
                if pages._default is not None:
                    quarantined = pages._default.audit()
            except Exception:
                quarantined = 0
        with self._lock:
            self.quarantined_pages += quarantined
            if quarantined <= 0:
                self._mark_suspect("corrupt")

    # -- admission + rebuild -------------------------------------------

    def admit(self, site: str = "dispatch") -> None:
        """Gate a dispatch on device health.  Healthy passes for free;
        suspect raises retryably until the backoff elapses, then the
        admitting thread performs the rebuild inline (the request pays
        the rehydration latency — everyone after it gets a warm pool)."""
        if not guard_enabled():
            return
        with self._lock:
            st = self._state
            if st == HEALTHY:
                return
            if st == DEAD:
                raise DeviceDead(
                    f"device dead after {self._failures} failed rebuilds"
                    f" (last: {self.last_error or self._incident})",
                    site=site, retry_after=60.0)
            now = self._clock()
            if st == REINITIALIZING or now < self._next_attempt:
                raise DeviceReinitializing(
                    f"device {STATE_NAMES[st]} after {self._incident}",
                    site=site,
                    retry_after=max(0.1, self._next_attempt - now))
            self._state = REINITIALIZING
        ok = False
        try:
            ok = self._reinitialize()
        finally:
            with self._lock:
                if ok:
                    self._state = HEALTHY
                    self._failures = 0
                    self._incident = ""
                    self._since = self._clock()
                else:
                    self._failures += 1
                    if self._failures >= MAX_REINIT_FAILURES:
                        self._state = DEAD
                    else:
                        self._state = SUSPECT
                        self._mark_suspect(self._incident or "crash")
        if not ok:
            raise DeviceReinitializing(
                f"device rebuild failed ({self.last_error})", site=site,
                retry_after=max(0.1, self._next_attempt - self._clock()))

    def _reinitialize(self) -> bool:
        """Tear down + rebuild: journal-dump and drop the page pool,
        prove the backend answers with a trivial synced op (under the
        hang watchdog — a still-wedged device must fail the rebuild,
        not block it), then warm-rehydrate the pool."""
        with self._lock:
            self.reinits += 1
        try:
            pool = None
            try:
                from ..pipeline import pages
                pool = pages._default
            except Exception:
                pool = None
            if pool is not None:
                pool.teardown()
            import jax
            import jax.numpy as jnp
            try:
                backend = jax.default_backend()
            except Exception:
                backend = "cpu"
            if backend not in ("cpu",):
                # a real accelerator rebuild must not reuse executables
                # compiled against the pre-incident device state
                jax.clear_caches()
            supervised_sync(
                "device.probe",
                lambda: jax.block_until_ready(
                    jnp.zeros((8,), jnp.float32) + 1.0))
            restored = 0
            if pool is not None:
                restored = pool.rehydrate()
            with self._lock:
                self.rehydrated_pages += restored
            return True
        except Exception as e:   # noqa: BLE001 - any failure = not ok
            with self._lock:
                self.last_error = f"{type(e).__name__}: {e}"[:200]
            return False

    # -- reporting ------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            now = self._clock()
            return {
                "enabled": guard_enabled(),
                "state": STATE_NAMES[self._state],
                "state_code": self._state,
                "since_s": round(now - self._since, 3),
                "incident": self._incident,
                "retry_in_s": round(max(0.0, self._next_attempt - now), 3)
                if self._state in (SUSPECT, REINITIALIZING) else 0.0,
                "reinit_failures": self._failures,
                "reinits": self.reinits,
                "hangs": self.hangs,
                "crashes": self.crashes,
                "ooms": self.ooms,
                "oom_retries": self.oom_retries,
                "corruptions": self.corruptions,
                "quarantined_pages": self.quarantined_pages,
                "rehydrated_pages": self.rehydrated_pages,
                "hang_deadline_s": hang_deadline_s(),
                "audit": pool_audit_enabled(),
                "last_error": self.last_error,
                "incidents": list(self.incidents),
            }


_default = DeviceSupervisor()


def default_supervisor() -> DeviceSupervisor:
    return _default


def staging_ok() -> bool:
    return _default.staging_ok()


_UNSET = object()

# -- two-in-flight wave supervision -------------------------------------
#
# The pipelined wave scheduler (pipeline/waves.py) keeps TWO waves in
# flight: wave N executing on device while wave N+1 stages its uploads.
# A staging-side sync that exceeds the watchdog is almost never the
# staging wave's fault — device_put serialises behind the executing
# program's stream, so a hung kernel presents as a hung *upload* on the
# assembly thread.  Execution windows let the watchdog attribute such a
# hang to the wave that is actually wedging the device.

_exec_lock = threading.Lock()
_exec_windows: dict = {}     # id(token) -> (site, t_start)
_exec_seq = [0]


def _staging_site(site: str) -> bool:
    """Staging-class sites: device uploads issued AHEAD of the program
    that will consume them (``wave.stage`` / ``mesh.stage``)."""
    return site.endswith(".stage")


class execution_window:
    """Marks ``site`` as the device program currently executing, for
    hang attribution while a second (staging) wave is in flight."""

    def __init__(self, site: str):
        self.site = site

    def __enter__(self):
        with _exec_lock:
            _exec_seq[0] += 1
            self._key = _exec_seq[0]
            _exec_windows[self._key] = (self.site, time.monotonic())
        return self

    def __exit__(self, *exc):
        with _exec_lock:
            _exec_windows.pop(self._key, None)
        return False


def attribute_hang(site: str) -> str:
    """Resolve which wave a watchdog timeout belongs to.

    A hang at an executing site is its own; a hang at a *staging* site
    while an older execution window is still open is attributed to the
    executing wave (the staging upload queued behind the wedged
    program).  With no execution window open, the staging site keeps
    the blame — the upload itself wedged."""
    if not _staging_site(site):
        return site
    with _exec_lock:
        live = sorted(_exec_windows.values(), key=lambda p: p[1])
    return live[0][0] if live else site


def supervised_sync(site: str, thunk: Callable,
                    deadline_s: Optional[float] = None):
    """Run a device sync under the hang watchdog.

    The sync executes on a daemon thread joined with the deadline: a
    hung ``np.asarray`` / ``block_until_ready`` cannot be interrupted
    from its own thread, so on timeout the orphaned thread is abandoned
    to the wedged runtime and the *caller* gets :class:`DeviceHang`
    (the supervisor is marked suspect first).  Fault-injection site
    ``device`` fires inside the watchdog scope, so ``device:hang:..``
    specs exercise the real deadline path.
    """
    if not guard_enabled():
        return thunk()
    deadline = hang_deadline_s() if deadline_s is None else deadline_s
    out = [_UNSET, None]

    def _run():
        try:
            from ..resilience import faults
            faults.inject("device")
            if _staging_site(site):
                out[0] = thunk()
            else:
                # window held by the SYNC thread: a hung dispatch keeps
                # its window open after the watchdog abandons it, so a
                # staging hang queued behind it attributes correctly
                with execution_window(site):
                    out[0] = thunk()
        except BaseException as e:   # noqa: BLE001 - re-raised below
            out[1] = e

    t = threading.Thread(target=_run, daemon=True, name="gsky-devsync")
    t.start()
    t.join(deadline if deadline > 0 else None)
    if t.is_alive():
        blame = attribute_hang(site)
        _default.record_hang(blame)
        detail = "" if blame == site else \
            f" (attributed to executing {blame!r})"
        raise DeviceHang(
            f"device sync {site!r} exceeded {deadline:.3g}s"
            f" watchdog{detail}", site=blame)
    if out[1] is not None:
        raise out[1]
    return out[0]


def _oom_relief() -> None:
    """The one-shot RESOURCE_EXHAUSTED relief protocol: trim the page
    pool's cold half and escalate the pressure monitor (cache relief +
    admission clamp + brownout)."""
    try:
        from ..pipeline import pages
        if pages._default is not None:
            pages._default.trim(0.5)
    except Exception:  # no page pool allocated yet - nothing to trim
        pass
    try:
        from ..resilience.pressure import default_monitor
        default_monitor().escalate()
    except Exception:  # pressure monitor absent - relief is best-effort
        pass


def run(site: str, thunk: Callable, reduced: Optional[Callable] = None):
    """Execute a device dispatch under full supervision: admission
    gate, fault injection, hang watchdog, incident classification, and
    the OOM relief+retry protocol.  ``reduced``, when given, is the
    reduced-batch variant used for the post-relief retry.

    With ``GSKY_DEVICE_GUARD=0`` this is exactly ``thunk()``.
    """
    if not guard_enabled():
        return thunk()
    sup = _default
    sup.admit(site)
    try:
        return supervised_sync(site, thunk)
    except DeviceGuardError:
        raise                   # hang: already recorded and typed
    except Exception as e:
        kind = classify(e)
        if kind == "oom":
            sup.record_oom(site, e)
            _oom_relief()
            retry = reduced if reduced is not None else thunk
            try:
                result = supervised_sync(site, retry)
            except DeviceGuardError:
                raise
            except Exception as e2:
                sup.record_oom(site, e2, fatal=True)
                raise DeviceGuardError(
                    f"device OOM at {site!r} persisted after relief:"
                    f" {e2}", site=site) from e2
            with sup._lock:
                sup.oom_retries += 1
            return result
        if kind == "crash":
            sup.record_crash(site, e)
            raise DeviceGuardError(
                f"device crash at {site!r}: {e}", site=site) from e
        raise


def integrity_check(site: str, arr) -> None:
    """The cheap output-integrity probe: sample the readback on a
    stride and reject it if any value is ±inf.  NaN is the pipeline's
    legal validity encoding and appears in every off-footprint region;
    inf is produced by NOTHING in the render path, so its presence
    means the device (or the DMA back from it) corrupted the buffer."""
    if not guard_enabled():
        return
    try:
        a = np.asarray(arr)
    except Exception:
        return
    if a.dtype.kind != "f" or a.size == 0:
        return
    flat = a.reshape(-1)
    step = max(1, flat.size // 4096)
    if np.isinf(flat[::step]).any():
        _default.record_corruption(site)
        raise DeviceCorruption(
            f"readback at {site!r} failed the integrity probe"
            " (non-finite beyond NaN validity)", site=site)


def _poison(arr):
    """device:corrupt injection: flip alternate floats to inf on a COPY
    of the readback — the shape a flaky HBM/DMA bit-flip presents."""
    a = np.array(arr, copy=True)
    if a.dtype.kind == "f" and a.size:
        a.reshape(-1)[::2] = np.inf
    return a


def guarded_readback(site: str, thunk: Callable):
    """Supervised readback: :func:`run` (watchdog + classification)
    plus corruption injection and the integrity probe on the result."""
    if not guard_enabled():
        return thunk()
    arr = run(site, thunk)
    from ..resilience import faults
    if faults.flag("device", "corrupt"):
        arr = _poison(arr)
    integrity_check(site, arr)
    return arr


def reset() -> None:
    """Test hook: fresh supervisor state.  Registered OOM hooks are
    kept — they are wired once at executor construction and must
    survive test resets the way the executor singleton does."""
    with _exec_lock:
        _exec_windows.clear()
    _default.reset()
