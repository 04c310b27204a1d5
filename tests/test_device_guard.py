"""Device guard (`gsky_tpu/device_guard/`, docs/RESILIENCE.md "Device
failures"): hang watchdog, incident classification, the suspect ->
reinitializing -> healthy/dead state machine with jittered backoff,
warm pool recovery through the page-residency journal, the OOM
relief+retry protocol, the output-integrity probe + pool audit
quarantine, worker crash-loop protection, and the GSKY_DEVICE_GUARD=0
byte-identity escape hatch."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from gsky_tpu import device_guard as dg
from gsky_tpu.device_guard import journal
from gsky_tpu.device_guard.supervisor import (DEAD, HEALTHY,
                                              MAX_REINIT_FAILURES,
                                              SUSPECT, DeviceSupervisor)
from gsky_tpu.pipeline.pages import PagePool
from gsky_tpu.resilience import faults
from gsky_tpu.resilience.pressure import default_monitor

PR, PC = 64, 128


@pytest.fixture(autouse=True)
def _hermetic(tmp_path, monkeypatch):
    """Per-test journal/ledger files and clean global state on both
    sides — supervisor incidents must never leak across tests."""
    monkeypatch.setenv("GSKY_POOL_JOURNAL", str(tmp_path / "journal.jsonl"))
    monkeypatch.setenv("GSKY_KERNEL_LEDGER", str(tmp_path / "ledger.jsonl"))
    import gsky_tpu.resilience as resilience
    resilience.reset()
    yield
    resilience.reset()


def _pool(cap=16):
    return PagePool(capacity=cap, page_rows=PR, page_cols=PC)


def _scene(seed=0, rows=2 * PR, cols=2 * PC):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(1.0, 100.0, (rows, cols))
                       .astype(np.float32))


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# classification + watchdog
# ---------------------------------------------------------------------------


class TestClassify:
    def test_matrix(self):
        assert dg.classify(dg.DeviceHang("h", site="s")) == "hang"
        assert dg.classify(dg.DeviceCorruption("c", site="s")) == "corrupt"
        assert dg.classify(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "oom"
        assert dg.classify(RuntimeError("Resource exhausted: HBM")) == "oom"
        assert dg.classify(RuntimeError("INTERNAL: stream failed")) == "crash"
        # type-name matching: a real jaxlib XlaRuntimeError classifies
        # even when its message carries no status prefix
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert dg.classify(XlaRuntimeError("boom")) == "crash"
        assert dg.classify(ValueError("caller bug")) is None
        assert dg.classify(RuntimeError("plain failure")) is None

    def test_injected_faults_ride_the_string_path(self):
        oom = faults.InjectedDeviceFault("dispatch.paged", "oom")
        crash = faults.InjectedDeviceFault("dispatch.paged", "crash")
        assert dg.classify(oom) == "oom"
        assert dg.classify(crash) == "crash"


class TestWatchdog:
    def test_hang_raises_and_suspects(self):
        release = threading.Event()
        with pytest.raises(dg.DeviceHang):
            dg.supervised_sync("t.hang", release.wait, deadline_s=0.1)
        release.set()       # let the orphaned thread exit
        sup = dg.default_supervisor()
        st = sup.stats()
        assert st["hangs"] == 1
        assert st["state"] == "suspect" and st["incident"] == "hang"

    def test_fast_sync_passes_and_exceptions_propagate(self):
        assert dg.supervised_sync("t.ok", lambda: 7, deadline_s=5.0) == 7
        with pytest.raises(ValueError):
            dg.supervised_sync("t.raise", self._boom, deadline_s=5.0)
        assert dg.default_supervisor().state() == HEALTHY

    @staticmethod
    def _boom():
        raise ValueError("caller bug")

    def test_injected_hang_fires_inside_watchdog(self, monkeypatch):
        """device:hang:<ms> sleeps inside the watchdog thread, so a
        deadline shorter than the injected sleep trips the REAL hang
        path — no test-only branches."""
        faults.configure("device:hang:30s")
        monkeypatch.setenv("GSKY_DEVICE_HANG_S", "0.1")
        with pytest.raises(dg.DeviceHang):
            dg.supervised_sync("t.inj", lambda: 1)
        assert dg.default_supervisor().stats()["hangs"] == 1


# ---------------------------------------------------------------------------
# state machine + rebuild
# ---------------------------------------------------------------------------


class TestStateMachine:
    def test_suspect_backoff_then_inline_rebuild(self, monkeypatch):
        monkeypatch.setenv("GSKY_DEVICE_REINIT_BACKOFF", "1,8")
        clock = FakeClock()
        sup = DeviceSupervisor(clock=clock)
        sup.record_crash("t", RuntimeError("INTERNAL: dead stream"))
        assert sup.state() == SUSPECT
        # mid-backoff: retryable refusal carrying the remaining wait
        with pytest.raises(dg.DeviceReinitializing) as ei:
            sup.admit("t")
        assert ei.value.retryable and ei.value.retry_after > 0
        assert sup.reinits == 0
        # jitter is 0.5x..1.5x of min(cap, base*2^0): 1.5s clears it
        clock.t += 1.6
        sup.admit("t")      # first dispatch past the deadline rebuilds
        assert sup.state() == HEALTHY
        assert sup.reinits == 1
        assert sup.stats()["reinit_failures"] == 0

    def test_repeated_rebuild_failure_goes_dead(self, monkeypatch):
        monkeypatch.setenv("GSKY_DEVICE_REINIT_BACKOFF", "0.1,0.2")
        clock = FakeClock()
        sup = DeviceSupervisor(clock=clock)
        monkeypatch.setattr(sup, "_reinitialize", lambda: False)
        sup.record_hang("t")
        for _ in range(MAX_REINIT_FAILURES):
            clock.t += 1.0
            with pytest.raises(dg.DeviceReinitializing):
                sup.admit("t")
        assert sup.state() == DEAD
        with pytest.raises(dg.DeviceDead) as ei:
            sup.admit("t")
        assert not ei.value.retryable
        assert sup.stats()["state"] == "dead"

    def test_backoff_grows_with_failures(self, monkeypatch):
        monkeypatch.setenv("GSKY_DEVICE_REINIT_BACKOFF", "1,64")
        clock = FakeClock()
        sup = DeviceSupervisor(clock=clock)
        monkeypatch.setattr(sup, "_reinitialize", lambda: False)
        sup.record_crash("t")
        first = sup._next_attempt - clock.t
        clock.t = sup._next_attempt + 0.01
        with pytest.raises(dg.DeviceReinitializing):
            sup.admit("t")
        second = sup._next_attempt - clock.t
        # attempt 1 waits ~base, attempt 2 ~2*base; jitter is 0.5..1.5x
        # so the doubled delay always exceeds the undoubled one's floor
        assert 0.5 <= first <= 1.5
        assert 1.0 <= second <= 3.0

    def test_staging_declined_while_suspect(self):
        """pages.table_for declines (and rolls back nothing) the moment
        the supervisor is not healthy — staging into a pool about to be
        torn down is wasted HBM traffic."""
        pool = _pool()
        dev = _scene()
        sup = dg.default_supervisor()
        sup.record_crash("t", RuntimeError("INTERNAL: x"))
        try:
            assert pool.table_for(dev, 1, 0, 1, 0, 1) is None
            assert pool.stats()["declined"] == 1
            assert pool.stats()["pinned"] == 0
        finally:
            sup.reset()
        t = pool.table_for(dev, 1, 0, 1, 0, 1)
        assert t is not None and len(t) == 4
        pool.unpin(t)


class TestRebuildLifecycle:
    def test_run_crash_reinit_rehydrate(self, monkeypatch):
        """End-to-end on CPU: a crash out of run() suspects the device;
        after the backoff the next admit tears the pool down (journals
        the hot set), probes the backend, and rehydrates the hottest
        pages from the scene cache."""
        monkeypatch.setenv("GSKY_DEVICE_REINIT_BACKOFF", "0.01,0.02")
        from gsky_tpu.pipeline import pages
        from gsky_tpu.pipeline import scene_cache as sc_mod
        pool = _pool()
        monkeypatch.setattr(pages, "_default", pool)
        dev = _scene()
        serial = 42
        monkeypatch.setitem(
            sc_mod.default_scene_cache._scenes, ("dgtest", serial),
            SimpleNamespace(serial=serial, dev=dev))
        try:
            t = pool.table_for(dev, serial, 0, 1, 0, 1)
            pool.unpin(t)
            # make page (0,0) the hottest via repeat hits
            for _ in range(3):
                t = pool.table_for(dev, serial, 0, 0, 0, 0)
                pool.unpin(t)
            with pytest.raises(dg.DeviceGuardError):
                dg.run("t.dispatch",
                       self._raise_internal)
            sup = dg.default_supervisor()
            assert sup.state() == SUSPECT and sup.crashes == 1
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                try:
                    assert dg.run("t.dispatch", lambda: 11) == 11
                    break
                except dg.DeviceReinitializing:
                    time.sleep(0.02)
            else:
                pytest.fail("device never readmitted")
            st = sup.stats()
            assert st["state"] == "healthy" and st["reinits"] == 1
            ps = pool.stats()
            assert ps["teardowns"] == 1
            assert ps["rehydrated"] == 4        # full hot set restored
            assert st["rehydrated_pages"] == 4
            # the hottest page went back in first
            assert next(iter(pool._slots)) == (serial, 0, 0)
        finally:
            sc_mod.default_scene_cache._scenes.pop(("dgtest", serial),
                                                   None)

    @staticmethod
    def _raise_internal():
        raise RuntimeError("INTERNAL: GPU stream failed")


# ---------------------------------------------------------------------------
# OOM relief + retry
# ---------------------------------------------------------------------------


class TestOOMRetry:
    def test_relief_then_retry_succeeds(self, monkeypatch):
        from gsky_tpu.pipeline import pages
        pool = _pool()
        monkeypatch.setattr(pages, "_default", pool)
        dev = _scene()
        t = pool.table_for(dev, 7, 0, 1, 0, 1)
        pool.unpin(t)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: HBM exhausted")
            return "ok"

        assert dg.run("t.oom", flaky) == "ok"
        sup = dg.default_supervisor()
        st = sup.stats()
        assert st["ooms"] == 1 and st["oom_retries"] == 1
        assert st["state"] == "healthy"     # non-fatal OOM: no suspect
        assert pool.stats()["trimmed"] == 2     # cold half released
        assert default_monitor().stats()["escalations"] == 1

    def test_reduced_variant_used_for_retry(self):
        seen = []

        def full():
            raise RuntimeError("RESOURCE_EXHAUSTED: HBM")

        def reduced():
            seen.append("reduced")
            return 3

        assert dg.run("t.oom", full, reduced=reduced) == 3
        assert seen == ["reduced"]

    def test_persistent_oom_is_fatal(self):
        def full():
            raise RuntimeError("RESOURCE_EXHAUSTED: HBM")

        with pytest.raises(dg.DeviceGuardError):
            dg.run("t.oom", full)
        st = dg.default_supervisor().stats()
        assert st["ooms"] == 2
        assert st["state"] == "suspect" and st["incident"] == "oom"


# ---------------------------------------------------------------------------
# corruption: probe, injection, audit quarantine
# ---------------------------------------------------------------------------


class TestIntegrity:
    def test_nan_is_legal_inf_convicts(self):
        ok = np.full((64, 64), np.nan, np.float32)
        dg.integrity_check("t", ok)     # all-NaN tile: fine
        bad = ok.copy()
        bad[5, 5] = np.inf
        with pytest.raises(dg.DeviceCorruption):
            dg.integrity_check("t", bad)
        st = dg.default_supervisor().stats()
        assert st["corruptions"] == 1 and st["state"] == "suspect"

    def test_guarded_readback_corrupt_injection(self):
        faults.configure("device:corrupt:1")
        src = np.ones((32, 32), np.float32)
        with pytest.raises(dg.DeviceCorruption):
            dg.guarded_readback("t.rb", lambda: src)
        # the poison hit a COPY, never the caller's buffer
        assert np.isfinite(src).all()
        assert dg.default_supervisor().stats()["corruptions"] == 1

    def test_audit_quarantines_bad_checksum(self, monkeypatch):
        monkeypatch.setenv("GSKY_POOL_AUDIT", "1")
        pool = _pool()
        dev = _scene()
        t = pool.table_for(dev, 9, 0, 1, 0, 1)
        pool.unpin(t)
        assert len(pool._checksums) == 4    # stage-time CRCs kept
        victim = (9, 0, 1)
        pool._checksums[victim] = 0xBAD     # simulate a flipped page
        assert pool.audit() == 1
        assert victim not in pool._slots
        assert pool.stats()["quarantined"] == 1
        # quarantined slot is free again: re-staging heals it
        t = pool.table_for(dev, 9, 0, 1, 0, 1)
        assert t is not None
        pool.unpin(t)

    def test_audited_corruption_keeps_device_in_service(self, monkeypatch):
        """With the audit finding a culprit page, record_corruption
        quarantines instead of suspecting the whole device."""
        monkeypatch.setenv("GSKY_POOL_AUDIT", "1")
        from gsky_tpu.pipeline import pages
        pool = _pool()
        monkeypatch.setattr(pages, "_default", pool)
        dev = _scene()
        t = pool.table_for(dev, 9, 0, 0, 0, 0)
        pool.unpin(t)
        pool._checksums[(9, 0, 0)] = 0xBAD
        sup = dg.default_supervisor()
        sup.record_corruption("t")
        st = sup.stats()
        assert st["quarantined_pages"] == 1
        assert st["state"] == "healthy"
        # no culprit found -> full suspect/rebuild fallback
        sup.record_corruption("t")
        assert sup.stats()["state"] == "suspect"

    def test_quarantined_pinned_slot_recycles_on_unpin(self, monkeypatch):
        monkeypatch.setenv("GSKY_POOL_AUDIT", "1")
        pool = _pool()
        dev = _scene()
        t = pool.table_for(dev, 9, 0, 0, 0, 0)      # pinned
        pool._checksums[(9, 0, 0)] = 0xBAD
        free_before = len(pool._free)
        assert pool.audit() == 1
        assert len(pool._free) == free_before       # pinned: held back
        pool.unpin(t)
        assert len(pool._free) == free_before + 1   # recycled now


# ---------------------------------------------------------------------------
# journal + warm recovery
# ---------------------------------------------------------------------------


class TestJournal:
    def test_replay_orders_hottest_first(self):
        journal.record_stage(1, 0, 0)
        journal.record_stage(1, 0, 1)
        journal.record_heat(1, 0, 1, hits=17)
        journal.record_stage(2, 3, 0)
        assert journal.replay() == [(1, 0, 1), (2, 3, 0), (1, 0, 0)]

    def test_drop_voids_earlier_events(self):
        journal.record_stage(1, 0, 0)
        journal.record_heat(1, 0, 0, hits=99)
        journal.record_stage(2, 0, 0)
        journal.record_drop(1)
        assert journal.replay() == [(2, 0, 0)]
        # a re-stage AFTER the drop is live again
        journal.record_stage(1, 5, 5)
        assert (1, 5, 5) in journal.replay()

    def test_corrupt_and_foreign_lines_skipped(self, tmp_path):
        journal.record_stage(1, 0, 0)
        with open(journal.journal_path(), "a") as fp:
            fp.write("{torn json\n")
            fp.write('{"v": 99, "op": "stage", "serial": 9, '
                     '"pi": 0, "pj": 0}\n')          # newer schema
            fp.write('{"v": 1, "op": "nuke", "serial": 9}\n')
            fp.write('{"v": 1, "op": "stage", "serial": 9, '
                     '"pi": -1, "pj": 0}\n')         # negative coords
            fp.write('{"v": 1, "op": "stage", "serial": "x", '
                     '"pi": 0, "pj": 0}\n')          # non-int serial
            fp.write("[1, 2, 3]\n")
        assert journal.replay() == [(1, 0, 0)]

    def test_disabled_journal_writes_nothing(self, monkeypatch,
                                             tmp_path):
        monkeypatch.setenv("GSKY_POOL_JOURNAL", "0")
        assert not journal.journal_enabled()
        journal.record_stage(1, 0, 0)
        assert journal.replay() == []

    def test_rehydrate_skips_stale_entries(self, monkeypatch):
        """Entries for evicted scenes and out-of-grid pages are skipped
        without consuming pool slots."""
        from gsky_tpu.pipeline import scene_cache as sc_mod
        pool = _pool()
        dev = _scene()                       # 2x2 page grid
        monkeypatch.setitem(
            sc_mod.default_scene_cache._scenes, ("dgstale", 5),
            SimpleNamespace(serial=5, dev=dev))
        journal.record_stage(5, 0, 0)        # live
        journal.record_stage(5, 7, 0)        # outside the 2x2 grid
        journal.record_stage(6, 0, 0)        # scene 6 evicted
        try:
            assert pool.rehydrate() == 1
            assert list(pool._slots) == [(5, 0, 0)]
        finally:
            sc_mod.default_scene_cache._scenes.pop(("dgstale", 5), None)

    def test_teardown_clears_state_and_lru_restored(self):
        pool = _pool(cap=4)                 # 3 usable slots (0 is null)
        dev = _scene()
        t = pool.table_for(dev, 3, 0, 1, 0, 0)      # 2 pages
        pool.unpin(t)
        pool.teardown()
        assert pool.stats()["resident"] == 0
        assert pool._pool is None and not pool._pins
        # the freelist is whole again: 3 stages fit, 4th LRU-evicts
        t = pool.table_for(dev, 3, 0, 1, 0, 1)
        assert t is None or len(t) <= 4     # capacity 4 => may decline
        if t is not None:
            pool.unpin(t)


# ---------------------------------------------------------------------------
# escape hatch
# ---------------------------------------------------------------------------


class TestEscapeHatch:
    def test_guard_off_is_byte_identical_passthrough(self, monkeypatch):
        """GSKY_DEVICE_GUARD=0: every entry point returns thunk()
        directly — even a dead supervisor and a poisoned readback are
        invisible, and the bytes are exactly the unguarded path's."""
        sup = dg.default_supervisor()
        sup.record_crash("t")               # suspect while guard is ON
        monkeypatch.setenv("GSKY_DEVICE_GUARD", "0")
        assert dg.run("t", lambda: 5) == 5  # no admit gate
        assert sup.staging_ok()             # staging not declined
        faults.configure("device:corrupt:1")
        src = np.ones((16, 16), np.float32)
        src[0, 0] = np.inf                  # would convict with guard on
        out = dg.guarded_readback("t", lambda: src)
        assert out is src                   # same object, zero copies
        release = threading.Event()
        try:
            # no watchdog thread either: the sync runs inline
            assert dg.supervised_sync("t", lambda: 9,
                                      deadline_s=0.0001) == 9
        finally:
            release.set()

    def test_executor_render_identical_with_guard_off(self, monkeypatch):
        """Executor-level byte identity: the same mosaic renders to the
        same bytes with the guard on and off (the tier-1 acceptance
        assertion for the escape hatch)."""
        import test_paged
        from gsky_tpu.pipeline import pages
        from gsky_tpu.pipeline.executor import WarpExecutor
        monkeypatch.setenv("GSKY_PAGE_SIZE", "64x128")
        monkeypatch.setenv("GSKY_PAGE_POOL_MB", "8")
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        group = test_paged._fake_group()
        monkeypatch.setattr(WarpExecutor, "_scene_groups",
                            lambda self, *a, **kw: [group])
        args = (None, [0, 0, 1], [3.0, 2.0, 1.0], None, None, 96, 96,
                2, "near")
        pages.reset_default_pool()
        try:
            c1, v1 = WarpExecutor().warp_mosaic_scenes(*args)
            monkeypatch.setenv("GSKY_DEVICE_GUARD", "0")
            pages.reset_default_pool()
            c0, v0 = WarpExecutor().warp_mosaic_scenes(*args)
            np.testing.assert_array_equal(np.asarray(c1),
                                          np.asarray(c0))
            np.testing.assert_array_equal(np.asarray(v1),
                                          np.asarray(v0))
        finally:
            pages.reset_default_pool()


# ---------------------------------------------------------------------------
# worker crash-loop protection (satellite: worker/pool.py)
# ---------------------------------------------------------------------------


class TestCrashLoop:
    def test_breaker_trips_inside_window_only(self):
        from gsky_tpu.worker.pool import CrashLoopBreaker
        clock = FakeClock()
        b = CrashLoopBreaker(max_crashes=3, window_s=60.0, clock=clock)
        # slow drip: one crash a minute never trips
        for _ in range(5):
            assert not b.record()
            clock.t += 61.0
        assert not b.tripped
        # burst: three inside the window latches tripped
        for _ in range(3):
            b.record()
        assert b.tripped
        st = b.stats()
        assert st["tripped"] and st["respawns"] == 8

    def test_respawn_backoff_grows_jittered(self):
        from gsky_tpu.worker.pool import (RESPAWN_BACKOFF_CAP_S,
                                          _respawn_backoff)
        lo = _respawn_backoff(0, rand=lambda: 0.0)
        hi = _respawn_backoff(0, rand=lambda: 1.0)
        assert lo == pytest.approx(0.25) and hi == pytest.approx(0.75)
        assert _respawn_backoff(3, rand=lambda: 0.5) == pytest.approx(4.0)
        # capped: a long outage never waits unboundedly
        assert _respawn_backoff(30, rand=lambda: 1.0) \
            <= RESPAWN_BACKOFF_CAP_S * 1.5

    def test_worker_info_carries_device_and_crash_state(self):
        """The client folds the worker's info_json device/pool blocks
        into fleet health: dead device or tripped breaker is fatal."""
        import json
        from gsky_tpu.worker import gskyrpc_pb2 as pb
        from gsky_tpu.worker.client import WorkerClient
        res = pb.Result()
        res.info_json = json.dumps({
            "draining": False,
            "device": {"state": "dead"},
            "pool": {"crash_loop": {"tripped": True}}})
        info = WorkerClient._info(res)
        assert info["device"]["state"] == "dead"
        assert info["pool"]["crash_loop"]["tripped"]
        assert not WorkerClient._draining(res)
        res.info_json = "{torn"
        assert WorkerClient._info(res) == {}


# ---------------------------------------------------------------------------
# supervisor surfaces
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_stats_shape(self):
        st = dg.default_supervisor().stats()
        for key in ("enabled", "state", "state_code", "incident",
                    "reinits", "hangs", "crashes", "ooms", "oom_retries",
                    "corruptions", "quarantined_pages",
                    "rehydrated_pages", "hang_deadline_s", "audit",
                    "incidents"):
            assert key in st
        assert st["state"] == "healthy" and st["state_code"] == HEALTHY

    def test_debug_block_present(self):
        from gsky_tpu.server.metrics import MetricsLogger
        doc = MetricsLogger().summary()
        assert doc["device"]["state"] == "healthy"
        assert "journal" in doc["device"]

    def test_run_passes_noise_through_unclassified(self):
        """Errors that are not the device's fault surface unchanged —
        the guard must not eat caller bugs."""
        def boom():
            raise KeyError("caller bug")

        with pytest.raises(KeyError):
            dg.run("t", boom)
        st = dg.default_supervisor().stats()
        assert st["state"] == "healthy"
        assert st["crashes"] == 0 and st["ooms"] == 0
