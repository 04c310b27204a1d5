"""Wave-level device serving (`pipeline/waves.py` + the output ring in
`ops/paged.py`): mixed-kind wave assembly, ragged occupancy, per-call
byte parity under GSKY_WAVES=0, cancellation at assembly, individual
failover on a device incident mid-wave, and readback-queue ordering."""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

import test_paged
from gsky_tpu.ops.drill import masked_mean_impl
from gsky_tpu.ops.paged import OutputRing
from gsky_tpu.ops.warp import render_scenes_ctrl, \
    warp_scenes_ctrl_scored
from gsky_tpu.pipeline import waves as W
from gsky_tpu.resilience import CancelToken, RequestCancelled, \
    cancel_scope


@pytest.fixture(autouse=True)
def _tmp_ledger(tmp_path, monkeypatch):
    """Hermetic race ledger per test (same rule as tests/test_paged.py)."""
    monkeypatch.setenv("GSKY_KERNEL_LEDGER",
                       str(tmp_path / "ledger.jsonl"))


@pytest.fixture(autouse=True)
def _fresh_waves():
    """Isolate the module singleton: a scheduler left over from another
    test module must not swallow this module's assertions (and vice
    versa)."""
    W.reset_waves()
    yield
    W.reset_waves()


def _byte_statics(n_ns, h, w, step):
    return ("near", n_ns, (h, w), step, True, 0)


def _submit_byte(sched, pool, tile, staged, sp, statics, results,
                 errors, i, percall=None):
    stack, ctrl, params, *_ = tile
    tables, p16 = staged

    def go():
        try:
            results[i] = sched.render_byte(
                pool, tables, p16, np.asarray(ctrl), sp, statics,
                (stack, params, None, None), percall)
        except Exception as e:   # noqa: BLE001 - asserted by caller
            errors[i] = e
    t = threading.Thread(target=go)
    t.start()
    return t


def _await_pending(sched, n, timeout=10.0):
    """Wait until n entries sit in the pending queue — the test then
    steps the scheduler deterministically with run_wave()."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with sched._lock:
            if len(sched._pending) >= n:
                return
        time.sleep(0.002)
    raise AssertionError(f"pending never reached {n}")


class TestOutputRing:
    def test_rows_roundtrip_and_wrap(self):
        ring = OutputRing(rows=8)
        blocks = [np.arange(i * 100, i * 100 + 3 * 4,
                            dtype=np.float32).reshape(3, 4)
                  for i in range(5)]
        # 5 x 3-row puts into an 8-row ring: wraps twice; every slice
        # must still read back ITS rows (take enqueued before next put)
        outs = [ring.put(jnp.asarray(b)) for b in blocks]
        for b, o in zip(blocks, outs):
            np.testing.assert_array_equal(b, np.asarray(o))
        st = ring.stats()
        assert st["writes"] == 5 and st["bypassed"] == 0
        assert st["lanes"] == 1     # one (tail, dtype) lane

    def test_oversize_block_bypasses(self):
        ring = OutputRing(rows=2)
        big = jnp.ones((4, 3), jnp.float32)
        out = ring.put(big)
        np.testing.assert_array_equal(np.asarray(out), np.ones((4, 3)))
        assert ring.stats()["bypassed"] == 1

    def test_separate_lanes_per_shape_and_dtype(self):
        ring = OutputRing(rows=8)
        a = ring.put(jnp.zeros((2, 4), jnp.float32))
        b = ring.put(jnp.ones((2, 4), jnp.uint8))
        c = ring.put(jnp.full((2, 5), 7.0, jnp.float32))
        assert ring.stats()["lanes"] == 3
        np.testing.assert_array_equal(np.asarray(a), np.zeros((2, 4)))
        np.testing.assert_array_equal(np.asarray(b),
                                      np.ones((2, 4), np.uint8))
        np.testing.assert_array_equal(np.asarray(c), np.full((2, 5), 7.0))


class TestWaveAssembly:
    def test_mixed_kinds_one_wave_ragged_occupancy(self, monkeypatch):
        """One tick carrying two RAGGED byte tiles (different granule
        counts) and two drills dispatches once per kind — and each
        request gets exactly its per-call reference back."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        pool = test_paged._pool(cap=64)
        sched = W.WaveScheduler(tick_ms=5000.0)   # stepped manually
        tiles = [test_paged._inputs(0, B=1, lo=1.0, hi=4000.0),
                 test_paged._inputs(1, B=2, lo=1.0, hi=4000.0)]
        _, _, _, h, w, step, n_ns = tiles[0]
        statics = _byte_statics(n_ns, h, w, step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        staged = [test_paged._stage_full(pool, t[0], t[2],
                                         serial0=100 * (i + 1))
                  for i, t in enumerate(tiles)]
        rng = np.random.default_rng(7)
        drills = [(rng.uniform(0, 9, (4, 96)).astype(np.float32),
                   rng.uniform(size=(4, 96)) > 0.4) for _ in range(2)]
        results = [None] * 4
        errors = [None] * 4
        ts = [_submit_byte(sched, pool, tiles[i], staged[i], sp,
                           statics, results, errors, i)
              for i in range(2)]
        for j, (d, v) in enumerate(drills):
            def god(j=j, d=d, v=v):
                try:
                    results[2 + j] = sched.drill_stats(
                        d, v, -3e38, 3e38, False, None)
                except Exception as e:   # noqa: BLE001
                    errors[2 + j] = e
            t = threading.Thread(target=god)
            t.start()
            ts.append(t)
        _await_pending(sched, 4)
        assert sched.run_wave() == 4
        for t in ts:
            t.join(timeout=60)
        assert errors == [None] * 4
        st = sched.stats()
        # one device program per kind, four requests amortised over two
        assert st["dispatches"] == 2 and st["requests"] == 4
        assert st["waves"] == 1
        assert st["occupancy"] == {2: 2}
        # byte lane: bit-exact vs the per-call bucketed reference
        for i, (stack, ctrl, params, h, w, step, n_ns) in \
                enumerate(tiles):
            rx = render_scenes_ctrl(stack, ctrl, params,
                                    jnp.asarray(sp), *statics)
            np.testing.assert_array_equal(np.asarray(rx), results[i])
        # drill lane: identical to the per-call masked mean
        for j, (d, v) in enumerate(drills):
            rv, rc = masked_mean_impl(d, v, -3e38, 3e38, False, np)
            vals, counts = results[2 + j]
            np.testing.assert_allclose(vals, rv, rtol=1e-6)
            np.testing.assert_array_equal(counts, rc)
        # pins released once readback completed
        assert pool.stats()["pinned"] == 0
        sched.shutdown()

    def test_cancellation_mid_assembly_reclaims_pins(self, monkeypatch):
        """An entry whose token fires while queued is dropped at wave
        assembly: its pages unpin, its future cancels, and the wave
        dispatches WITHOUT it."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        pool = test_paged._pool(cap=64)
        sched = W.WaveScheduler(tick_ms=5000.0)
        tile = test_paged._inputs(0, B=1, lo=1.0, hi=4000.0)
        stack, ctrl, params, h, w, step, n_ns = tile
        statics = _byte_statics(n_ns, h, w, step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        staged = test_paged._stage_full(pool, stack, params, serial0=70)
        tok = CancelToken()
        errors = [None]

        def go():
            try:
                with cancel_scope(tok):
                    tables, p16 = staged
                    sched.render_byte(pool, tables, p16,
                                      np.asarray(ctrl), sp, statics,
                                      (stack, params, None, None), None)
            except BaseException as e:   # noqa: BLE001
                # RequestCancelled subclasses asyncio.CancelledError,
                # which is a BaseException — Exception misses it
                errors[0] = e
        t = threading.Thread(target=go)
        t.start()
        _await_pending(sched, 1)
        assert pool.stats()["pinned"] > 0
        tok.cancel()
        assert sched.run_wave() == 0    # nothing left to dispatch
        t.join(timeout=30)
        assert isinstance(errors[0], RequestCancelled)
        st = sched.stats()
        assert st["cancelled"] == 1 and st["dispatches"] == 0
        assert pool.stats()["pinned"] == 0   # pages reclaimed NOW
        sched.shutdown()

    def test_incident_fails_requests_over_individually(self,
                                                       monkeypatch):
        """A device incident during a wave dispatch must not fail the
        wave as a unit: every entry re-renders through its own per-call
        leg, and pins still release."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        pool = test_paged._pool(cap=64)
        sched = W.WaveScheduler(tick_ms=5000.0)
        monkeypatch.setattr(
            sched, "_dispatch_group",
            lambda kind, es: (_ for _ in ()).throw(
                RuntimeError("injected device incident")))
        tiles = [test_paged._inputs(0, B=1, lo=1.0, hi=4000.0),
                 test_paged._inputs(1, B=2, lo=1.0, hi=4000.0)]
        _, _, _, h, w, step, n_ns = tiles[0]
        statics = _byte_statics(n_ns, h, w, step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        staged = [test_paged._stage_full(pool, t[0], t[2],
                                         serial0=100 * (i + 1))
                  for i, t in enumerate(tiles)]
        sentinels = [np.full((h, w), 11, np.uint8),
                     np.full((h, w), 22, np.uint8)]
        results = [None, None]
        errors = [None, None]
        ts = [_submit_byte(sched, pool, tiles[i], staged[i], sp,
                           statics, results, errors, i,
                           percall=lambda i=i: sentinels[i])
              for i in range(2)]
        _await_pending(sched, 2)
        sched.run_wave()
        for t in ts:
            t.join(timeout=30)
        assert errors == [None, None]
        for i in range(2):
            np.testing.assert_array_equal(results[i], sentinels[i])
        st = sched.stats()
        assert st["fallbacks"] == 2 and st["dispatches"] == 0
        assert pool.stats()["pinned"] == 0
        sched.shutdown()

    def test_readback_queue_ordering_across_waves(self):
        """Several waves in flight: the async readback queue must hand
        every entry ITS result even as ring lanes are reused across
        consecutive waves (the donation-ordering property)."""
        sched = W.WaveScheduler(tick_ms=5000.0, ring_rows=4)
        rng = np.random.default_rng(3)
        cases = [(rng.uniform(0, 9, (2, 48)).astype(np.float32),
                  rng.uniform(size=(2, 48)) > 0.3) for _ in range(6)]
        results = [None] * 6
        errors = [None] * 6
        ts = []
        # three waves of two, dispatched back to back so the readback
        # queue holds multiple result blocks from the same ring lane
        for wave in range(3):
            for j in range(2):
                i = wave * 2 + j

                def go(i=i):
                    try:
                        results[i] = sched.drill_stats(
                            cases[i][0], cases[i][1], -3e38, 3e38,
                            False, None)
                    except Exception as e:   # noqa: BLE001
                        errors[i] = e
                t = threading.Thread(target=go)
                t.start()
                ts.append(t)
            _await_pending(sched, 2)
            sched.run_wave()
        for t in ts:
            t.join(timeout=60)
        assert errors == [None] * 6
        for i, (d, v) in enumerate(cases):
            rv, rc = masked_mean_impl(d, v, -3e38, 3e38, False, np)
            vals, counts = results[i]
            np.testing.assert_allclose(vals, rv, rtol=1e-6)
            np.testing.assert_array_equal(counts, rc)
        st = sched.stats()
        assert st["dispatches"] == 3
        assert st["ring"]["writes"] >= 6     # lanes reused, not bypassed
        assert st["ring"]["bypassed"] == 0
        sched.shutdown()

    def test_brownout_clamps_wave_size(self, monkeypatch):
        """Pressure brownout shrinks the admission wave: level 2 quarters
        the configured max."""
        sched = W.WaveScheduler(max_entries=16)
        import gsky_tpu.resilience.pressure as pressure
        monkeypatch.setattr(pressure, "brownout_level", lambda: 2)
        assert sched._effective_max() == 4
        monkeypatch.setattr(pressure, "brownout_level", lambda: 1)
        assert sched._effective_max() == 8
        monkeypatch.setattr(pressure, "brownout_level", lambda: 0)
        assert sched._effective_max() == 16
        sched.shutdown()


class TestWavePipeline:
    """The two-stage pipeline (PERF.md "Continuous device occupancy"):
    the assembly stage plans, stacks and uploads into the donated
    staging ring while the dispatch stage executes — byte parity with
    the synchronous ticker, cancellation releasing staging pins,
    watchdog attribution with two waves in flight, the
    GSKY_WAVE_PIPELINE=0 escape hatch, and donated-ring reuse."""

    def test_pipelined_parity_all_lanes(self, monkeypatch):
        """The SAME byte / scored / drill submissions through the
        staged assemble_once()/dispatch_once() pipeline and through the
        synchronous run_wave() ticker return identical bytes, and both
        match the per-call references."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        # queue depth 4: assemble_once stages three groups before the
        # test pops any of them (depth 1 would block assembly)
        monkeypatch.setenv("GSKY_WAVE_QUEUE", "4")
        # planning off: small groups would otherwise route bucketed
        # (nothing staged) and the staging-ring assertions go dark
        monkeypatch.setenv("GSKY_PLAN", "0")

        tiles = [test_paged._inputs(0, B=1, lo=1.0, hi=4000.0),
                 test_paged._inputs(1, B=2, lo=1.0, hi=4000.0)]
        _, _, _, h, w, step, n_ns = tiles[0]
        b_statics = _byte_statics(n_ns, h, w, step)
        s_statics = ("near", n_ns, (h, w), step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        rng = np.random.default_rng(11)
        drills = [(rng.uniform(0, 9, (3, 64)).astype(np.float32),
                   rng.uniform(size=(3, 64)) > 0.4) for _ in range(2)]

        def run_leg(pipelined):
            monkeypatch.setenv("GSKY_WAVE_PIPELINE",
                               "1" if pipelined else "0")
            pool = test_paged._pool(cap=64)
            sched = W.WaveScheduler(tick_ms=5000.0,
                                    manual_dispatch=pipelined)
            staged = [test_paged._stage_full(pool, t[0], t[2],
                                             serial0=100 * (i + 1))
                      for i, t in enumerate(tiles)]
            results = [None] * 5
            errors = [None] * 5
            ts = [_submit_byte(sched, pool, tiles[i], staged[i], sp,
                               b_statics, results, errors, i)
                  for i in range(2)]
            sc_tab, sc_p16 = test_paged._stage_full(
                pool, tiles[0][0], tiles[0][2], serial0=900)

            def go_scored():
                try:
                    results[2] = sched.warp_scored(
                        pool, sc_tab, sc_p16,
                        np.asarray(tiles[0][1]), s_statics,
                        (tiles[0][0], tiles[0][2], None, None), None)
                except Exception as e:   # noqa: BLE001
                    errors[2] = e
            t = threading.Thread(target=go_scored)
            t.start()
            ts.append(t)
            for j, (d, v) in enumerate(drills):
                def god(j=j, d=d, v=v):
                    try:
                        results[3 + j] = sched.drill_stats(
                            d, v, -3e38, 3e38, False, None)
                    except Exception as e:   # noqa: BLE001
                        errors[3 + j] = e
                t = threading.Thread(target=god)
                t.start()
                ts.append(t)
            _await_pending(sched, 5)
            if pipelined:
                # assembly stages all three groups ahead of dispatch,
                # then the dispatch stage pops them back-to-back
                assert sched.assemble_once() == 5
                st = sched.stats()
                assert st["staged_waves"] == 3
                assert st["staged_queue_depth"] == 3
                n = 0
                while True:
                    got = sched.dispatch_once(timeout=1.0)
                    if got == 0:
                        break
                    n += got
                assert n == 5
            else:
                assert sched.run_wave() == 5
            for t in ts:
                t.join(timeout=60)
            assert errors == [None] * 5
            st = sched.stats()
            assert st["dispatches"] == 3 and st["requests"] == 5
            assert pool.stats()["pinned"] == 0
            if pipelined:
                # all three groups staged through the ring (the drill
                # stacks pass through upload already on device)
                assert st["staging"]["staged"] == 3
            sched.shutdown()
            return results

        sync = run_leg(False)
        pipe = run_leg(True)
        # pipelined vs synchronous: bit-exact, every lane
        for i in range(2):
            np.testing.assert_array_equal(sync[i], pipe[i])
        np.testing.assert_array_equal(sync[2][0], pipe[2][0])
        np.testing.assert_array_equal(sync[2][1], pipe[2][1])
        for j in range(2):
            np.testing.assert_array_equal(sync[3 + j][0], pipe[3 + j][0])
            np.testing.assert_array_equal(sync[3 + j][1], pipe[3 + j][1])
        # and both match the per-call references
        for i, (stack, ctrl, params, h, w, step, n_ns) in \
                enumerate(tiles):
            rx = render_scenes_ctrl(stack, ctrl, params,
                                    jnp.asarray(sp), *b_statics)
            np.testing.assert_array_equal(np.asarray(rx), pipe[i])
        cx, bx = warp_scenes_ctrl_scored(
            tiles[0][0], tiles[0][1], tiles[0][2], *s_statics)
        np.testing.assert_array_equal(np.asarray(cx), pipe[2][0])
        np.testing.assert_array_equal(
            np.asarray(bx) > -np.inf, pipe[2][1])
        for j, (d, v) in enumerate(drills):
            rv, rc = masked_mean_impl(d, v, -3e38, 3e38, False, np)
            np.testing.assert_allclose(pipe[3 + j][0], rv, rtol=1e-6)
            np.testing.assert_array_equal(pipe[3 + j][1], rc)

    def test_cancellation_mid_upload_releases_staging_slot(
            self, monkeypatch):
        """A wave cancelled BETWEEN assembly (inputs already uploaded
        into the staging ring) and dispatch skips the device program,
        unpins its pages AND frees the staging slot for the next
        wave."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        monkeypatch.setenv("GSKY_WAVE_PIPELINE", "1")
        monkeypatch.setenv("GSKY_PLAN", "0")   # force the staged path
        pool = test_paged._pool(cap=64)
        sched = W.WaveScheduler(tick_ms=5000.0, manual_dispatch=True)
        tile = test_paged._inputs(0, B=1, lo=1.0, hi=4000.0)
        stack, ctrl, params, h, w, step, n_ns = tile
        statics = _byte_statics(n_ns, h, w, step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        toks = [CancelToken(), CancelToken()]
        errors = [None, None]
        ts = []
        for i in range(2):
            staged_i = test_paged._stage_full(pool, stack, params,
                                              serial0=50 + 10 * i)

            def go(i=i, staged_i=staged_i):
                try:
                    with cancel_scope(toks[i]):
                        tables, p16 = staged_i
                        sched.render_byte(
                            pool, tables, p16, np.asarray(ctrl), sp,
                            statics, (stack, params, None, None), None)
                except BaseException as e:   # noqa: BLE001
                    errors[i] = e
            t = threading.Thread(target=go)
            t.start()
            ts.append(t)
        _await_pending(sched, 2)
        assert sched.assemble_once() == 2    # staged + uploaded
        assert pool.stats()["pinned"] > 0    # pins ride to dispatch
        for tok in toks:
            tok.cancel()
        assert sched.dispatch_once(timeout=1.0) == 0   # skipped
        for t in ts:
            t.join(timeout=30)
        assert all(isinstance(e, RequestCancelled) for e in errors)
        st = sched.stats()
        assert st["cancelled"] == 2 and st["dispatches"] == 0
        assert pool.stats()["pinned"] == 0
        # the slot freed by the cancelled wave must be reacquirable —
        # a leaked pin here would wedge assembly at the ring
        fam = ("byte", (tuple(statics), id(pool)))
        tok2 = sched.staging.acquire(fam)     # returns, doesn't block
        tok3 = sched.staging.acquire(fam)     # BOTH slots came back
        assert {tok2[1], tok3[1]} == {0, 1}
        sched.staging.release(tok2)
        sched.staging.release(tok3)
        sched.shutdown()

    def test_watchdog_attributes_hang_to_executing_wave(self):
        """Two waves in flight: a staging upload that times out while
        an older wave's program is EXECUTING blames the executing
        wave (the upload queued behind the wedged program); with no
        execution window open, the staging site keeps the blame."""
        from gsky_tpu.device_guard import supervisor as sup
        sup.reset()
        try:
            with sup.execution_window("dispatch.wave"):
                with pytest.raises(sup.DeviceHang) as ei:
                    sup.supervised_sync("wave.stage",
                                        lambda: time.sleep(0.5),
                                        deadline_s=0.05)
            assert ei.value.site == "dispatch.wave"
            assert "attributed to executing" in str(ei.value)
            with pytest.raises(sup.DeviceHang) as ei2:
                sup.supervised_sync("wave.stage",
                                    lambda: time.sleep(0.5),
                                    deadline_s=0.05)
            assert ei2.value.site == "wave.stage"
            # an executing-site hang is always its own
            with pytest.raises(sup.DeviceHang) as ei3:
                sup.supervised_sync("dispatch.wave",
                                    lambda: time.sleep(0.5),
                                    deadline_s=0.05)
            assert ei3.value.site == "dispatch.wave"
        finally:
            sup.reset()

    def test_pipeline_escape_hatch_synchronous_identity(
            self, monkeypatch):
        """GSKY_WAVE_PIPELINE=0 restores the synchronous ticker: no
        staging, no staged waves, and the result still matches the
        per-call reference (the acceptance escape hatch)."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        monkeypatch.setenv("GSKY_WAVE_PIPELINE", "0")
        assert not W.wave_pipeline_enabled()
        pool = test_paged._pool(cap=64)
        sched = W.WaveScheduler(tick_ms=5000.0)
        tile = test_paged._inputs(0, B=1, lo=1.0, hi=4000.0)
        stack, ctrl, params, h, w, step, n_ns = tile
        statics = _byte_statics(n_ns, h, w, step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        staged = test_paged._stage_full(pool, stack, params, serial0=60)
        results = [None]
        errors = [None]
        t = _submit_byte(sched, pool, tile, staged, sp, statics,
                         results, errors, 0)
        _await_pending(sched, 1)
        assert sched.run_wave() == 1
        t.join(timeout=30)
        assert errors == [None]
        rx = render_scenes_ctrl(stack, ctrl, params, jnp.asarray(sp),
                                *statics)
        np.testing.assert_array_equal(np.asarray(rx), results[0])
        st = sched.stats()
        assert st["pipeline"] is False
        assert st["staged_waves"] == 0
        assert st["staging"]["staged"] == 0   # ring never touched
        assert pool.stats()["pinned"] == 0
        sched.shutdown()

    def test_donated_ring_reuse_across_consecutive_waves(
            self, monkeypatch):
        """Three consecutive pipelined waves of the same program
        family: the output ring keeps ONE donated lane across waves
        (no per-wave re-allocation) and the staging ring refreshes
        its slot buffers in place (slot_reuse) once the round-robin
        wraps."""
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        monkeypatch.setenv("GSKY_WAVE_PIPELINE", "1")
        monkeypatch.setenv("GSKY_PLAN", "0")   # force the staged path
        pool = test_paged._pool(cap=64)
        sched = W.WaveScheduler(tick_ms=5000.0, manual_dispatch=True)
        tile = test_paged._inputs(0, B=1, lo=1.0, hi=4000.0)
        stack, ctrl, params, h, w, step, n_ns = tile
        statics = _byte_statics(n_ns, h, w, step)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        results = [None] * 3
        errors = [None] * 3
        for wv in range(3):
            staged = test_paged._stage_full(pool, stack, params,
                                            serial0=200 + 10 * wv)
            t = _submit_byte(sched, pool, tile, staged, sp, statics,
                             results, errors, wv)
            _await_pending(sched, 1)
            assert sched.assemble_once() == 1
            assert sched.dispatch_once(timeout=1.0) == 1
            t.join(timeout=30)
        assert errors == [None] * 3
        rx = np.asarray(render_scenes_ctrl(
            stack, ctrl, params, jnp.asarray(sp), *statics))
        for wv in range(3):
            np.testing.assert_array_equal(rx, results[wv])
        st = sched.stats()
        assert st["dispatches"] == 3 and st["staged_waves"] == 3
        # ONE uint8 ring lane serves all three waves, donated across
        # dispatches rather than re-allocated
        assert st["ring"]["writes"] >= 3
        assert st["ring"]["lanes"] == 1
        assert st["ring"]["bypassed"] == 0
        # two staging slots round-robin: wave 3 lands back on wave 1's
        # slot and refreshes every same-shape host stack in place
        assert st["staging"]["families"] == 1
        assert st["staging"]["staged"] == 3
        assert st["staging"]["slot_reuse"] >= 1
        assert pool.stats()["pinned"] == 0
        sched.shutdown()


class TestWaveGate:
    def test_gsky_waves_0_restores_per_call_byte_identical(
            self, monkeypatch):
        """Executor-level escape hatch: the same mosaic renders to the
        same bytes with waves on (wave scheduler engaged, dispatch
        count amortised) and with GSKY_WAVES=0 (per-call paged
        dispatch) — the tier-1 acceptance assertion for the gate."""
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
            monkeypatch.setenv("GSKY_WAVES", "1")
            ex1 = WarpExecutor()
            c1, v1 = ex1.warp_mosaic_scenes(*args)
            assert ex1.paged_engaged == 1
            st = W.wave_stats()
            assert st and st["requests"] == 1 and st["dispatches"] == 1
            assert pages._default.stats()["pinned"] == 0
            monkeypatch.setenv("GSKY_WAVES", "0")
            pages.reset_default_pool()
            ex0 = WarpExecutor()
            c0, v0 = ex0.warp_mosaic_scenes(*args)
            assert ex0.paged_engaged == 1    # still paged, per-call
            assert W.wave_stats()["requests"] == 1   # untouched
            np.testing.assert_array_equal(np.asarray(c1),
                                          np.asarray(c0))
            np.testing.assert_array_equal(np.asarray(v1),
                                          np.asarray(v0))
        finally:
            pages.reset_default_pool()

    def test_waves_follow_paged_gate(self, monkeypatch):
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        monkeypatch.delenv("GSKY_WAVES", raising=False)
        assert W.waves_enabled()
        monkeypatch.setenv("GSKY_WAVES", "0")
        assert not W.waves_enabled()
        monkeypatch.delenv("GSKY_WAVES", raising=False)
        monkeypatch.setenv("GSKY_PAGED", "0")
        assert not W.waves_enabled()     # no paged kernels, no waves
