"""Parity tests for the Pallas TPU reduction kernels
(`gsky_tpu/ops/pallas_tpu.py`) against their XLA counterparts, run in
interpreter mode so they execute on the CPU test backend."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from gsky_tpu.ops.drill import masked_mean
from gsky_tpu.ops.mosaic import mosaic_first_valid
from gsky_tpu.ops.pallas_tpu import (masked_stats_pallas,
                                     mosaic_first_valid_pallas)


@pytest.fixture(autouse=True)
def _tmp_ledger(tmp_path, monkeypatch):
    """Race verdicts are durable now (ops/kernel_ledger.py): point every
    test at its own ledger file so races here never leak demotions into
    the shared default ledger (or read stale ones from it).  Also pin
    the dispatch mode: GSKY_PALLAS=interpret (the CI kernel-parity
    step) bypasses the race entirely, and the race tests below need the
    race to happen."""
    monkeypatch.setenv("GSKY_KERNEL_LEDGER", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setenv("GSKY_PALLAS", "1")


class TestMosaicKernel:
    def test_matches_xla_first_valid(self):
        rng = np.random.default_rng(7)
        stack = rng.normal(size=(6, 200, 300)).astype(np.float32) * 50
        valid = rng.uniform(size=(6, 200, 300)) > 0.4
        out, ok = mosaic_first_valid_pallas(
            jnp.asarray(stack), jnp.asarray(valid), interpret=True)
        ref, refok = mosaic_first_valid(jnp.asarray(stack),
                                        jnp.asarray(valid))
        ref = jnp.where(refok, ref, 0.0)  # kernel zero-fills invalid
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(refok))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_all_invalid(self):
        stack = np.ones((3, 64, 64), np.float32)
        valid = np.zeros((3, 64, 64), bool)
        out, ok = mosaic_first_valid_pallas(
            jnp.asarray(stack), jnp.asarray(valid), interpret=True)
        assert not np.asarray(ok).any()
        assert (np.asarray(out) == 0).all()

    def test_priority_order_wins(self):
        stack = np.stack([np.full((32, 32), 9.0, np.float32),
                          np.full((32, 32), 5.0, np.float32)])
        valid = np.ones((2, 32, 32), bool)
        out, ok = mosaic_first_valid_pallas(
            jnp.asarray(stack), jnp.asarray(valid), interpret=True)
        assert (np.asarray(out) == 9.0).all()


class TestStatsKernel:
    def test_matches_xla_masked_mean(self):
        rng = np.random.default_rng(11)
        data = rng.normal(size=(5, 7000)).astype(np.float32) * 100
        valid = rng.uniform(size=(5, 7000)) > 0.3
        s, c = masked_stats_pallas(jnp.asarray(data), jnp.asarray(valid),
                                   -80.0, 120.0, interpret=True)
        ref_v, ref_c = masked_mean(jnp.asarray(data), jnp.asarray(valid),
                                   -80.0, 120.0)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(ref_c))
        got = np.where(np.asarray(c) > 0,
                       np.asarray(s) / np.maximum(np.asarray(c), 1), 0.0)
        np.testing.assert_allclose(got, np.asarray(ref_v), rtol=1e-5)

    def test_empty_bands(self):
        data = np.ones((3, 500), np.float32)
        valid = np.zeros((3, 500), bool)
        s, c = masked_stats_pallas(jnp.asarray(data), jnp.asarray(valid),
                                   interpret=True)
        assert (np.asarray(c) == 0).all()
        assert (np.asarray(s) == 0).all()

    def test_bench_shape_b1000(self):
        """The BENCH cfg5 shape (B=1000 timesteps) that OOM'd VMEM in
        round 3: the row axis must be tiled, not held whole per block."""
        rng = np.random.default_rng(5)
        data = rng.normal(size=(1000, 4096)).astype(np.float32)
        valid = rng.uniform(size=(1000, 4096)) > 0.5
        s, c = masked_stats_pallas(jnp.asarray(data), jnp.asarray(valid),
                                   -2.0, 2.0, interpret=True)
        ref_v, ref_c = masked_mean(jnp.asarray(data), jnp.asarray(valid),
                                   -2.0, 2.0)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(ref_c))
        got = np.where(np.asarray(c) > 0,
                       np.asarray(s) / np.maximum(np.asarray(c), 1), 0.0)
        # sum-order differs between the chunked kernel and XLA's fused
        # reduction; means here are O(1e-2) so atol covers the near-zero
        # rows where rtol alone blows up
        np.testing.assert_allclose(got, np.asarray(ref_v), rtol=1e-5,
                                   atol=1e-6)


class TestSelectionGates:
    def test_warp_family_is_interpret_only(self, monkeypatch):
        """On a TPU the streaming kernels Mosaic compiles are selected;
        the gather-form warp family it refuses — and with it pages and
        waves — is not, by what the code knows.  Interpret mode (these
        parity tests) keeps the whole family reachable."""
        from gsky_tpu.ops import pallas_tpu as pt
        from gsky_tpu.ops.paged import paged_enabled
        from gsky_tpu.pipeline.waves import waves_enabled

        monkeypatch.setattr(pt, "tpu_like_backend", lambda: True)
        monkeypatch.delenv("GSKY_PALLAS", raising=False)
        assert pt.use_pallas()
        assert not pt.warp_pallas_enabled()
        assert not pt.warp_pallas_ok(384, 384, 1)
        assert not paged_enabled() and not waves_enabled()
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        assert pt.warp_pallas_enabled() and pt.warp_pallas_ok(384, 384, 1)
        assert paged_enabled() and waves_enabled()
        monkeypatch.setenv("GSKY_PALLAS", "0")
        assert not pt.use_pallas() and not pt.warp_pallas_enabled()
        assert not paged_enabled()


class TestRunWithFallback:
    def test_failure_is_loud_and_not_retried(self, caplog):
        from gsky_tpu.ops import pallas_tpu as pt

        calls = {"pallas": 0, "xla": 0}

        def bad():
            calls["pallas"] += 1
            raise RuntimeError("Mosaic VMEM OOM (simulated)")

        def good():
            calls["xla"] += 1
            return "xla-result"

        orig = pt.use_pallas
        pt._FAILED.pop("test_kernel", None)
        pt.use_pallas = lambda: True
        try:
            with caplog.at_level("ERROR", logger="gsky.pallas"):
                assert pt.run_with_fallback("test_kernel", bad,
                                            good) == "xla-result"
            # logged as an error with the traceback, and readable by
            # name (what /debug, prewarm and chip_smoke.py act on)
            rec = [r for r in caplog.records if "test_kernel" in
                   r.getMessage()]
            assert rec and rec[0].levelname == "ERROR" \
                and rec[0].exc_info
            assert "VMEM OOM" in pt.kernel_state()["failed"]["test_kernel"]
            # second call must not retry the broken kernel
            assert pt.run_with_fallback("test_kernel", bad,
                                        good) == "xla-result"
        finally:
            pt.use_pallas = orig
            pt._FAILED.pop("test_kernel", None)
        assert calls == {"pallas": 1, "xla": 2}

    def test_speed_race_demotes_slow_pallas(self):
        """First call per (kernel, shape) races pallas against the XLA
        fallback; a clear loser is demoted for the process — 'works'
        must not beat 'faster' (the r5 warm-drill lesson)."""
        import time as _t

        from gsky_tpu.ops import pallas_tpu as pt

        calls = {"pallas": 0, "xla": 0}

        def slow_pallas():
            calls["pallas"] += 1
            _t.sleep(0.05)
            return np.float32(1.0)

        def fast_xla():
            calls["xla"] += 1
            return np.float32(1.0)

        key = ("race_kernel", (8, 8))
        orig = pt.use_pallas
        pt.use_pallas = lambda: True
        try:
            with pytest.warns(UserWarning, match="race_kernel"):
                pt.run_with_fallback("race_kernel", slow_pallas,
                                     fast_xla, sync_token=(8, 8))
            assert key in pt._SLOW
            p_before = calls["pallas"]
            pt.run_with_fallback("race_kernel", slow_pallas, fast_xla,
                                 sync_token=(8, 8))
            assert calls["pallas"] == p_before  # demoted: straight XLA
        finally:
            pt.use_pallas = orig
            pt._SLOW.discard(key)
            pt._PROVEN.pop(key, None)

    def test_speed_race_keeps_fast_pallas(self):
        import time as _t

        from gsky_tpu.ops import pallas_tpu as pt

        calls = {"pallas": 0, "xla": 0}

        def fast_pallas():
            calls["pallas"] += 1
            return np.float32(1.0)

        def slow_xla():
            calls["xla"] += 1
            _t.sleep(0.05)
            return np.float32(2.0)

        key = ("race_kernel2", (4, 4))
        orig = pt.use_pallas
        pt.use_pallas = lambda: True
        try:
            r = pt.run_with_fallback("race_kernel2", fast_pallas,
                                     slow_xla, sync_token=(4, 4))
            assert float(r) == 1.0 and key not in pt._SLOW
            x_before = calls["xla"]
            r = pt.run_with_fallback("race_kernel2", fast_pallas,
                                     slow_xla, sync_token=(4, 4))
            assert float(r) == 1.0
            assert calls["xla"] == x_before     # steady state: no XLA
        finally:
            pt.use_pallas = orig
            pt._SLOW.discard(key)
            pt._PROVEN.pop(key, None)

    def test_disabled_goes_straight_to_xla(self):
        from gsky_tpu.ops import pallas_tpu as pt

        orig = pt.use_pallas
        pt.use_pallas = lambda: False
        try:
            assert pt.run_with_fallback(
                "k", lambda: (_ for _ in ()).throw(AssertionError),
                lambda: 42) == 42
        finally:
            pt.use_pallas = orig
