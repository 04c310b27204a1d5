"""Persistent kernel race ledger (`gsky_tpu/ops/kernel_ledger.py` +
`pallas_tpu.reload_ledger`): durable verdicts, restart-sim no-re-race,
corrupt-line recovery, delete-file re-race, /debug stats shape."""

import json
import time as _t

import numpy as np
import pytest

pytest.importorskip("jax")

from gsky_tpu.ops import kernel_ledger, pallas_tpu as pt


@pytest.fixture(autouse=True)
def _tmp_ledger(tmp_path, monkeypatch):
    """Hermetic ledger file per test + pinned dispatch mode
    (GSKY_PALLAS=interpret would bypass the races these tests rely
    on)."""
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("GSKY_KERNEL_LEDGER", str(path))
    monkeypatch.setenv("GSKY_PALLAS", "1")
    yield path


def _clean(*keys):
    for name, token in keys:
        pt._FAILED.pop(name, None)
        pt._SLOW.discard((name, token))
        pt._PROVEN.pop((name, token), None)


class TestRecordFormat:
    def test_roundtrip(self, _tmp_ledger):
        token = ((8, 512, 512), "int16", (128, 128), (256, 256), "near",
                 1, 16)
        kernel_ledger.record("warp_scored", token, "demoted", 12.5, 3.25)
        ents = kernel_ledger.entries()
        assert len(ents) == 1
        (key, rec), = ents.items()
        assert key == ("warp_scored", repr(token))
        assert rec["verdict"] == "demoted"
        assert rec["t_pallas_ms"] == 12.5
        assert rec["t_xla_ms"] == 3.25
        assert rec["pid"] > 0 and rec["ts"] > 0
        # token must decode back to the EXACT tuple run_with_fallback
        # uses as its _SLOW key
        assert kernel_ledger.decode_token(key[1]) == token

    def test_last_verdict_wins(self):
        kernel_ledger.record("k", (8, 8), "demoted")
        kernel_ledger.record("k", (8, 8), "promoted")
        ents = kernel_ledger.entries()
        assert ents[("k", repr((8, 8)))]["verdict"] == "promoted"

    def test_invalid_verdict_not_written(self, _tmp_ledger):
        kernel_ledger.record("k", (8, 8), "banana")
        assert not _tmp_ledger.exists()

    def test_missing_file_is_empty(self):
        assert kernel_ledger.entries() == {}


class TestRestartSim:
    def test_demote_then_reload_never_re_races(self):
        """The acceptance criterion: a demoted kernel is never re-raced
        in a fresh process with the ledger present.  The restart is
        simulated by clearing the in-process race state and replaying
        the file, exactly what import does."""
        calls = {"pallas": 0}
        key = ("ledger_kernel", (8, 8))

        def slow_pallas():
            calls["pallas"] += 1
            _t.sleep(0.05)
            return np.float32(1.0)

        orig = pt.use_pallas
        pt.use_pallas = lambda: True
        try:
            with pytest.warns(UserWarning, match="ledger_kernel"):
                pt.run_with_fallback("ledger_kernel", slow_pallas,
                                     lambda: np.float32(1.0),
                                     sync_token=(8, 8))
            assert key in pt._SLOW
            # "restart": wipe in-process state, replay the file
            _clean(key)
            assert key not in pt._SLOW
            assert pt.reload_ledger() >= 1
            assert key in pt._SLOW
            before = calls["pallas"]
            pt.run_with_fallback("ledger_kernel", slow_pallas,
                                 lambda: np.float32(1.0),
                                 sync_token=(8, 8))
            assert calls["pallas"] == before    # straight to XLA
        finally:
            pt.use_pallas = orig
            _clean(key)

    def test_promoted_reload_skips_race(self):
        """A promoted verdict replays into _PROVEN: the fresh process
        dispatches pallas without timing the XLA leg at all."""
        calls = {"pallas": 0, "xla": 0}
        key = ("ledger_kernel2", (4, 4))

        def fast_pallas():
            calls["pallas"] += 1
            return np.float32(1.0)

        def xla():
            calls["xla"] += 1
            _t.sleep(0.05)
            return np.float32(2.0)

        orig = pt.use_pallas
        pt.use_pallas = lambda: True
        try:
            pt.run_with_fallback("ledger_kernel2", fast_pallas, xla,
                                 sync_token=(4, 4))
            assert key in pt._PROVEN
            _clean(key)
            pt.reload_ledger()
            assert key in pt._PROVEN
            x_before = calls["xla"]
            r = pt.run_with_fallback("ledger_kernel2", fast_pallas, xla,
                                     sync_token=(4, 4))
            assert float(r) == 1.0
            assert calls["xla"] == x_before     # no race re-paid
        finally:
            pt.use_pallas = orig
            _clean(key)

    def test_failed_reload_blacklists_name(self):
        kernel_ledger.record("ledger_kernel3", (2, 2), "failed")
        try:
            pt.reload_ledger()
            assert "ledger_kernel3" in pt._FAILED
            # blacklisted by name: straight to XLA, pallas never runs
            assert pt.run_with_fallback(
                "ledger_kernel3",
                lambda: (_ for _ in ()).throw(AssertionError),
                lambda: 42) == 42
        finally:
            _clean(("ledger_kernel3", (2, 2)))

    def test_delete_file_re_races(self, _tmp_ledger):
        kernel_ledger.record("ledger_kernel4", (8, 8), "demoted")
        pt.reload_ledger()
        try:
            assert ("ledger_kernel4", (8, 8)) in pt._SLOW
            _tmp_ledger.unlink()                # the operator reset knob
            _clean(("ledger_kernel4", (8, 8)))  # + restart
            assert pt.reload_ledger() == 0
            assert ("ledger_kernel4", (8, 8)) not in pt._SLOW
        finally:
            _clean(("ledger_kernel4", (8, 8)))


class TestCorruptLedger:
    def test_corrupt_lines_skipped(self, _tmp_ledger):
        kernel_ledger.record("good", (8, 8), "demoted")
        with open(_tmp_ledger, "a") as fp:
            fp.write("{truncated json\n")
            fp.write("[1, 2, 3]\n")             # not a dict
            fp.write(json.dumps({"kernel": "x"}) + "\n")  # no verdict
            fp.write(json.dumps({"kernel": "y", "token": "(1,)",
                                 "verdict": "banana"}) + "\n")
            fp.write("\x00\x01garbage\n")
        kernel_ledger.record("good2", (4, 4), "promoted")
        ents = kernel_ledger.entries()
        assert set(ents) == {("good", repr((8, 8))),
                             ("good2", repr((4, 4)))}

    def test_reload_survives_binary_garbage(self, _tmp_ledger):
        _tmp_ledger.write_bytes(b"\x89PNG\r\n\x1a\n" + b"\xff" * 64)
        assert pt.reload_ledger() == 0          # no exception, nothing

    def test_undecodable_token_skipped(self):
        kernel_ledger.record("k", object(), "demoted")  # repr not literal
        assert pt.reload_ledger() == 0


class TestStats:
    def test_debug_block_shape(self, _tmp_ledger):
        kernel_ledger.record("warp_scored", (8, 8), "promoted", 1.0, 2.0)
        kernel_ledger.record("warp_scored", (16, 16), "demoted", 9.0,
                             2.0)
        kernel_ledger.record("masked_stats", (1024, 16384), "promoted")
        doc = kernel_ledger.stats()
        assert doc["ledger_path"] == str(_tmp_ledger)
        assert doc["ledger_present"] is True
        ws = doc["kernels"]["warp_scored"]
        assert ws["promoted"] == 1 and ws["demoted"] == 1
        assert len(ws["entries"]) == 2
        assert doc["kernels"]["masked_stats"]["promoted"] == 1
        sess = doc["session"]
        assert {"pallas_enabled", "interpret", "failed_kernels",
                "demoted_pairs", "proven_pairs"} <= set(sess)

    def test_metrics_summary_includes_kernels(self):
        from gsky_tpu.server.metrics import MetricsLogger
        kernel_ledger.record("warp_render", (8, 8), "promoted")
        doc = MetricsLogger().summary()
        assert doc["kernels"]["kernels"]["warp_render"]["promoted"] == 1
