"""server/metrics.py coverage: remote-addr parsing, /debug percentile
math over the rolling reservoir, log rotation + gzip retention, and the
stdout sink's flush behaviour."""

import datetime as real_dt
import gzip
import itertools
import json
import os
import types

import gsky_tpu.server.metrics as M
from gsky_tpu.server.metrics import MetricsLogger


class TestSetRemote:
    def _collector(self):
        return MetricsLogger().collector()

    def test_v4_with_port(self):
        c = self._collector()
        c.set_remote("10.1.2.3:5001")
        assert c.info["remote_addr"] == "10.1.2.3:5001"
        assert c.info["remote_host"] == "10.1.2.3"
        assert c.info["remote_port"] == "5001"

    def test_v6_with_port(self):
        c = self._collector()
        c.set_remote("[2001:db8::1]:8443")
        assert c.info["remote_host"] == "2001:db8::1"
        assert c.info["remote_port"] == "8443"

    def test_bare_v4(self):
        c = self._collector()
        c.set_remote("10.1.2.3")
        assert c.info["remote_host"] == "10.1.2.3"
        assert c.info["remote_port"] == ""

    def test_bare_v6(self):
        # >1 colon and no bracket: must NOT be split at a colon
        c = self._collector()
        c.set_remote("2001:db8::1")
        assert c.info["remote_host"] == "2001:db8::1"
        assert c.info["remote_port"] == ""


def _info(service="WMS", request="GetMap", dur_ms=10, status=200,
          device_ms=0, rpc_ms=0):
    return {"url": {"query": {"service": service, "request": request}},
            "req_duration": int(dur_ms * 1e6),   # ns
            "http_status": status,
            "device": {"duration": int(device_ms * 1e6)},
            "rpc": {"duration": int(rpc_ms * 1e6)}}


class TestSummary:
    def test_percentiles_over_known_distribution(self):
        ml = MetricsLogger()
        for ms in range(1, 101):          # 1..100 ms
            ml.record_summary(_info(dur_ms=ms))
        s = ml.summary()["requests"]["WMS.GetMap"]
        assert s["count"] == 100 and s["window"] == 100
        assert s["errors"] == 0
        # sorted lat[min(int(n*p), n-1)]: p50 -> lat[50], p99 -> lat[99]
        assert s["p50_ms"] == 51.0
        assert s["p99_ms"] == 100.0

    def test_reservoir_window_caps_but_count_does_not(self):
        ml = MetricsLogger()
        for _ in range(MetricsLogger._RESERVOIR + 88):
            ml.record_summary(_info(dur_ms=5))
        s = ml.summary()["requests"]["WMS.GetMap"]
        assert s["count"] == MetricsLogger._RESERVOIR + 88
        assert s["window"] == MetricsLogger._RESERVOIR

    def test_errors_and_verb_split(self):
        ml = MetricsLogger()
        ml.record_summary(_info(status=500))
        ml.record_summary(_info(service="WCS", request="GetCoverage",
                                device_ms=7, rpc_ms=9))
        ml.record_summary({"url": {"query": {"dap4.ce": "/x"}},
                           "req_duration": 0, "http_status": 200,
                           "device": {"duration": 0},
                           "rpc": {"duration": 0}})
        req = ml.summary()["requests"]
        assert req["WMS.GetMap"]["errors"] == 1
        assert req["WCS.GetCoverage"]["device_ms_total"] == 7.0
        assert req["WCS.GetCoverage"]["pipeline_ms_total"] == 9.0
        assert "DAP4.ce" in req

    def test_empty_summary_has_no_percentiles(self):
        doc = MetricsLogger().summary()
        assert doc["requests"] == {}
        assert "cache" in doc


class TestFleetDebugBlock:
    def test_summary_surfaces_live_router_counters(self):
        """/debug carries one ``fleet`` entry per live router: ring
        membership/generation, in-flight load, locality ledger, health
        states and hedge counters."""
        from gsky_tpu.fleet import FleetRouter

        r = FleetRouter(["n1:11429", "n2:11429", "n3:11429"],
                        name="dbg-fleet")          # strong ref: WeakSet
        node = None
        try:
            key = "layer|EPSG:3857|0,0,1,1|256x256"
            node = r.candidates(key)[0]
            r.task_started(node)
            r.record_locality(key, node)
            r.record_locality(key, node)           # repeat -> hit
            r.node_result(node, ok=True, latency_s=0.01)

            fs = MetricsLogger().summary()["fleet"]["dbg-fleet"]
            assert set(fs["ring"]["nodes"]) == {"n1:11429", "n2:11429",
                                                "n3:11429"}
            assert fs["ring"]["generation"] >= 1
            assert fs["routed"] == 2
            assert fs["locality"] == {"hits": 1, "misses": 0,
                                      "rate": 1.0}
            assert fs["load"][node] == 1
            assert fs["health"][node]["state"] == "healthy"
            assert fs["hedge"]["primaries"] == 0
            assert "delay_s" in fs["hedge"] and "tokens" in fs["hedge"]
        finally:
            if node is not None:
                r.task_finished(node)
            r.close()

    def test_summary_fleet_block_absent_without_routers(self):
        # fleet_stats() only reports routers this process actually
        # created; a plain logger must not invent the block (other
        # tests' routers may linger in the WeakSet, so assert shape
        # rather than absence when any survive)
        doc = MetricsLogger().summary()
        if "fleet" in doc:
            assert all(isinstance(v, dict) and "ring" in v
                       for v in doc["fleet"].values())


class TestSinks:
    def test_no_sink_is_noop(self):
        MetricsLogger().write({"a": 1})     # must not raise or print

    def test_stdout_sink_flushes_each_record(self, monkeypatch):
        events = []

        class FakeOut:
            def write(self, s):
                events.append(("write", s))

            def flush(self):
                events.append(("flush", None))
        monkeypatch.setattr(M.sys, "stdout", FakeOut())
        ml = MetricsLogger(verbose=True)
        ml.write({"a": 1})
        # records must hit the pipe immediately, not sit in the
        # block buffer of an idle server
        assert events[0][0] == "write"
        assert ("flush", None) in events
        assert json.loads(events[0][1]) == {"a": 1}

    def test_rotation_gzip_and_retention(self, tmp_path, monkeypatch):
        # rotation filenames are second-resolution; fake the clock so
        # every rotation gets a distinct stamp
        seq = itertools.count()

        class _FakeDateTime:
            @staticmethod
            def now(tz=None):
                return (real_dt.datetime(2026, 1, 1,
                                         tzinfo=real_dt.timezone.utc)
                        + real_dt.timedelta(seconds=next(seq)))
        monkeypatch.setattr(M, "dt", types.SimpleNamespace(
            datetime=_FakeDateTime, timezone=real_dt.timezone))

        ml = MetricsLogger(log_dir=str(tmp_path))
        ml.max_size = 1          # every write overflows -> rotate next
        ml.max_files = 2
        for i in range(6):
            ml.write({"i": i})

        names = os.listdir(tmp_path)
        live = [f for f in names if f.endswith(".log")]
        gz = sorted(f for f in names if f.endswith(".log.gz"))
        assert len(live) == 1            # exactly one active file
        assert len(gz) == ml.max_files   # retention pruned the oldest
        with gzip.open(tmp_path / gz[-1], "rt") as fp:
            rec = json.loads(fp.readline())
        assert rec == {"i": 4}           # newest archived record intact


class TestTraceCorrelation:
    """The structured request log carries the flight-recorder trace_id
    so a slow log line can be joined to its span waterfall."""

    def test_log_fills_trace_id_from_context(self):
        from gsky_tpu import obs
        obs.reset_recorder()
        try:
            c = MetricsLogger().collector()
            with obs.start_trace("req") as tr:
                c.log(200)
            assert c.info["trace_id"] == tr.trace_id
        finally:
            obs.reset_recorder()

    def test_log_untraced_leaves_trace_id_blank(self):
        c = MetricsLogger().collector()
        c.log(200)
        assert c.info["trace_id"] == ""


class TestCacheHandles:
    """cache_stats resolves its import handles once per process, then
    reads through the owning modules so swapped singletons stay live."""

    def test_handles_resolved_once(self, monkeypatch):
        monkeypatch.setattr(M, "_CACHE_HANDLES", None)
        M.cache_stats()
        handles = M._CACHE_HANDLES
        assert handles                       # resolved and cached
        M.cache_stats()
        assert M._CACHE_HANDLES is handles   # no per-scrape re-resolve

    def test_handles_read_live_singletons(self, monkeypatch):
        import gsky_tpu.pipeline.scene_cache as sc
        monkeypatch.setattr(M, "_CACHE_HANDLES", None)
        M.cache_stats()                      # resolve against the real module
        monkeypatch.setattr(sc, "default_scene_cache",
                            types.SimpleNamespace(
                                stats=lambda: {"hits": 41, "misses": 1}))
        out = M.cache_stats()
        assert out["scene"] == {"hits": 41, "misses": 1}
