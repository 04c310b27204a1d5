"""Staged GetMap pipeline tests (`pipeline/tile_stages.py`): the staged
route's answer against the modular route's across resample methods,
the fused/multi-CRS/RGB ladder rungs and degraded partial mosaics; encode-pool exception/cancellation behaviour;
stage-gate release on error; shape-bucket prewarm zero-recompile."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from gsky_tpu.geo.crs import EPSG4326, parse_crs
from gsky_tpu.geo.transform import GeoTransform
from gsky_tpu.index import MASClient, MASStore
from gsky_tpu.index.crawler import extract
from gsky_tpu.io import write_geotiff
from gsky_tpu.io.png import (decode_png, encode_async, encode_pool_stats,
                             reset_encode_pool)
from gsky_tpu.pipeline import tile_stages
from gsky_tpu.resilience import faults
from gsky_tpu.server.config import ConfigWatcher
from gsky_tpu.server.metrics import MetricsLogger
from gsky_tpu.server.ows import OWSServer

UTM55 = parse_crs("EPSG:32755")
DATE = "2020-01-10T00:00:00.000Z"
# granules sit around lon 148.0-148.3, lat -35.2..-35.4 (the shared
# fixture footprint); bbox in EPSG:3857
BBOX3857 = "16478548,-4211230,16489679,-4198025"
SIZE = 512


def _tif(root, name, *, origin=(590000.0, 6105000.0), crs=UTM55,
         px=30.0, bands=1, seed=1):
    """One int16 granule named so the crawler dates it 2020-01-10."""
    rng = np.random.default_rng(seed)
    gt = GeoTransform(origin[0], px, 0.0, origin[1], 0.0, -px)
    shape = (bands, SIZE, SIZE) if bands > 1 else (SIZE, SIZE)
    data = rng.uniform(200, 3000, shape).astype(np.int16)
    data[..., : SIZE // 8, : SIZE // 8] = -999
    p = os.path.join(root, name)
    write_geotiff(p, data, gt, crs, nodata=-999)
    return p


def _ingest(store, path, namespace=None):
    rec = extract(path, approx_stats=True)
    assert not rec.get("error"), rec
    if namespace is not None:
        for ds in rec["geo_metadata"]:
            ds["namespace"] = namespace
    store.ingest(rec)


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    root = tmp_path_factory.mktemp("tilepipe")
    data = root / "data"
    data.mkdir()
    store = MASStore()
    # two overlapping UTM granules sharing one product namespace (the
    # single-product mosaic every byte-identity case renders)
    _ingest(store, _tif(str(data), "MOSA_20200110.tif", seed=1),
            namespace="MOS")
    _ingest(store, _tif(str(data), "MOSB_20200110.tif", seed=2,
                        origin=(590000.0 + SIZE * 30 // 2,
                                6105000.0 - SIZE * 30 // 4)),
            namespace="MOS")
    # a UTM + EPSG:4326 pair over the same area: mixed-CRS granule sets
    # fall off the single-group fused path on BOTH modes
    _ingest(store, _tif(str(data), "MCRSA_20200110.tif", seed=3),
            namespace="MCRS")
    _ingest(store, _tif(str(data), "MCRSB_20200110.tif", seed=4,
                        origin=(147.9, -35.0), crs=EPSG4326,
                        px=0.6 / SIZE),
            namespace="MCRS")
    # one 3-band scene for the packed-RGBA ladder rung
    _ingest(store, _tif(str(data), "S2RGB_20200110.tif", bands=3, seed=5))
    # degraded mosaic: granule B's file is corrupted AFTER ingestion, so
    # its window decode fails deterministically (1/2 <= the degradation
    # budget -> a partial mosaic, not an error)
    _ingest(store, _tif(str(data), "DEGA_20200110.tif", seed=6),
            namespace="DEG")
    broken = _tif(str(data), "DEGB_20200110.tif", seed=7,
                  origin=(590000.0 + SIZE * 30 // 2,
                          6105000.0 - SIZE * 30 // 4))
    _ingest(store, broken, namespace="DEG")
    with open(broken, "wb") as fp:
        fp.write(b"this is no longer a GeoTIFF")

    palette = {"interpolate": True, "colours": [
        {"R": 0, "G": 0, "B": 128, "A": 255},
        {"R": 255, "G": 255, "B": 0, "A": 255}]}
    layers = [
        {"name": "mosaic", "data_source": str(data),
         "rgb_products": ["MOS"], "time_generator": "mas",
         "palette": palette},
        {"name": "mosaic_bi", "data_source": str(data),
         "rgb_products": ["MOS"], "resample": "bilinear",
         "time_generator": "mas", "palette": palette},
        {"name": "mosaic_cu", "data_source": str(data),
         "rgb_products": ["MOS"], "resample": "cubic",
         "time_generator": "mas", "palette": palette},
        {"name": "multicrs", "data_source": str(data),
         "rgb_products": ["MCRS"], "time_generator": "mas",
         "palette": palette},
        {"name": "rgb", "data_source": str(data),
         "rgb_products": ["S2RGB_20200110_b1", "S2RGB_20200110_b2",
                          "S2RGB_20200110_b3"],
         "resample": "bilinear", "time_generator": "mas"},
        {"name": "degraded", "data_source": str(data),
         "rgb_products": ["DEG"], "time_generator": "mas",
         "palette": palette},
    ]
    conf_dir = root / "conf"
    conf_dir.mkdir()
    (conf_dir / "config.json").write_text(json.dumps({
        "service_config": {"ows_hostname": "", "mas_address": "inproc"},
        "layers": layers}))

    mas_client = MASClient(store)
    watcher = ConfigWatcher(str(conf_dir),
                            mas_factory=lambda addr: mas_client,
                            install_signal=False)
    # gateway=None: the serving gateway's response cache + singleflight
    # would satisfy the second fetch of every pair from cache and turn
    # the byte-identity comparison into a tautology
    server = OWSServer(watcher, mas_factory=lambda addr: mas_client,
                       metrics=MetricsLogger(), gateway=None)
    return {"server": server, "watcher": watcher}


def _get(env, path):
    from aiohttp.test_utils import TestClient, TestServer

    async def go():
        client = TestClient(TestServer(env["server"].app()))
        await client.start_server()
        try:
            resp = await client.get(path)
            return (resp.status, resp.content_type, await resp.read(),
                    dict(resp.headers))
        finally:
            await client.close()
    return asyncio.new_event_loop().run_until_complete(go())


def _getmap(layer, fmt="image/png", size=256):
    return (f"/ows?service=WMS&request=GetMap&version=1.3.0"
            f"&layers={layer}&crs=EPSG:3857&bbox={BBOX3857}"
            f"&width={size}&height={size}&format={fmt}&time={DATE}")


def _fetch_both(env, path, monkeypatch):
    """The same request through the staged path, then through the
    modular route (`_render_with_fusion`: `TilePipeline.process` +
    `ops.scale.scale_to_byte`) by having `render_staged` decline it, as
    it does a request the fast path cannot serve.  The two share
    neither `composite_prep` nor a fused kernel."""
    staged = _get(env, path)
    with monkeypatch.context() as m:
        m.setattr("gsky_tpu.server.ows.render_staged",
                  lambda *a, **kw: None)
        modular = _get(env, path)
    return modular, staged


# where the two routes are different XLA programs over the same f32
# arithmetic (the packed RGBA kernel scales three channels in one
# fusion, the modular route one band at a time), a value on a byte
# boundary may round to either side: the bound tests_tpu/ holds two
# programs of one kernel to
RGB_BOUND_SHARE, RGB_BOUND_LEVELS = 0.005, 1


class TestByteIdentity:
    @pytest.mark.parametrize("layer,fmt,ctype", [
        ("mosaic", "image/png", "image/png"),
        ("mosaic_bi", "image/png", "image/png"),
        ("mosaic_cu", "image/png", "image/png"),
        ("multicrs", "image/png", "image/png"),
        ("rgb", "image/png", "image/png"),
        ("mosaic", "image/jpeg", "image/jpeg"),
    ])
    def test_staged_matches_modular(self, env, monkeypatch, layer, fmt,
                                    ctype):
        modular, staged = _fetch_both(env, _getmap(layer, fmt),
                                      monkeypatch)
        assert modular[0] == 200, modular[2][:300]
        assert staged[0] == 200, staged[2][:300]
        assert modular[1] == staged[1] == ctype
        if layer == "rgb":
            a = decode_png(modular[2]).astype(np.int16)
            b = decode_png(staged[2]).astype(np.int16)
            diff = np.abs(a - b)
            assert diff.max() <= RGB_BOUND_LEVELS
            assert np.mean(diff != 0) <= RGB_BOUND_SHARE
        else:
            assert modular[2] == staged[2]
        if ctype == "image/png":
            assert decode_png(staged[2]).shape == (256, 256, 4)

    def test_staged_rgb_is_the_kernels_bytes(self, env, monkeypatch):
        """The bound above is between two programs.  Staging itself
        (threads, prefetched readback, the encode pool) must leave the
        packed-RGBA kernel's bytes untouched: the served tile, decoded,
        equals `TilePipeline.render_rgba_byte` called directly with
        what `render_staged` was handed."""
        seen = {}

        def spy(pipe, req, n_exprs, *args):
            seen["pipe"], seen["req"] = pipe, req
            seen["style"] = args[:5]      # offset, scale, clip, colour, auto
            return tile_stages.render_staged(pipe, req, n_exprs, *args)

        monkeypatch.setattr("gsky_tpu.server.ows.render_staged", spy)
        staged = _get(env, _getmap("rgb"))
        assert staged[0] == 200, staged[2][:300]
        direct = seen["pipe"].render_rgba_byte(seen["req"], *seen["style"])
        assert direct is not None
        np.testing.assert_array_equal(decode_png(staged[2]),
                                      np.asarray(direct))

    def test_staged_output_not_empty(self, env):
        rgba = decode_png(_get(env, _getmap("mosaic"))[2])
        # the mosaic has real data: some opaque, non-uniform pixels
        assert (rgba[..., 3] == 255).any()
        assert len(np.unique(rgba[..., 0])) > 4

    def test_degraded_partial_mosaic(self, env, monkeypatch):
        """Granule B's file is corrupt: both routes must serve the SAME
        partial mosaic, labelled degraded — under an injected decode
        latency fault, which stresses the stage overlap without
        perturbing bytes (rate-1.0 latency clauses draw no RNG, so the
        fault sequence is identical across the two runs)."""
        faults.configure("decode:latency:1ms")
        try:
            modular, staged = _fetch_both(env, _getmap("degraded"),
                                          monkeypatch)
        finally:
            faults.reset()
        assert modular[0] == 200, modular[2][:300]
        assert staged[0] == 200, staged[2][:300]
        assert modular[3].get("X-GSKY-Degraded") == "decode"
        assert staged[3].get("X-GSKY-Degraded") == "decode"
        assert modular[2] == staged[2]

    def test_total_decode_loss_identical_error(self, env, monkeypatch):
        """decode:error:1.0 fails every scene load AND every window
        decode: both routes must raise the same TooManyFailures into
        the same 503 body (the staged path degrades through the
        fallback ladder, never a divergent error shape)."""
        from gsky_tpu.pipeline.scene_cache import default_scene_cache
        default_scene_cache.clear()    # force both routes through decode
        faults.configure("decode:error:1.0", seed=0)
        try:
            modular, staged = _fetch_both(env, _getmap("mosaic"),
                                          monkeypatch)
        finally:
            faults.reset()
        assert modular[0] == staged[0] == 503
        assert modular[2] == staged[2]
        assert b"decode failures exceed" in staged[2]


class TestStageTelemetry:
    def test_debug_tile_stages_and_gather_window(self, env):
        status, _, body, _ = _get(env, _getmap("mosaic"))
        assert status == 200
        status, _, body, _ = _get(env, "/debug")
        assert status == 200
        doc = json.loads(body)
        ts = doc["tile_stages"]
        assert ts["tiles"] >= 1
        for k in ("plan_s", "index_s", "decode_s", "dispatch_s",
                  "readback_s", "encode_s"):
            assert k in ts, ts
        assert "decode" in ts["gates"] and "dispatch" in ts["gates"]
        assert ts["gates"]["dispatch"]["entries"] >= 1
        assert ts["encode_pool"]["encoded"] >= 1
        gw = doc["executor"]["gather_window"]
        assert set(gw) == {"engaged", "declined"}

    def test_tile_index_is_a_span_where_the_query_runs(self, env,
                                                       monkeypatch):
        """`tile.index` is measured round the MAS query itself, so it
        lies inside `tile.plan`; `tile_stages.index_s` is the same
        clock pair it always was."""
        from gsky_tpu import obs
        obs.reset_recorder()
        m = MetricsLogger()
        monkeypatch.setattr(env["server"], "metrics", m)
        try:
            status, _, _, _ = _get(env, _getmap("mosaic"))
            traces = obs.default_recorder().traces()
        finally:
            obs.reset_recorder()
        assert status == 200
        spans = [sp for tr in traces for sp in tr["spans"]]
        plan = [sp for sp in spans if sp["name"] == "tile.plan"]
        index = [sp for sp in spans if sp["name"] == "tile.index"]
        assert len(plan) == 1 and len(index) == 1
        plan, index = plan[0], index[0]
        assert index["parent_id"] == plan["span_id"]
        assert plan["t0"] <= index["t0"]
        assert index["t0"] + index["dur_s"] \
            <= plan["t0"] + plan["dur_s"] + 1e-3
        last = m.summary()["tile_stages"]["last"]
        # index_s clocks the span from outside: the same to a fraction
        # of a millisecond, never less
        assert index["dur_s"] <= last["index_s"] < index["dur_s"] + 1e-3
        assert last["plan_s"] >= 0


class TestEncodePool:
    def test_exception_fans_out_to_awaiter(self):
        reset_encode_pool()

        def boom():
            raise ValueError("encode exploded")

        async def go():
            with pytest.raises(ValueError, match="encode exploded"):
                await encode_async(boom)
        try:
            asyncio.new_event_loop().run_until_complete(go())
            st = encode_pool_stats()
            assert st["pending"] == 0
            assert st["errors"] == 1
        finally:
            reset_encode_pool()

    def test_concurrent_errors_each_reach_their_awaiter(self):
        reset_encode_pool()

        def boom(i):
            raise RuntimeError(f"tile {i}")

        async def go():
            outs = await asyncio.gather(
                *[encode_async(boom, i) for i in range(6)],
                return_exceptions=True)
            assert sorted(str(e) for e in outs) == \
                [f"tile {i}" for i in range(6)]
        try:
            asyncio.new_event_loop().run_until_complete(go())
            st = encode_pool_stats()
            assert st["pending"] == 0
            assert st["errors"] == 6
        finally:
            reset_encode_pool()

    def test_cancellation_releases_pending_slot(self):
        """A cancelled await must still decrement the pending gauge, or
        the occupancy telemetry creeps up forever under client aborts."""
        reset_encode_pool()

        def slow():
            time.sleep(0.2)
            return b"late"

        async def go():
            task = asyncio.ensure_future(encode_async(slow))
            await asyncio.sleep(0.05)     # encode is on the pool now
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
        try:
            asyncio.new_event_loop().run_until_complete(go())
            # the pool thread finishes its sleep, then the finally runs
            deadline = time.time() + 5
            while (encode_pool_stats()["pending"] != 0
                   and time.time() < deadline):
                time.sleep(0.01)
            st = encode_pool_stats()
            assert st["pending"] == 0
        finally:
            reset_encode_pool()

    def test_spans_and_result_round_trip(self):
        reset_encode_pool()

        async def go():
            spans = {}
            out = await encode_async(lambda: b"png-bytes", spans=spans)
            assert out == b"png-bytes"
            assert spans["encode_s"] >= 0.0
            assert spans["encode_queue_max"] >= 1
        try:
            asyncio.new_event_loop().run_until_complete(go())
        finally:
            reset_encode_pool()


class TestStageGate:
    def test_release_on_exception(self):
        tile_stages.reset_gates()
        try:
            gate = tile_stages._gate("dispatch")
            with pytest.raises(RuntimeError):
                with gate.enter():
                    raise RuntimeError("dispatch blew up")
            # every slot must be back: `limit` concurrent entries
            # acquire without blocking
            entered = []
            import contextlib
            with contextlib.ExitStack() as stack:
                for _ in range(gate.limit):
                    stack.enter_context(gate.enter())
                    entered.append(1)
            assert len(entered) == gate.limit
            st = gate.stats()
            assert st["waiting"] == 0
            assert st["entries"] == 1 + gate.limit
        finally:
            tile_stages.reset_gates()

    def test_queue_highwater_lands_in_spans(self):
        tile_stages.reset_gates()
        try:
            gate = tile_stages._gate("decode")
            spans = {}
            with gate.enter(spans, "decode_queue_max"):
                pass
            assert spans["decode_queue_max"] == 1
        finally:
            tile_stages.reset_gates()

    def test_env_sizing(self, monkeypatch):
        monkeypatch.setenv("GSKY_TILE_DISPATCH_SLOTS", "5")
        tile_stages.reset_gates()
        try:
            assert tile_stages._gate("dispatch").limit == 5
        finally:
            tile_stages.reset_gates()


class TestPrewarm:
    def test_layer_specs_from_config(self, env):
        from gsky_tpu.server.prewarm import layer_specs
        specs = layer_specs(env["watcher"].configs)
        assert ("near", 1, True, 0) in specs
        assert ("bilinear", 1, True, 0) in specs
        assert ("cubic", 1, True, 0) in specs
        assert ("bilinear", 3, True, 0) in specs

    def test_layer_expr_specs_parse_config_entries(self):
        """Config algebra entries are `name = expr` — the spec sweep
        must apply the same split the request path does, and dedup
        structurally identical expressions to one fingerprint."""
        from gsky_tpu.server.config import Config, Layer
        from gsky_tpu.server.prewarm import layer_expr_specs
        lay = Layer.from_json({
            "name": "algebra", "data_source": "/tmp",
            "rgb_products": ["ndvi = (a - b) / (a + b)"],
            "styles": [
                # same structure, different variable names: one spec
                {"name": "same",
                 "rgb_products": ["nd2 = (x - y) / (x + y)"]},
                {"name": "mask",
                 "rgb_products": ["m = a > 1200 ? a : b"]},
                # bare band name: trivial, rides the byte path
                {"name": "plain", "rgb_products": ["a"]},
            ]})
        specs = layer_expr_specs({"": Config(layers=[lay])})
        assert len(specs) == 2
        assert {fp.slots for _, _, _, fp in specs} == {
            ("a", "b"), ("x", "y")}

    def test_prewarm_then_render_zero_recompile(self, env):
        """After prewarming the configured layers at a tile size no
        other test uses (128 px), rendering that exact shape through
        the staged server path must compile nothing new."""
        from gsky_tpu.server.prewarm import compile_count, prewarm
        warm = prewarm(env["watcher"].configs, sizes=[128],
                       bucket=512, max_scenes=2)
        assert warm["failures"] == 0
        assert warm["programs"] > 0
        c0 = compile_count()
        for layer in ("mosaic", "mosaic_bi", "rgb"):
            status, _, body, _ = _get(env, _getmap(layer, size=128))
            assert status == 200, body[:300]
        assert compile_count() - c0 == 0

    def test_prewarm_is_idempotent_in_process(self, env):
        """A second identical prewarm is pure jit-cache hits."""
        from gsky_tpu.server.prewarm import prewarm
        prewarm(env["watcher"].configs, sizes=[128], bucket=512,
                max_scenes=2)
        again = prewarm(env["watcher"].configs, sizes=[128],
                        bucket=512, max_scenes=2)
        assert again["compiles"] == 0
        assert again["failures"] == 0

    def test_prewarm_failure_is_fatal(self, env, monkeypatch):
        """A program that fails in the sweep, or a pallas kernel that
        lands in `_FAILED` during it, stops start-up: the server does
        not come up on a path it knows is broken."""
        import importlib

        from gsky_tpu.ops import pallas_tpu as pt
        from gsky_tpu.server.prewarm import PrewarmError, prewarm
        warp = importlib.import_module("gsky_tpu.ops.warp")

        def broken(*a, **kw):
            raise RuntimeError("no such program")

        with monkeypatch.context() as m:
            m.setattr(warp, "render_rgba_ctrl", broken)
            with pytest.raises(PrewarmError, match="1 program"):
                prewarm(env["watcher"].configs, sizes=[128], bucket=512,
                        max_scenes=2)

        real = pt.render_byte_raced

        def fails_a_kernel(*a, **kw):
            pt._FAILED["warp_render"] = "NotImplementedError: simulated"
            return real(*a, **kw)

        monkeypatch.setattr(pt, "render_byte_raced", fails_a_kernel)
        try:
            with pytest.raises(PrewarmError, match="warp_render"):
                prewarm(env["watcher"].configs, sizes=[128], bucket=512,
                        max_scenes=2)
        finally:
            pt._FAILED.pop("warp_render", None)


class TestCancellation:
    """End-to-end cooperative cancellation at the pipeline stages: a
    fired token must unwind decode/dispatch/readback/encode/batch waits
    promptly AND give every gate slot / pool slot back."""

    class _Req:
        @staticmethod
        def dst_gt():
            return None
        crs, height, width = None, 64, 64

    def test_cancel_unwinds_decode_and_releases_gate(self):
        from gsky_tpu.resilience import (RequestCancelled, cancel_scope,
                                         reset_cancel_stats)
        from gsky_tpu.resilience.cancel import cancel_stats
        reset_cancel_stats()
        tile_stages.reset_gates()
        try:
            with cancel_scope() as tok:
                tok.cancel("client-disconnect")
                with pytest.raises(RequestCancelled):
                    tile_stages._decode_stage(None, self._Req(),
                                              [object()], {})
            gate = tile_stages._gate("decode")
            st = gate.stats()
            assert st["waiting"] == 0
            # every slot came back: fill the gate without blocking
            import contextlib
            with contextlib.ExitStack() as stack:
                for _ in range(gate.limit):
                    stack.enter_context(gate.enter())
            assert cancel_stats()["stages"].get("decode", 0) >= 1
        finally:
            tile_stages.reset_gates()
            reset_cancel_stats()

    def test_cancel_inside_dispatch_gate_skips_dispatch(self):
        from gsky_tpu.resilience import (RequestCancelled, cancel_scope,
                                         reset_cancel_stats)
        reset_cancel_stats()
        tile_stages.reset_gates()
        ran = []
        try:
            with cancel_scope() as tok:
                tok.cancel("deadline")
                with pytest.raises(RequestCancelled):
                    tile_stages._dispatch_stage(
                        lambda: ran.append(1), {})
            assert ran == []            # the device never saw it
            gate = tile_stages._gate("dispatch")
            import contextlib
            with contextlib.ExitStack() as stack:
                for _ in range(gate.limit):
                    stack.enter_context(gate.enter())
        finally:
            tile_stages.reset_gates()
            reset_cancel_stats()

    def test_cancel_before_readback(self):
        from gsky_tpu.resilience import (RequestCancelled, cancel_scope,
                                         reset_cancel_stats)
        reset_cancel_stats()
        with cancel_scope() as tok:
            tok.cancel()
            with pytest.raises(RequestCancelled):
                tile_stages._readback(np.zeros((2, 2)), {})
        reset_cancel_stats()

    def test_cancelled_encode_returns_slot_without_encoding(self):
        from gsky_tpu.resilience import (RequestCancelled, cancel_scope,
                                         reset_cancel_stats)
        reset_cancel_stats()
        reset_encode_pool()
        ran = []

        async def go():
            with cancel_scope() as tok:
                tok.cancel("client-disconnect")
                with pytest.raises(RequestCancelled):
                    await encode_async(lambda: ran.append(1))
        try:
            asyncio.new_event_loop().run_until_complete(go())
            assert ran == []            # no CPU burnt for a dead client
            st = encode_pool_stats()
            assert st["pending"] == 0
        finally:
            reset_encode_pool()
            reset_cancel_stats()
