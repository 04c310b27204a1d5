"""The device residency budget (`device.residency_budget`) and the one
ledger that keeps decoded scenes and the executor's stacks of them
inside it (`pipeline/scene_cache.py`)."""

import logging
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsky_tpu import device
from gsky_tpu.geo.crs import EPSG3857, parse_crs
from gsky_tpu.geo.transform import GeoTransform
from gsky_tpu.io import write_geotiff
from gsky_tpu.pipeline.executor import WarpExecutor
from gsky_tpu.pipeline.scene_cache import SceneCache
from gsky_tpu.pipeline.types import Granule

UTM55 = parse_crs("EPSG:32755")
SIDE = 256
SCENE_BYTES = SIDE * SIDE * 4           # one scene as the cache holds it
GT = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
V5E_BYTES_LIMIT = 16909008896           # what a v5e chip reports (15.75 GiB)
S2_BAND_PX = 11008 * 11008              # a 10,980^2 band, 256-px-bucketed


@pytest.fixture
def budget_of(monkeypatch):
    """`residency_budget()` as resolved on a device that reports
    `bytes_limit` (None: a backend without memory_stats, the CPU)."""
    def resolve(bytes_limit):
        stats = None if bytes_limit is None else {"bytes_limit": bytes_limit}
        monkeypatch.setattr(device, "_budget", None)
        monkeypatch.setattr(jax, "devices", lambda: [
            types.SimpleNamespace(memory_stats=lambda: stats)])
        return device.residency_budget()
    yield resolve
    monkeypatch.setattr(device, "_budget", None)


def _granule(tmp_path, k):
    p = str(tmp_path / f"scene{k}_20200110.tif")
    data = np.full((SIDE, SIDE), 100 + k, np.int16)
    write_geotiff(p, data, GT, UTM55, nodata=-999)
    return Granule(path=p, ds_name=p, namespace="b1", base_namespace="b1",
                   band=1, time_index=None, timestamp=float(k),
                   geo_transform=list(GT.to_gdal()), srs="EPSG:32755",
                   nodata=-999.0)


class TestBudget:
    def test_cpu_fallback_is_the_old_constants(self, budget_of):
        b = budget_of(None)
        assert b["source"] == "fallback"
        assert b["budget"] == 2 << 30
        cache = SceneCache()
        assert cache.max_bytes == 2 << 30
        assert cache.max_scene_px == 64 << 20

    def test_derived_from_what_the_device_reports(self, budget_of):
        b = budget_of(V5E_BYTES_LIMIT)
        assert b["source"] == "memory_stats"
        assert b["bytes_limit"] == V5E_BYTES_LIMIT
        assert b["budget"] == V5E_BYTES_LIMIT - b["drill_stacks"] \
            - b["headroom"]
        assert b["drill_stacks"] == device.DRILL_STACK_BYTES == 4 << 30
        assert b["headroom"] == V5E_BYTES_LIMIT // 4

    def test_a_granule_band_is_cacheable_on_the_chip_only(self, budget_of):
        """A 10,980^2 band (121 M px) is past the old 64 Mi px: cached
        where the device's budget allows, twelve of them resident at
        once, and declined on the fallback budget."""
        assert S2_BAND_PX > 64 << 20
        budget_of(V5E_BYTES_LIMIT)
        chip = SceneCache()
        assert S2_BAND_PX <= chip.max_scene_px
        assert 12 * S2_BAND_PX * 4 <= chip.max_bytes
        budget_of(None)
        assert S2_BAND_PX > SceneCache().max_scene_px

    def test_debug_shows_the_budget_and_what_is_charged(self):
        from gsky_tpu.server.metrics import MetricsLogger
        doc = MetricsLogger().summary()
        res = doc["device"]["residency"]
        assert {"source", "bytes_limit", "drill_stacks", "headroom",
                "budget"} <= set(res)
        scene = doc["cache"]["scene"]
        assert {"upload_bytes", "resident_bytes", "stack_bytes",
                "stack_evictions", "evictions", "budget_bytes"} <= set(scene)
        assert scene["resident_bytes"] + scene["stack_bytes"] \
            <= scene["budget_bytes"]


class TestLedger:
    @pytest.mark.parametrize("scenes_that_fit,cached", [(8, True),
                                                        (7, False)])
    def test_scene_cached_only_if_eight_fit(self, tmp_path, caplog,
                                            scenes_that_fit, cached):
        cache = SceneCache(max_bytes=scenes_that_fit * SCENE_BYTES)
        g = _granule(tmp_path, 0)
        with caplog.at_level(logging.WARNING, logger="gsky.scene_cache"):
            assert (cache.get(g) is not None) == cached
            cache.get(g)
        told = [r.getMessage() for r in caplog.records]
        if cached:
            assert not told
            assert cache.stats()["upload_bytes"] == SCENE_BYTES
        else:
            # why, and once for the file however often it is asked for
            assert len(told) == 1 and "over budget" in told[0]
            assert cache.stats()["resident_bytes"] == 0

    def test_stack_bytes_are_charged_and_reused(self):
        cache = SceneCache(max_bytes=16 * SCENE_BYTES)
        made = []

        def make():
            made.append(1)
            return jnp.zeros((4, SIDE, SIDE), jnp.float32)

        a = cache.stack((1, 2, 3, 4), make)
        assert cache.stack((1, 2, 3, 4), make) is a and len(made) == 1
        st = cache.stats()
        assert st["stack_bytes"] == 4 * SCENE_BYTES and st["stacks"] == 1
        cache.clear()
        assert cache.stats()["stack_bytes"] == 0

    def test_scripted_sequence_stays_inside_the_budget(self, tmp_path):
        """Stacks go first, least recently used first, then scenes; a
        stack that does not fit beside the scenes is not kept."""
        budget = 8 * SCENE_BYTES
        cache = SceneCache(max_bytes=budget)
        gs = [_granule(tmp_path, k) for k in range(9)]

        def stack(key, n):
            return cache.stack(key, lambda: jnp.zeros((n, SIDE, SIDE),
                                                      jnp.float32))

        def held():
            st = cache.stats()
            assert st["resident_bytes"] + st["stack_bytes"] <= budget
            return (st["resident_bytes"] // SCENE_BYTES,
                    st["stack_bytes"] // SCENE_BYTES,
                    st["stack_evictions"], st["evictions"])

        for g in gs[:4]:
            cache.get(g)
        stack("a", 2)
        stack("b", 2)
        assert held() == (4, 4, 0, 0)           # full
        stack("a", 2)                           # "a" is now the newer
        cache.get(gs[4])                        # needs room: "b" goes
        assert held() == (5, 2, 1, 0)
        stack("c", 3)                           # 5 + 2 + 3 > 8: "a" goes
        assert held() == (5, 3, 2, 0)
        stack("d", 3)
        assert held() == (5, 3, 3, 0)           # "c" went, "d" is kept
        for g in gs[5:8]:
            cache.get(g)                        # scenes push "d" out ...
        assert held() == (8, 0, 4, 0)
        assert stack("e", 1).shape == (1, SIDE, SIDE)
        assert held() == (8, 0, 4, 0)           # served, not kept
        cache.get(gs[8])                        # ... then the oldest scene
        assert held() == (8, 0, 4, 1)
        assert cache.get(gs[1]).serial          # still resident: a hit
        assert cache.stats()["misses"] == 9

    def test_executor_charges_stacks_but_not_band_tuples(self, tmp_path):
        cache = SceneCache(max_bytes=64 * SCENE_BYTES)
        ex = WarpExecutor()
        gs = [_granule(tmp_path, k) for k in range(3)]
        dst_gt = GeoTransform(16478548.0, 40.0, 0.0, -4198025.0, 0.0, -40.0)
        args = (gs, [0, 0, 0], [3.0, 2.0, 1.0], dst_gt, EPSG3857, 256, 256)
        group, = ex._scene_groups(*args, cache=cache)
        stack = group.stack
        assert stack.shape == (4, SIDE, SIDE)   # 3 scenes, padded to 4
        assert cache.stats()["stack_bytes"] == 4 * SCENE_BYTES
        group, = ex._scene_groups(*args, cache=cache, stacked=False)
        devs = group.stack
        assert len(devs) == 4 and devs[3] is devs[0]
        assert all(d.shape == (SIDE, SIDE) for d in devs)
        assert cache.stats()["stack_bytes"] == 4 * SCENE_BYTES
        assert cache.stats()["resident_bytes"] == 3 * SCENE_BYTES
