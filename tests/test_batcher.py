"""RenderBatcher unit tests (`pipeline/batcher.py`): power-of-two
padding, wait-timer cancellation on full flush, union-window bucketing
vs whole-stack fallback, exception fan-out — plus the `split_bbox`
ragged edge-tile contract the WCS export plan depends on."""

import threading

import numpy as np
import pytest

import gsky_tpu.pipeline.batcher as batcher_mod
from gsky_tpu.pipeline.batcher import RenderBatcher

H = W = 8
STATICS = ("near", 1, (H, W), 1, False, 0)


def _item(i=0):
    ctrl = np.full((2, 3), float(i), np.float32)
    params = np.full(8, float(i), np.float32)
    sp = np.zeros(4, np.float32)
    return ctrl, params, sp


def _submit(b, stack, n, win_raw=None, key=("k",)):
    """Drive n concurrent render() calls; returns (results, errors)."""
    results = [None] * n
    errors = [None] * n

    def go(i):
        try:
            ctrl, params, sp = _item(i)
            results[i] = b.render(key, stack, ctrl, params, sp, STATICS,
                                  win_raw=win_raw)
        except Exception as e:   # noqa: BLE001 - recorded for asserts
            errors[i] = e
    ts = [threading.Thread(target=go, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    return results, errors


class _FakeKernel:
    """Stands in for render_scenes_ctrl_many: records batch shapes."""

    def __init__(self):
        self.calls = []

    def __call__(self, stack, ctrls, params, sps, method, n_ns, out_hw,
                 step, auto, colour_scale, win=None, win0=None):
        self.calls.append({"n": int(np.asarray(ctrls).shape[0]),
                           "win": win})
        return np.zeros((np.asarray(ctrls).shape[0], *out_hw), np.uint8)


@pytest.fixture()
def fake(monkeypatch):
    fk = _FakeKernel()
    monkeypatch.setattr(batcher_mod, "render_scenes_ctrl_many", fk)
    return fk


STACK = np.zeros((2, 32, 32), np.float32)
# union-window tests need a stack larger than the minimum
# 64-px gather bucket, or finish_window always declines
BIG = np.zeros((2, 256, 256), np.float32)


class TestPadding:
    @pytest.mark.parametrize("n,padded", [(1, 1), (3, 4), (5, 8),
                                          (16, 16)])
    def test_pow2_padding(self, fake, n, padded):
        b = RenderBatcher(max_batch=16, max_wait_s=0.25)
        results, errors = _submit(b, STACK, n)
        assert errors == [None] * n
        assert all(r is not None and r.shape == (H, W) for r in results)
        assert sum(c["n"] for c in fake.calls) >= padded
        assert max(c["n"] for c in fake.calls) == padded

    def test_full_batch_is_single_dispatch(self, fake):
        b = RenderBatcher(max_batch=16, max_wait_s=5.0)
        results, errors = _submit(b, STACK, 16)
        assert errors == [None] * 16
        # one dispatch of exactly max_batch, no timer-driven stragglers
        assert [c["n"] for c in fake.calls] == [16]


class TestTimerCancel:
    def test_full_flush_cancels_wait_timer(self, fake, monkeypatch):
        """When a batch fills to max_batch, the pending max_wait timer
        must be cancelled, not left to fire into an empty group."""
        made = []
        real_timer = threading.Timer

        class RecordingTimer(real_timer):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                made.append(self)
        monkeypatch.setattr(batcher_mod.threading, "Timer",
                            RecordingTimer)
        b = RenderBatcher(max_batch=4, max_wait_s=30.0)
        _submit(b, STACK, 4)
        assert len(made) == 1
        # cancel() sets finished; a 30 s timer can't have fired already
        assert made[0].finished.is_set()
        made[0].join(timeout=1)
        assert not made[0].is_alive()


class TestUnionWindow:
    def test_union_bucketing(self, fake):
        b = RenderBatcher(max_batch=4, max_wait_s=0.2)
        # small overlapping footprints union into one sub-stack window
        results, errors = _submit(b, BIG, 3, win_raw=(4, 40, 2, 50))
        assert errors == [None] * 3
        assert any(c["win"] is not None for c in fake.calls)
        assert b.win_batches >= 1

    def test_missing_bounds_forces_whole_stack(self, fake):
        b = RenderBatcher(max_batch=4, max_wait_s=0.2)
        results, errors = _submit(b, STACK, 3, win_raw=None)
        assert errors == [None] * 3
        assert all(c["win"] is None for c in fake.calls)
        assert b.full_batches >= 1

    def test_whole_stack_union_falls_back(self, fake):
        b = RenderBatcher(max_batch=4, max_wait_s=0.2)
        # bounds spanning the full stack -> finish_window declines
        results, errors = _submit(b, BIG, 2, win_raw=(0, 256, 0, 256))
        assert errors == [None] * 2
        assert all(c["win"] is None for c in fake.calls)
        assert b.full_batches >= 1

    def test_union_window_direct(self):
        b = RenderBatcher()
        items = [(None, None, None, (2, 70, 4, 100), None),
                 (None, None, None, (4, 90, 2, 80), None)]
        win, win0 = b._union_window(items, BIG)
        assert win is not None
        wr, wc = win
        # bucketed to cover rows 2..90, cols 2..100
        assert wr >= 88 and wc >= 98
        r0, c0 = int(win0[0]), int(win0[1])
        assert r0 <= 2 and c0 <= 2
        assert r0 + wr <= 256 and c0 + wc <= 256

    def test_union_window_any_none(self):
        b = RenderBatcher()
        items = [(None, None, None, (2, 70, 4, 100), None),
                 (None, None, None, None, None)]
        assert b._union_window(items, BIG) == (None, None)


class TestExceptionFanOut:
    def test_kernel_error_reaches_all_waiters(self, monkeypatch):
        def boom(*a, **kw):
            raise RuntimeError("kernel exploded")
        monkeypatch.setattr(batcher_mod, "render_scenes_ctrl_many", boom)
        b = RenderBatcher(max_batch=4, max_wait_s=0.2)
        results, errors = _submit(b, STACK, 4)
        assert results == [None] * 4
        assert all(isinstance(e, RuntimeError) for e in errors)


class TestThroughputKnee:
    def test_first_sample_per_size_discarded(self):
        b = RenderBatcher()
        b._observe(8, 8, 800.0)            # carries the jit compile
        assert b.stats()["tile_ms"] == {}
        b._observe(8, 8, 80.0)
        assert b.stats()["tile_ms"] == {8: 10.0}

    def test_knee_ratchets_down_past_regression(self):
        """The regression shape: x8 batches at 9.29 ms/tile vs 4.10 single
        -> the ratchet caps the flush threshold at 4."""
        b = RenderBatcher(max_batch=16)
        assert b.knee == 16
        for _ in range(3):
            b._observe(1, 1, 4.10)
        for _ in range(3):
            b._observe(8, 8, 8 * 9.29)
        assert b.knee == 4
        # the knee never ratchets back up on a lucky sample
        b._observe(8, 8, 8 * 0.5)
        assert b.knee == 4

    def test_size_within_ratio_keeps_knee(self):
        b = RenderBatcher(max_batch=16)
        for _ in range(3):
            b._observe(1, 1, 4.0)
        for _ in range(3):
            b._observe(8, 8, 8 * 4.5)      # 1.125x: under the 1.25 knee
        assert b.knee == 16

    def test_flush_threshold_respects_knee(self, fake):
        b = RenderBatcher(max_batch=16, max_wait_s=30.0)
        b.knee = 2
        # far below max_batch, but at the knee: flushes immediately
        # instead of waiting out the 30 s timer
        results, errors = _submit(b, STACK, 2)
        assert errors == [None, None]
        assert [c["n"] for c in fake.calls] == [2]

    def test_env_cap_pins_knee(self, monkeypatch):
        monkeypatch.setenv("GSKY_RENDER_BATCH_MAX", "2")
        assert RenderBatcher(max_batch=16).knee == 2
        monkeypatch.setenv("GSKY_RENDER_BATCH_MAX", "not-a-number")
        assert RenderBatcher(max_batch=16).knee == 16
        monkeypatch.setenv("GSKY_RENDER_BATCH_MAX", "64")
        # clamped to the module-wide max batch
        assert RenderBatcher(max_batch=16).knee == 16

    def test_stats_payload_shape(self):
        b = RenderBatcher()
        st = b.stats()
        assert set(st) == {"batch_knee", "tile_ms", "win_batches",
                           "full_batches", "paged_batches",
                           "pad_waste_bytes"}
        assert st["batch_knee"] == b.knee
        assert st["win_batches"] == 0
        assert st["full_batches"] == 0
        assert st["paged_batches"] == 0
        assert st["pad_waste_bytes"] == 0


class TestSplitBBoxRaggedEdges:
    def test_ragged_last_row_and_column(self):
        from gsky_tpu.geo.transform import BBox, split_bbox
        bbox = BBox(0.0, 0.0, 100.0, 60.0)
        tiles = split_bbox(bbox, 100, 60, 32, 32)
        # 4 columns (32,32,32,4) x 2 rows (32,28)
        assert len(tiles) == 8
        xs = sorted({t[1] for t in tiles})
        ys = sorted({t[2] for t in tiles})
        assert xs == [0, 32, 64, 96]
        assert ys == [0, 32]
        by_off = {(t[1], t[2]): t for t in tiles}
        assert by_off[(96, 0)][3] == 4      # ragged last column width
        assert by_off[(0, 32)][4] == 28     # ragged last row height
        # offsets + sizes tile the output exactly, no overlap, no gap
        cover = np.zeros((60, 100), np.int32)
        for tb, ox, oy, tw, th in tiles:
            cover[oy:oy + th, ox:ox + tw] += 1
        assert (cover == 1).all()
        # each tile's bbox is the pixel-aligned slice of the request
        for tb, ox, oy, tw, th in tiles:
            assert tb.xmin == pytest.approx(ox)
            assert tb.xmax == pytest.approx(ox + tw)
            assert tb.ymax == pytest.approx(60 - oy)
            assert tb.ymin == pytest.approx(60 - (oy + th))
