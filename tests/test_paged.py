"""Ragged paged rendering tier (`ops/paged.py`, `pipeline/pages.py`):
interpret-mode parity of the paged warp kernel against the XLA
reference AND the bucketed pallas kernel (bit-exact nearest, <= 2 ulp
bilinear, page-boundary-crossing gathers, ragged scene counts in one
batch), PagePool residency semantics (LRU, sharing, pins, decline
rollback), ledger token versioning, and executor engagement
with the GSKY_PAGED=0 byte-identity escape."""

from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from gsky_tpu.ops import kernel_ledger
from gsky_tpu.ops import paged
from gsky_tpu.ops import pallas_tpu as pt
from gsky_tpu.ops.warp import render_scenes_ctrl, warp_scenes_ctrl_scored
from gsky_tpu.pipeline.pages import PagePool


@pytest.fixture(autouse=True)
def _tmp_ledger(tmp_path, monkeypatch):
    """Hermetic ledger per test: parity runs must never read or write
    the shared default race ledger."""
    monkeypatch.setenv("GSKY_KERNEL_LEDGER", str(tmp_path / "ledger.jsonl"))


# small pages keep interpret-mode gathers cheap while still exercising
# multi-page walks on modest scenes (96 px scene -> 2 row pages)
PR, PC = 64, 128


def _pool(cap=64):
    return PagePool(capacity=cap, page_rows=PR, page_cols=PC)


def _inputs(seed=0, B=4, S=96, h=64, w=64, step=16, n_ns=2,
            lo=-500.0, hi=3000.0, c_lo=4.0, c_hi=None):
    """Same recipe as tests/test_warp_pallas.py::_inputs — NaN patches,
    an all-nodata granule, two namespaces, unique priorities — with B
    configurable down to 1 for the ragged-batch tests.  Interpolated
    parity vs XLA needs lo > 0 (sign-stable data) for the same
    FMA-contraction reason documented there."""
    rng = np.random.default_rng(seed)
    stack = rng.uniform(lo, hi, (B, S, S)).astype(np.float32)
    stack[0, 10:20, 10:20] = np.nan
    if B > 1:
        stack[1, :, :] = -999.0
    gh = (h - 1 + step - 1) // step + 1
    gw = (w - 1 + step - 1) // step + 1
    if c_hi is None:
        c_hi = S - 12.0
    ctrl = np.stack([
        np.linspace(c_lo, c_hi, gw,
                    dtype=np.float32)[None, :].repeat(gh, 0),
        np.linspace(c_lo, c_hi, gh,
                    dtype=np.float32)[:, None].repeat(gw, 1)])
    params = np.zeros((B, 11), np.float32)
    for k in range(B):
        params[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01, 0.99,
                     S, S, -999.0, 100.0 - k, k % n_ns]
    return (jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params),
            h, w, step, n_ns)


def _stage_full(pool, stack, params, serial0=100):
    """Stage every granule's WHOLE scene into the pool and build the
    (T, S) page table + (T, 16) params rows the kernel expects —
    the hand-rolled equivalent of `executor._paged_from_group` with
    full page coverage.  Tables come back pinned (callers unpin or
    drop the pool)."""
    arr = np.asarray(stack)
    B = arr.shape[0]
    tabs, grids = [], []
    for k in range(B):
        sh, sw = arr[k].shape
        ni = -(-sh // pool.page_rows)
        nj = -(-sw // pool.page_cols)
        t = pool.table_for(jnp.asarray(arr[k]), serial0 + k,
                           0, ni - 1, 0, nj - 1)
        assert t is not None
        tabs.append(t)
        grids.append((ni, nj))
    S = 1
    while S < max(t.size for t in tabs):
        S *= 2
    tables = np.zeros((B, S), np.int32)
    p16 = np.zeros((B, paged.PARAMS_W), np.float32)
    p16[:, :11] = np.asarray(params)[:, :11]
    for k, (t, (ni, nj)) in enumerate(zip(tabs, grids)):
        tables[k, :t.size] = t
        p16[k, 13] = ni * pool.page_rows
        p16[k, 14] = nj * pool.page_cols
        p16[k, 15] = nj
    return tables, p16


def _run_paged(pool, tables, p16, ctrl, method, n_ns, hw, step):
    with pool.locked_pool() as parr:
        c, b = paged.warp_scored_paged(
            parr, jnp.asarray(tables[None]), jnp.asarray(p16),
            jnp.asarray(ctrl)[None], method, n_ns, hw, step,
            interpret=True)
    return np.asarray(c[0]), np.asarray(b[0])


class TestPagedKernelParity:
    @pytest.mark.parametrize("seed", [0, 3])
    def test_nearest_bit_exact_vs_xla(self, seed):
        stack, ctrl, params, h, w, step, n_ns = _inputs(seed)
        pool = _pool()
        tables, p16 = _stage_full(pool, stack, params)
        cp, bp = _run_paged(pool, tables, p16, ctrl, "near", n_ns,
                            (h, w), step)
        cx, bx = warp_scenes_ctrl_scored(stack, ctrl, params, "near",
                                         n_ns, (h, w), step)
        np.testing.assert_array_equal(np.asarray(bx), bp)
        np.testing.assert_array_equal(np.asarray(cx), cp)

    def test_bilinear_2ulp_vs_xla_bit_exact_vs_pallas(self):
        stack, ctrl, params, h, w, step, n_ns = _inputs(
            1, lo=1.0, hi=4000.0)
        pool = _pool()
        tables, p16 = _stage_full(pool, stack, params)
        cp, bp = _run_paged(pool, tables, p16, ctrl, "bilinear", n_ns,
                            (h, w), step)
        cx, bx = warp_scenes_ctrl_scored(stack, ctrl, params,
                                         "bilinear", n_ns, (h, w), step)
        np.testing.assert_array_equal(np.asarray(bx), bp)
        np.testing.assert_array_almost_equal_nulp(np.asarray(cx), cp,
                                                  nulp=2)
        # the strongest paged-parity statement: the page walk is
        # BIT-exact against the bucketed pallas kernel (same body,
        # different gather plumbing)
        cb, bb = pt.warp_scenes_scored_pallas(stack, ctrl, params,
                                              "bilinear", n_ns, (h, w),
                                              step, interpret=True)
        np.testing.assert_array_equal(np.asarray(cb), cp)
        np.testing.assert_array_equal(np.asarray(bb), bp)

    def test_cubic_2ulp_vs_pallas(self):
        """Cubic is held to the bound ops/paged.py documents for
        interpolated methods, <= 2 ulp — of the TAP magnitude: the
        16-tap sum has negative weights, the two programs may contract
        it differently, and an ulp of a result that cancelled to near
        zero bounds nothing.  The mosaic winners stay bit-exact."""
        stack, ctrl, params, h, w, step, n_ns = _inputs(2)
        pool = _pool()
        tables, p16 = _stage_full(pool, stack, params)
        cp, bp = _run_paged(pool, tables, p16, ctrl, "cubic", n_ns,
                            (h, w), step)
        cb, bb = pt.warp_scenes_scored_pallas(stack, ctrl, params,
                                              "cubic", n_ns, (h, w),
                                              step, interpret=True)
        ulp = np.spacing(np.float32(np.nanmax(np.abs(np.asarray(stack)))))
        assert np.abs(np.asarray(cb) - cp).max() <= 2 * ulp
        np.testing.assert_array_equal(np.asarray(bb), bp)

    def test_render_byte_bit_exact(self):
        stack, ctrl, params, h, w, step, n_ns = _inputs(
            4, lo=1.0, hi=4000.0)
        pool = _pool()
        tables, p16 = _stage_full(pool, stack, params)
        sp = np.array([10.0, 250.0, 0.0], np.float32)
        with pool.locked_pool() as parr:
            rp = paged.render_byte_paged(
                parr, jnp.asarray(tables[None]), jnp.asarray(p16),
                jnp.asarray(ctrl)[None], jnp.asarray(sp[None]), "near",
                n_ns, (h, w), step, True, 0, interpret=True)
        rx = render_scenes_ctrl(stack, ctrl, params, jnp.asarray(sp),
                                "near", n_ns, (h, w), step, True, 0)
        np.testing.assert_array_equal(np.asarray(rx),
                                      np.asarray(rp[0]))

    def test_edge_straddling_bit_exact(self):
        """Granule affines shifted so footprints run off the top-left:
        oob poisoning vs the true extent must behave identically to
        both references (nearest, bit-exact)."""
        stack, ctrl, params, h, w, step, n_ns = _inputs(5)
        params = np.asarray(params).copy()
        params[:, 0] -= 60.0
        params[:, 3] -= 55.0
        params = jnp.asarray(params)
        pool = _pool()
        tables, p16 = _stage_full(pool, stack, params)
        cp, bp = _run_paged(pool, tables, p16, ctrl, "near", n_ns,
                            (h, w), step)
        cx, bx = warp_scenes_ctrl_scored(stack, ctrl, params, "near",
                                         n_ns, (h, w), step)
        np.testing.assert_array_equal(np.asarray(bx), bp)
        np.testing.assert_array_equal(np.asarray(cx), cp)


class TestPageWalk:
    def test_page_boundary_crossing_gathers(self):
        """256-px scenes over 64x128 pages: the gather walks a 4x2 page
        grid and taps cross page boundaries in both axes.  Nearest is
        bit-exact vs XLA; bilinear is <= 2 ulp vs the bucketed pallas
        kernel (at these coordinate magnitudes XLA may contract the
        affine with FMA differently on either side, the same 1-ulp
        coordinate effect test_warp_pallas.py documents)."""
        stack, ctrl, params, h, w, step, n_ns = _inputs(
            6, S=256, lo=1.0, hi=4000.0, c_lo=40.0, c_hi=236.0)
        pool = _pool()
        tables, p16 = _stage_full(pool, stack, params)
        assert tables.shape[1] >= 8     # really a multi-page walk
        cp, bp = _run_paged(pool, tables, p16, ctrl, "near", n_ns,
                            (h, w), step)
        cx, bx = warp_scenes_ctrl_scored(stack, ctrl, params, "near",
                                         n_ns, (h, w), step)
        np.testing.assert_array_equal(np.asarray(bx), bp)
        np.testing.assert_array_equal(np.asarray(cx), cp)
        cp, bp = _run_paged(pool, tables, p16, ctrl, "bilinear", n_ns,
                            (h, w), step)
        cb, bb = pt.warp_scenes_scored_pallas(stack, ctrl, params,
                                              "bilinear", n_ns, (h, w),
                                              step, interpret=True)
        np.testing.assert_array_equal(np.asarray(bb), bp)
        np.testing.assert_array_almost_equal_nulp(np.asarray(cb), cp,
                                                  nulp=2)

    def test_ragged_scene_counts_one_batch(self):
        """Tiles with 1, 2 and 4 real granules coalesce into ONE padded
        (N=3 -> T=4) dispatch; every tile matches its own per-tile XLA
        reference bit for bit, and padding rows never leak."""
        pool = _pool()
        tiles = [_inputs(seed, B=B) for seed, B in
                 ((0, 1), (1, 2), (2, 4))]
        _, _, _, h, w, step, n_ns = tiles[0]
        staged = [_stage_full(pool, t[0], t[2], serial0=1000 * (i + 1))
                  for i, t in enumerate(tiles)]
        T = max(tb.shape[0] for tb, _ in staged)
        S = max(tb.shape[1] for tb, _ in staged)
        N = len(tiles)
        tables = np.zeros((N, T, S), np.int32)
        p16 = np.zeros((N, T, paged.PARAMS_W), np.float32)
        p16[:, :, 10] = -1.0            # ragged padding rows
        for i, (tb, pp) in enumerate(staged):
            tables[i, :tb.shape[0], :tb.shape[1]] = tb
            p16[i, :pp.shape[0]] = pp
        ctrls = jnp.stack([t[1] for t in tiles])
        with pool.locked_pool() as parr:
            c, b = paged.warp_scored_paged(
                parr, jnp.asarray(tables),
                jnp.asarray(p16.reshape(N * T, paged.PARAMS_W)),
                ctrls, "near", n_ns, (h, w), step, interpret=True)
        for i, (stack, ctrl, params, h, w, step, n_ns) in \
                enumerate(tiles):
            cx, bx = warp_scenes_ctrl_scored(stack, ctrl, params,
                                             "near", n_ns, (h, w), step)
            np.testing.assert_array_equal(np.asarray(bx),
                                          np.asarray(b[i]))
            np.testing.assert_array_equal(np.asarray(cx),
                                          np.asarray(c[i]))

    def test_null_page_table_all_invalid(self):
        """A table of slot 0 (the reserved all-NaN null page) with a
        live window extent must come back fully invalid — never
        garbage.  This is the prewarm contract: warmup dispatches run
        real page walks over the null page."""
        pool = _pool(cap=4)
        tables = np.zeros((2, 1), np.int32)
        p16 = np.zeros((2, paged.PARAMS_W), np.float32)
        for k in range(2):
            p16[k, :11] = [0, 1, 0, 0, 0, 1, PR, PC, -999.0,
                           5.0 - k, 0]
            p16[k, 13] = PR
            p16[k, 14] = PC
            p16[k, 15] = 1
        gh = 5
        ctrl = np.stack([
            np.linspace(2, 60, gh, dtype=np.float32)[None, :]
            .repeat(gh, 0),
            np.linspace(2, 60, gh, dtype=np.float32)[:, None]
            .repeat(gh, 1)])
        cp, bp = _run_paged(pool, tables, p16, jnp.asarray(ctrl),
                            "near", 1, (64, 64), 16)
        assert not np.isfinite(bp).any()
        assert (cp == 0.0).all()


class TestPagePool:
    def test_stage_hit_share_and_unpin(self):
        pool = _pool(cap=8)
        dev = jnp.asarray(np.arange(PR * PC,
                                    dtype=np.float32).reshape(PR, PC))
        t1 = pool.table_for(dev, 1, 0, 0, 0, 0)
        t2 = pool.table_for(dev, 1, 0, 0, 0, 0)
        np.testing.assert_array_equal(t1, t2)   # shared, not restaged
        st = pool.stats()
        assert st["staged"] == 1 and st["hits"] == 1
        assert 0 not in t1                      # slot 0 is reserved
        assert st["pinned"] >= 1
        pool.unpin(t1)
        pool.unpin(t2)
        assert pool.stats()["pinned"] == 0

    def test_staged_page_content_nan_padded(self):
        pool = _pool(cap=4)
        scene = np.arange(50 * 70, dtype=np.float32).reshape(50, 70)
        t = pool.table_for(jnp.asarray(scene), 7, 0, 0, 0, 0)
        with pool.locked_pool() as parr:
            page = np.asarray(parr[int(t[0])])
        np.testing.assert_array_equal(page[:50, :70], scene)
        assert np.isnan(page[50:, :]).all()
        assert np.isnan(page[:50, 70:]).all()
        pool.unpin(t)

    def test_pins_block_eviction_then_lru(self):
        pool = _pool(cap=3)                 # slots 1..2 usable
        a = jnp.asarray(np.ones((PR, PC), np.float32))
        t1 = pool.table_for(a, 1, 0, 0, 0, 0)
        t2 = pool.table_for(a, 2, 0, 0, 0, 0)
        # pool full and everything pinned -> decline, count it
        assert pool.table_for(a, 3, 0, 0, 0, 0) is None
        assert pool.stats()["declined"] == 1
        pool.unpin(t2)
        t3 = pool.table_for(a, 3, 0, 0, 0, 0)
        # scene 1 is older but pinned: the unpinned slot is recycled
        assert int(t3[0]) == int(t2[0])
        assert pool.stats()["evictions"] == 1
        pool.unpin(t1)
        pool.unpin(t3)

    def test_decline_rolls_back_partial_pins(self):
        pool = _pool(cap=3)                 # 2 usable slots
        big = jnp.asarray(np.ones((PR * 2, PC * 2), np.float32))
        # 4 pages can't fit: decline, and the partial pins roll back
        assert pool.table_for(big, 1, 0, 1, 0, 1) is None
        assert pool.stats()["pinned"] == 0
        t = pool.table_for(big, 1, 0, 0, 0, 1)   # 2 pages: fits
        assert t is not None and t.size == 2
        pool.unpin(t)

    def test_drop_scene_keeps_pinned_pages(self):
        pool = _pool(cap=8)
        a = jnp.asarray(np.ones((PR, PC), np.float32))
        t1 = pool.table_for(a, 1, 0, 0, 0, 0)
        t2 = pool.table_for(a, 2, 0, 0, 0, 0)
        pool.unpin(t2)
        pool.drop_scene(1)                  # pinned: stays resident
        pool.drop_scene(2)                  # unpinned: freed
        assert pool.stats()["resident"] == 1
        pool.unpin(t1)
        pool.drop_scene(1)
        assert pool.stats()["resident"] == 0


class TestLedgerTokenVersioning:
    def test_token_version_ok_matrix(self):
        # paged kernels require their version prefix
        assert kernel_ledger.token_version_ok(
            "warp_scored_paged", ("pg1", 1, 4, 2))
        assert not kernel_ledger.token_version_ok(
            "warp_scored_paged", ((8, 512, 512), "near"))
        assert not kernel_ledger.token_version_ok(
            "warp_scored_paged", ("pg0", 1))
        assert not kernel_ledger.token_version_ok(
            "warp_scored_paged", None)
        # bucketed kernels reject paged-scheme tokens, keep their own
        assert kernel_ledger.token_version_ok(
            "warp_scored", ((8, 512, 512), "near"))
        assert not kernel_ledger.token_version_ok(
            "warp_scored", ("pg1", 8))

    def test_paged_tokens_lead_with_version(self):
        pool_arr = jnp.zeros((2, PR, PC), jnp.float32)
        tables = jnp.zeros((1, 2, 2), jnp.int32)
        tok = paged._paged_token(pool_arr, tables, "near", 1, (64, 64),
                                 16)
        assert tok[0] == paged.PAGED_TOKEN_VERSION
        assert kernel_ledger.token_version_ok("warp_scored_paged", tok)
        assert not kernel_ledger.token_version_ok("warp_scored", tok)

    def test_schema_version_written_and_unknown_skipped(self, tmp_path):
        import json
        kernel_ledger.record("warp_scored", ((8, 64, 64), "near"),
                             "demoted", 1.0, 2.0)
        path = kernel_ledger.ledger_path()
        with open(path) as fp:
            doc = json.loads(fp.readline())
        assert doc["v"] == kernel_ledger.SCHEMA_VERSION
        # foreign lines: newer schema, junk version, and pre-versioning
        with open(path, "a") as fp:
            fp.write(json.dumps({"v": 99, "kernel": "future",
                                 "token": "('x',)",
                                 "verdict": "promoted"}) + "\n")
            fp.write(json.dumps({"v": "x", "kernel": "junk",
                                 "token": "('x',)",
                                 "verdict": "promoted"}) + "\n")
            fp.write(json.dumps({"kernel": "legacy",
                                 "token": "((8, 64, 64), 'near')",
                                 "verdict": "demoted"}) + "\n")
        ents = kernel_ledger.entries()
        kernels = {k for k, _ in ents}
        assert "warp_scored" in kernels          # v1: kept
        assert "legacy" in kernels               # missing v: kept (v1)
        assert "future" not in kernels           # v99: skipped
        assert "junk" not in kernels             # junk v: skipped

    def test_reload_skips_stale_token_schemes(self):
        """A bucketed-era verdict in the ledger must never replay onto
        a paged kernel (and vice versa); current-scheme verdicts do."""
        stale = ((8, 512, 512), "near", 2)
        good = ("pg1", 1, 4, 2, 64, 128, "near", 2, (64, 64), 16)
        foreign = ("pg1", 8)
        kernel_ledger.record("warp_scored_paged", stale, "demoted",
                             1.0, 2.0)
        kernel_ledger.record("warp_scored_paged", good, "demoted",
                             1.0, 2.0)
        kernel_ledger.record("warp_scored", foreign, "demoted",
                             1.0, 2.0)
        saved = set(pt._SLOW)
        try:
            applied = pt.reload_ledger()
            assert applied >= 1
            assert ("warp_scored_paged", good) in pt._SLOW
            assert ("warp_scored_paged", stale) not in pt._SLOW
            assert ("warp_scored", foreign) not in pt._SLOW
        finally:
            pt._SLOW.clear()
            pt._SLOW.update(saved)


def _fake_group(B=3, sh=200, sw=220, h=96, w=96, step=16, shift=True):
    """A crafted `executor.SceneGroup` so executor tests drive the real
    `_paged_from_group` span logic without a scene cache: B granules,
    one with its affine shifted off the top-left edge (partial page
    coverage), each scene on a footprint of its own (what the band-set
    leg groups by)."""
    from gsky_tpu.geo.crs import EPSG3857
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.pipeline.executor import SceneGroup, _bucket_pow2
    rng = np.random.default_rng(21)
    scenes = rng.uniform(0.0, 100.0, (B, sh, sw)).astype(np.float32)
    scenes[0, 40:60, 50:80] = np.nan
    Bp = _bucket_pow2(B)
    params64 = np.zeros((Bp, 11), np.float64)
    params64[:, 10] = -1.0
    for k in range(B):
        params64[k] = [0.4 * k - 0.2, 1.01, 0.02, 0.3 * k, -0.01,
                       0.99, sh, sw, -999.0, 10.0 - k, k % 2]
    if shift and B > 1:
        params64[1, 0] -= 60.0
        params64[1, 3] -= 55.0
    gh = (h - 1 + step - 1) // step + 1
    gw = (w - 1 + step - 1) // step + 1
    ctrl = np.stack([
        np.linspace(4.0, sw - 10.0, gw,
                    dtype=np.float32)[None, :].repeat(gh, 0),
        np.linspace(4.0, sh - 10.0, gh,
                    dtype=np.float32)[:, None].repeat(gw, 1)])
    gs = [SimpleNamespace(dev=jnp.asarray(scenes[k]), serial=500 + k,
                          gt=GeoTransform(1000.0 * k, 1.0, 0.0, 0.0, 0.0,
                                          -1.0),
                          crs=EPSG3857, height=sh, width=sw, nodata=-999.0)
          for k in range(B)]
    devs = [g.dev for g in gs] + [gs[0].dev] * (Bp - B)
    stack = jnp.stack(devs)
    return SceneGroup(stack=stack, ctrl=ctrl, ctrl_dev=jnp.asarray(ctrl),
                      params=params64.astype(np.float32),
                      params64=params64, step=step, skey=("sk",),
                      win=None, win0=None, scenes=gs)


@pytest.fixture()
def fresh_pool(monkeypatch):
    from gsky_tpu.pipeline import pages
    monkeypatch.setenv("GSKY_PAGE_SIZE", "64x128")
    monkeypatch.setenv("GSKY_PAGE_POOL_MB", "8")
    pages.reset_default_pool()
    yield pages
    pages.reset_default_pool()


class TestExecutorPaged:
    def test_paged_parity_and_gsky_paged_0_escape(self, monkeypatch,
                                                  fresh_pool):
        """The executor's paged dispatch (real `_paged_from_group` span
        logic, interpret kernel) matches the XLA path bit for bit, pins
        are released after dispatch, and GSKY_PAGED=0 restores the
        bucketed dispatch byte-identically."""
        from gsky_tpu.pipeline.executor import WarpExecutor
        group = _fake_group()
        monkeypatch.setattr(WarpExecutor, "_scene_groups",
                            lambda self, *a, **kw: [group])
        args = (None, [0, 0, 1], [3.0, 2.0, 1.0], None, None, 96, 96,
                2, "near")
        monkeypatch.setenv("GSKY_PALLAS", "0")
        ex0 = WarpExecutor()
        cx, vx = ex0.warp_mosaic_scenes(*args)
        assert ex0.paged_engaged == 0       # pallas off: never paged
        assert np.asarray(vx).any()
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        ex1 = WarpExecutor()
        cp, vp = ex1.warp_mosaic_scenes(*args)
        assert ex1.paged_engaged == 1 and ex1.paged_declined == 0
        np.testing.assert_array_equal(np.asarray(vx), np.asarray(vp))
        np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))
        assert fresh_pool._default is not None
        assert fresh_pool._default.stats()["pinned"] == 0
        assert fresh_pool._default.stats()["staged"] > 0
        monkeypatch.setenv("GSKY_PAGED", "0")
        ex2 = WarpExecutor()
        cb, vb = ex2.warp_mosaic_scenes(*args)
        assert ex2.paged_engaged == 0 and ex2.paged_declined == 0
        np.testing.assert_array_equal(np.asarray(vx), np.asarray(vb))
        np.testing.assert_array_equal(np.asarray(cx), np.asarray(cb))

    def test_over_slot_budget_declines_to_buckets(self, monkeypatch,
                                                  fresh_pool):
        """A window needing more pages than GSKY_PAGE_SLOTS falls back
        to the bucketed dispatch — counted, and still correct."""
        from gsky_tpu.pipeline.executor import WarpExecutor
        group = _fake_group()
        monkeypatch.setattr(WarpExecutor, "_scene_groups",
                            lambda self, *a, **kw: [group])
        args = (None, [0, 0, 1], [3.0, 2.0, 1.0], None, None, 96, 96,
                2, "near")
        monkeypatch.setenv("GSKY_PALLAS", "0")
        cx, vx = WarpExecutor().warp_mosaic_scenes(*args)
        monkeypatch.setenv("GSKY_PALLAS", "interpret")
        monkeypatch.setenv("GSKY_PAGE_SLOTS", "1")
        ex = WarpExecutor()
        cp, vp = ex.warp_mosaic_scenes(*args)
        assert ex.paged_engaged == 0 and ex.paged_declined == 1
        np.testing.assert_array_equal(np.asarray(vx), np.asarray(vp))
        np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))

