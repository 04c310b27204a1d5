#!/usr/bin/env python
"""How a cubic tap set is best fetched, measured on the chip.

`ops.warp._resample_c` fetches a cubic pixel's 4 x 4 Catmull-Rom taps
either a gather a tap (16 gathers) or as neighbourhoods (`_tap_pairs`:
two gathers of 8-value rows from an unfolded copy of the source).  This
probe times the forms, checks each against the per-tap form bit for bit,
and reads each program's temporary memory from the compiled executable:

    python tools/tap_probe.py                  # on the chip: every part
    python tools/tap_probe.py --part forms     # one part
    JAX_PLATFORMS=cpu python tools/tap_probe.py --small   # a rehearsal

Parts:

``forms``   the whole cubic resample of one 1536² window at an export
            tile's 1,048,576 coordinates, int16 and f32, in five forms:
            ``per_tap`` (16 scalar gathers), ``slice_1x4`` (four gathers
            of ``slice_sizes=(1, 4)``, one a tap row), ``slice_4x4`` (one
            gather of ``(4, 4)``), ``rows_2x8`` (the kernel's: two
            gathers of unfolded 8-value rows) and ``rows_1x16`` (one
            gather of unfolded 16-value rows).
``tiles``   a WMS tile's kernels at 256²: `render_rgba_ctrl` over 3
            channels and `warp_scenes_ctrl_scored` over one, each over 1
            and 4 granules from a 384² window, 16-bit and f32, per tap
            against neighbourhoods (three-channel: rows of 8 C values,
            or 8 a channel).
``memory``  compile only: the temporary bytes of the export kernel and
            of `render_rgba_ctrl` at the export cell's tile (a 1536² f32
            window, depth 1), at the largest inputs the executor hands
            them (a 4096² window, whole-scene stacks of depth 10, three
            channels over four granules) and near the bound, in the form
            the kernel picks and in the other.

The last stdout line is the result as one JSON object; it also goes to
``<out>/result.json``.  The process takes the chip unless
JAX_PLATFORMS=cpu (`gsky_tpu.device.ensure_platform`).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import gsky_tpu.ops.warp  # noqa: E402,F401

warp = sys.modules["gsky_tpu.ops.warp"]
NODATA = -999.0


# --- the fetch forms: each gives `_tap_pairs`' 16 (h, w, C) taps --------

def _starts(src, r0, c0):
    H, W, _ = src.shape
    sp = jnp.pad(src, ((3, 3), (3, 3), (0, 0)))
    return sp, jnp.clip(r0, -2, H) + 2, jnp.clip(c0, -2, W) + 2


def _slices(src, r0, c0, rows: int):
    """``16 // rows`` gathers of a (rows, 4, C) slice a pixel."""
    sp, rs, cs = _starts(src, r0, c0)
    C = src.shape[2]
    taps = []
    for dr in range(0, 4, rows):
        def one(r, c, dr=dr):
            return lax.dynamic_slice(sp, (r + dr, c, 0), (rows, 4, C))
        blk = jax.vmap(one)(rs.reshape(-1), cs.reshape(-1))
        blk = blk.reshape(r0.shape + (rows, 4, C))
        taps += [blk[..., i, j, :] for i in range(rows) for j in range(4)]
    return taps


def _rows_1x16(src, r0, c0):
    """One gather of a 16-value row a pixel from a 4 x 4 unfolding."""
    sp, rs, cs = _starts(src, r0, c0)
    H, W, C = src.shape
    hp, wp = H + 3, W + 3
    quads = jnp.stack([sp[dr:dr + hp, dc:dc + wp]
                       for dr in range(4) for dc in range(4)], axis=2)
    got = quads.reshape(hp * wp, 16 * C)[rs * wp + cs]
    got = got.reshape(r0.shape + (16, C))
    return [got[..., j, :] for j in range(16)]


def _per_channel(src, r0, c0, pairs=warp._tap_pairs):
    """`_tap_pairs` a channel: rows of 8 values whatever C is."""
    parts = [pairs(src[..., c:c + 1], r0, c0) for c in range(src.shape[2])]
    return [jnp.concatenate([p[k] for p in parts], axis=-1)
            for k in range(16)]


FETCH = {"slice_1x4": lambda s, r, c: _slices(s, r, c, 1),
         "slice_4x4": lambda s, r, c: _slices(s, r, c, 4),
         "rows_1x16": _rows_1x16, "rows_8_a_channel": _per_channel}


class _Form:
    """Trace the kernels in one form: every cubic program unfolds (or
    none does) in place of `_unfolds`' choice, with ``fetch`` in place
    of `_tap_pairs`.  Clears jax's caches on entry and exit, so no
    program of another form is reused."""

    def __init__(self, unfold, fetch=None):
        self.unfold, self.fetch = unfold, fetch

    def __enter__(self):
        self.saved = (warp._unfolds, warp._tap_pairs)
        warp._unfolds = lambda method, sources: \
            self.unfold and method == "cubic"
        if self.fetch is not None:
            warp._tap_pairs = self.fetch
        jax.clear_caches()

    def __exit__(self, *exc):
        warp._unfolds, warp._tap_pairs = self.saved
        jax.clear_caches()


def _form(name):
    if name == "per_tap":
        return _Form(False)
    if name in ("rows_2x8", "rows_8C"):
        return _Form(True)
    return _Form(True, FETCH[name])


# --- measuring -------------------------------------------------------------

def _time(fn, args, reps: int):
    out = jax.block_until_ready(fn(*args))           # compile + warm
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(round((time.perf_counter() - t0) * 1e3, 4))
    return out, ms


def _temp_bytes(fn, args):
    try:
        mem = fn.lower(*args).compile().memory_analysis()
    except Exception as e:      # noqa: BLE001 - reported, not fatal
        return {"error": f"{type(e).__name__}: {str(e)[:300]}"}
    if mem is None:
        return {}
    return {"temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes)}


def _equal(got, want):
    """(bit-equal, pixels differing, largest difference) over outputs."""
    n, big = 0, 0.0
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            d = a.view(np.uint32) != b.view(np.uint32)
            if d.any():
                big = max(big, float(np.nanmax(np.abs(a - b)[d],
                                               initial=0.0)))
        else:
            d = a != b
        n += int(d.sum())
    return {"bit_equal": n == 0, "n_diff": n, "max_diff": big}


def _run_forms(label, forms, make_fn, args, reps, res):
    ref = None
    for name in forms:
        with _form(name):
            fn = make_fn()
            rec = _temp_bytes(fn, args)
            out, ms = _time(fn, args, reps)
        rec.update(ms=ms, ms_min=min(ms))
        if ref is None:
            ref = out
        rec.update(_equal(out, ref))
        res[f"{label}:{name}"] = rec
        print(f"{label}:{name}", json.dumps(rec), flush=True)


# --- the parts -------------------------------------------------------------

def _coords(h, win, rng):
    """An export tile's coordinates: a 1.3-pixel step turned 2 degrees,
    over the window's top-left edges, a few NaN."""
    jj, ii = np.meshgrid(np.arange(h) + 0.5, np.arange(h) + 0.5)
    th = np.deg2rad(2.0)
    s = 1.3 * (win - 8) / (1.3 * h * 1.04)
    cols = -3.0 + s * (np.cos(th) * jj - np.sin(th) * ii)
    rows = -2.0 + s * (np.sin(th) * jj + np.cos(th) * ii)
    nan = rng.uniform(0, 1, rows.shape) < 0.01
    rows[nan] = np.nan
    return rows.astype(np.float32), cols.astype(np.float32)


def part_forms(res, small, reps):
    win, h = (48, 32) if small else (1536, 1024)
    rng = np.random.default_rng(44)
    rows, cols = _coords(h, win, rng)
    for dt in (np.int16, np.float32):
        src = rng.uniform(100.0, 3000.0, (win, win, 1))
        src[rng.uniform(0, 1, src.shape) < 0.03] = NODATA
        src = src.astype(dt)
        args = (jnp.asarray(src), jnp.float32(NODATA),
                jnp.asarray(rows), jnp.asarray(cols))
        forms = ["per_tap", "rows_2x8", "rows_1x16"]
        if dt == np.int16:
            forms += ["slice_1x4", "slice_4x4"]

        def make():
            unfold = warp._unfolds("cubic", [])
            return jax.jit(lambda s, n, r, c: warp._resample_c(
                s, n, r, c, "cubic", unfold=unfold))
        _run_forms(f"forms:{np.dtype(dt).name}:{win}", forms, make, args,
                   reps, res)


def _ctrl(h, step, scale, off):
    gh = (h - 1 + step - 1) // step + 1
    jj, ii = np.meshgrid(np.arange(gh) * step + 0.5,
                         np.arange(gh) * step + 0.5)
    th = np.deg2rad(1.0)
    return np.stack([off + scale * (np.cos(th) * jj - np.sin(th) * ii),
                     off + scale * (np.sin(th) * jj + np.cos(th) * ii)]
                    ).astype(np.float32)


def _params(G, S):
    p = np.zeros((G, 11), np.float32)
    for k in range(G):
        p[k] = [0.3 * k, 1.0, 0.0, 0.2 * k, 0.0, 1.0, S - 40, S - 30,
                0.0, 10.0 + k, 0.0]
    return p


def part_tiles(res, small, reps):
    h, win, S, step = (32, 48, 64, 16) if small else (256, 384, 1024, 16)
    rng = np.random.default_rng(45)
    ctrl = jnp.asarray(_ctrl(h, step, 1.2, 20.0))
    win0 = np.array([8, 8], np.int32)
    sp = jnp.asarray(np.array([0.0, 0.1, 3000.0], np.float32))
    for G, dt in [(G, dt) for G in (1, 4)
                  for dt in (np.uint16, np.float32)]:
        name = np.dtype(dt).name
        bands = tuple(tuple(jnp.asarray(rng.integers(
            1, 4000, (S, S)).astype(dt)) for _ in range(3))
            for _ in range(G))
        params = jnp.asarray(_params(G, S))
        prios = jnp.asarray(np.tile(np.arange(G, dtype=np.float32)[:, None],
                                    (1, 3)))
        w0 = jnp.asarray(np.tile(win0, (G, 1)))

        def make_rgba():
            fn = warp.render_rgba_ctrl.__wrapped__
            return jax.jit(lambda b, c, p, q, s, w: fn(
                b, c, p, q, s, "cubic", (h, h), step, True, 0,
                win=(win, win), win0=w))
        _run_forms(f"tiles:render_rgba_ctrl:{name}:C3:G{G}",
                   ["per_tap", "rows_8C", "rows_8_a_channel"], make_rgba,
                   (bands, ctrl, params, prios, sp, w0), reps, res)
        stack = jnp.asarray(rng.integers(1, 4000, (G, S, S)).astype(
            np.int16 if dt == np.uint16 else dt))

        def make_scored():
            fn = warp.warp_scenes_ctrl_scored.__wrapped__
            return jax.jit(lambda s, c, p, w: fn(
                s, c, p, "cubic", 1, (h, h), step, win=(win, win),
                win0=w))
        _run_forms(f"tiles:warp_scenes_ctrl_scored:{stack.dtype}:C1:G{G}",
                   ["per_tap", "rows_2x8"], make_scored,
                   (stack, ctrl, params, jnp.asarray(win0)), reps, res)


def part_memory(res, small):
    """Compile only, from shapes: nothing is allocated on the device."""
    f = 16 if small else 1
    sds = jax.ShapeDtypeStruct

    def scored(B, S, win, h, dt):
        fn = warp.warp_scenes_ctrl_scored.__wrapped__
        h = h // f
        stack = sds((B, S[0] // f, S[1] // f), dt)
        w = None if win is None else (win // f, win // f)
        args = (stack, sds((2, h // 16 + 1, h // 16 + 1), jnp.float32),
                sds((B, 11), jnp.float32), sds((2,), jnp.int32))
        form = warp.tap_form("cubic", stack, w)
        return form, lambda: jax.jit(lambda s, c, p, w0: fn(
            s, c, p, "cubic", 1, (h, h), 16, win=w, win0=w0)), args

    def rgba(G, S, win, h, dt=jnp.uint16):
        fn = warp.render_rgba_ctrl.__wrapped__
        h = h // f
        bands = tuple(tuple(sds((S // f, S // f), dt)
                            for _ in range(3)) for _ in range(G))
        w = (win // f, win // f)
        args = (bands, sds((2, h // 16 + 1, h // 16 + 1), jnp.float32),
                sds((G, 11), jnp.float32), sds((G, 3), jnp.float32),
                sds((3,), jnp.float32), sds((G, 2), jnp.int32))
        form = "neighbourhood" if warp._unfolds(
            "cubic", [((w[0], w[1], 3), dt)] * G) else "per_tap"
        return form, lambda: jax.jit(lambda b, c, p, q, s, w0: fn(
            b, c, p, q, s, "cubic", (h, h), 16, True, 0, win=w,
            win0=w0)), args

    cases = {
        # the export cell's tile: the scene cache holds f32 scenes
        "scored:f32:B1:win1536:out1024": scored(
            1, (2048, 2048), 1536, 1024, jnp.float32),
        "scored:int16:B1:win1536:out1024": scored(
            1, (2048, 2048), 1536, 1024, jnp.int16),
        "scored:int16:B4:win1536:out1024": scored(
            4, (2048, 2048), 1536, 1024, jnp.int16),
        "scored:int16:B1:win4096:out1024": scored(
            1, (7680, 7936), 4096, 1024, jnp.int16),
        "scored:f32:B1:win4096:out1024": scored(
            1, (7680, 7936), 4096, 1024, jnp.float32),
        # no window: the footprint covers the whole stack
        "scored:int16:B10:whole7680x7936:out1024": scored(
            10, (7680, 7936), None, 1024, jnp.int16),
        "scored:int16:B10:whole7680x7936:out256": scored(
            10, (7680, 7936), None, 256, jnp.int16),
        "rgba:uint16:C3:G4:win4096:out256": rgba(4, 8192, 4096, 256),
        "rgba:uint16:C3:G4:win2048:out256": rgba(4, 4096, 2048, 256),
        "rgba:uint16:C3:G4:win1024:out256": rgba(4, 2048, 1024, 256),
        # near the bound
        "scored:int16:B1:win2048:out1024": scored(
            1, (4096, 4096), 2048, 1024, jnp.int16),
        "scored:int16:B2:win1536:out1024": scored(
            2, (2048, 2048), 1536, 1024, jnp.int16),
        "scored:f32:B2:win1536:out1024": scored(
            2, (2048, 2048), 1536, 1024, jnp.float32),
        "scored:f32:B1:win1024:out1024": scored(
            1, (2048, 2048), 1024, 1024, jnp.float32),
        "scored:int16:B4:win768:out256": scored(
            4, (2048, 2048), 768, 256, jnp.int16),
        "rgba:float32:C3:G1:win1024:out256": rgba(
            1, 2048, 1024, 256, jnp.float32),
        "rgba:float32:C3:G4:win512:out256": rgba(
            4, 1024, 512, 256, jnp.float32),
        "rgba:float32:C3:G4:win384:out256": rgba(
            4, 1024, 384, 256, jnp.float32),
    }
    for name, (form, make, args) in cases.items():
        for forced in ("picked", "other"):
            nb = (form == "neighbourhood") == (forced == "picked")
            use = "rows_2x8" if nb else "per_tap"
            with _form(use):
                rec = _temp_bytes(make(), args)
            rec.update(form=use)
            res[f"memory:{name}:{forced}"] = rec
            print(f"memory:{name}:{forced}", json.dumps(rec), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--part", choices=("forms", "tiles", "memory"),
                    action="append")
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes, for a rehearsal on the CPU")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/tap_probe")
    a = ap.parse_args()
    from gsky_tpu.device import ensure_platform
    plat = ensure_platform()
    res = {"device": {"platform": plat["platform"],
                      "kind": plat["device_kind"]},
           "tapside": warp._use_tapside()}
    parts = a.part or ["forms", "tiles", "memory"]
    if "forms" in parts:
        part_forms(res, a.small, a.reps)
    if "tiles" in parts:
        part_tiles(res, a.small, a.reps)
    if "memory" in parts:
        part_memory(res, a.small)
    stats = jax.devices()[0].memory_stats() or {}
    res["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, "result.json"), "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
