#!/usr/bin/env python
"""One-shot Pallas-vs-XLA race table (docs/KERNELS.md).

Prints the backend, the dispatch mode, and every verdict in the
persistent kernel ledger — the same data /debug serves, without
needing a server:

    python tools/kernel_probe.py               # dump the race table
    python tools/kernel_probe.py --selftest    # + tiny interpret parity run
    python tools/kernel_probe.py --reset       # delete the ledger (re-race)
    python tools/kernel_probe.py --mosaic      # on the chip: which Pallas
                                               # kernels Mosaic compiles

Honours GSKY_KERNEL_LEDGER / GSKY_PALLAS like the server does.  The
process resolves its platform like every entry point
(`gsky_tpu.device.ensure_platform`): it takes the chip unless
JAX_PLATFORMS=cpu.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _fmt_ms(v):
    return "-" if v is None else "%.3f" % v


def dump_table():
    from gsky_tpu.device import ensure_platform
    from gsky_tpu.ops import kernel_ledger, pallas_tpu as pt

    plat = ensure_platform()
    doc = kernel_ledger.stats()
    print("backend:         ", plat["platform"], plat["device_kind"])
    print("pallas enabled:  ", pt.use_pallas())
    print("warp pallas:     ", pt.warp_pallas_enabled())
    print("interpret mode:  ", pt.pallas_interpret())
    print("ledger path:     ", doc["ledger_path"])
    print("ledger present:  ", doc["ledger_present"])
    sess = doc.get("session", {})
    print("session state:    failed=%s demoted=%d proven=%d" % (
        sess.get("failed_kernels", []), sess.get("demoted_pairs", 0),
        sess.get("proven_pairs", 0)))
    print()
    if not doc["kernels"]:
        print("no race verdicts recorded yet")
        return
    hdr = "%-14s %-9s %11s %11s  %s" % (
        "kernel", "verdict", "pallas_ms", "xla_ms", "token")
    print(hdr)
    print("-" * len(hdr))
    for kernel in sorted(doc["kernels"]):
        k = doc["kernels"][kernel]
        for e in k["entries"]:
            print("%-14s %-9s %11s %11s  %s" % (
                kernel, e["verdict"], _fmt_ms(e["t_pallas_ms"]),
                _fmt_ms(e["t_xla_ms"]), e["token"]))
        print("%-14s totals: promoted=%d demoted=%d failed=%d" % (
            kernel, k["promoted"], k["demoted"], k["failed"]))


def selftest():
    """Tiny interpret-mode parity run: the fused warp kernel vs the XLA
    warp on one 64x64 tile.  Exit non-zero on mismatch."""
    import numpy as np

    import jax.numpy as jnp

    from gsky_tpu.ops.pallas_tpu import warp_scenes_scored_pallas
    from gsky_tpu.ops.warp import warp_scenes_ctrl_scored

    rng = np.random.default_rng(0)
    B, S, h, w, step = 2, 96, 64, 64, 16
    stack = rng.uniform(1.0, 100.0, size=(B, S, S)).astype(np.float32)
    gh = (h - 1 + step - 1) // step + 1
    gw = (w - 1 + step - 1) // step + 1
    ctrl = np.stack(np.meshgrid(np.linspace(4.0, 80.0, gw),
                                np.linspace(4.0, 80.0, gh)),
                    axis=0).astype(np.float32)
    params = np.array(
        [[0.1 * k, 1.0, 0.0, 0.1 * k, 0.0, 1.0, S, S, -999.0,
          100.0 - k, 0.0] for k in range(B)], np.float32)

    canv_p, best_p = warp_scenes_scored_pallas(
        jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params),
        method="near", n_ns=1, out_hw=(h, w), step=step, interpret=True)
    canv_x, best_x = warp_scenes_ctrl_scored(
        jnp.asarray(stack), jnp.asarray(ctrl), jnp.asarray(params),
        method="near", n_ns=1, out_hw=(h, w), step=step)
    np.testing.assert_array_equal(np.asarray(canv_p), np.asarray(canv_x))
    np.testing.assert_array_equal(np.asarray(best_p), np.asarray(best_x))
    print("selftest: interpret warp kernel parity OK "
          "(%dx%d tile, %d scenes, nearest, bit-exact)" % (h, w, B))


def mosaic_compile():
    """Compile every Pallas kernel for the real Mosaic backend at
    serving shapes, BYPASSING the selection gates, and print one JSON
    line {kernel: {"compiled", "seconds", "error"}} — the evidence
    `pallas_tpu.warp_pallas_enabled` and PERF.md "Bring-up" rest on.
    Needs the chip: off a TPU every non-interpret pallas_call raises."""
    import numpy as np

    import jax.numpy as jnp

    from gsky_tpu.device import ensure_platform
    from gsky_tpu.ops import paged
    from gsky_tpu.ops import pallas_tpu as pt
    from gsky_tpu.ops.expr import fingerprint, parse_band_expressions

    plat = ensure_platform()
    out = {}

    def probe(name, fn, *args, **kw):
        t0 = time.perf_counter()
        try:
            r = fn(*args, **kw)
            for leaf in (r if isinstance(r, tuple) else (r,)):
                leaf.block_until_ready()
            out[name] = {"compiled": True, "error": None}
        except Exception as e:  # noqa: BLE001 - the refusal IS the result
            out[name] = {"compiled": False,
                         "error": "%s: %s" % (type(e).__name__,
                                              str(e)[:400])}
        out[name]["seconds"] = round(time.perf_counter() - t0, 2)

    rng = np.random.default_rng(0)
    probe("mosaic_first_valid[T=8,256x256]", pt.mosaic_first_valid_pallas,
          jnp.asarray(rng.normal(size=(8, 256, 256)).astype(np.float32)),
          jnp.asarray(rng.uniform(size=(8, 256, 256)) > 0.5))
    probe("mosaic_first_valid[T=64,256x256]", pt.mosaic_first_valid_pallas,
          jnp.asarray(rng.normal(size=(64, 256, 256)).astype(np.float32)),
          jnp.asarray(rng.uniform(size=(64, 256, 256)) > 0.5))
    probe("masked_stats[1024x16384]", pt.masked_stats_pallas,
          jnp.asarray(rng.normal(size=(1024, 16384)).astype(np.float32)),
          jnp.asarray(rng.uniform(size=(1024, 16384)) > 0.5))

    # the warp family at a Landsat-size scene stack, 384-px window
    B, sh, sw, h, w, step = 4, 7680, 7936, 256, 256, 16
    stack = jnp.full((B, sh, sw), 1.0, jnp.float32)
    gh = (h - 1 + step - 1) // step + 1
    ctrl = jnp.asarray(np.stack(np.meshgrid(
        np.linspace(100.0, 400.0, gh), np.linspace(100.0, 400.0, gh)),
        axis=0).astype(np.float32))
    params = jnp.asarray(np.array(
        [[0.0, 1.0, 0.0, 0.0, 0.0, 1.0, sh, sw, np.nan, B - k, 0.0]
         for k in range(B)], np.float32))
    win0 = jnp.asarray(np.array([64, 64], np.int32))
    pool = jnp.zeros((16, 128, 512), jnp.float32)
    tables = jnp.zeros((1, B, 2), jnp.int32)
    p16 = np.zeros((B, paged.PARAMS_W), np.float32)
    p16[:, :11] = np.asarray(params)
    p16[:, 13], p16[:, 14], p16[:, 15] = 128, 1024, 2
    p16 = jnp.asarray(p16)
    sps = jnp.zeros((1, 3), jnp.float32)
    fp = fingerprint(parse_band_expressions(
        ["ndvi = (a - b) / (a + b)"]).expressions[0])
    for m in ("near", "bilinear", "cubic"):
        probe("warp_scored[%s]" % m, pt.warp_scenes_scored_pallas,
              stack, ctrl, params, method=m, n_ns=1, out_hw=(h, w),
              step=step, win=(384, 384), win0=win0)
        probe("warp_render[%s]" % m, pt.render_scenes_pallas,
              stack, ctrl, params, sps[0], method=m, n_ns=1,
              out_hw=(h, w), step=step, win=(384, 384), win0=win0)
        probe("warp_scored_paged[%s]" % m, paged.warp_scored_paged,
              pool, tables, p16, ctrl[None], method=m, n_ns=1,
              out_hw=(h, w), step=step)
        probe("warp_render_paged[%s]" % m, paged.render_byte_paged,
              pool, tables, p16, ctrl[None], sps, method=m, n_ns=1,
              out_hw=(h, w), step=step)
        probe("render_expr_paged[%s]" % m, paged.render_expr_paged,
              pool, tables, p16, ctrl[None], sps,
              jnp.asarray(fp.const_array()[None]), method=m, n_ns=2,
              out_hw=(h, w), step=step, fp=fp.key)
    print(json.dumps({"platform": plat["platform"],
                      "device_kind": plat["device_kind"],
                      "kernels": out}))
    return out


def reset():
    from gsky_tpu.ops import kernel_ledger

    path = kernel_ledger.ledger_path()
    if os.path.exists(path):
        os.unlink(path)
        print("deleted", path, "- every kernel re-races on next start")
    else:
        print("no ledger at", path)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true",
                    help="run a tiny interpret-mode parity check")
    ap.add_argument("--reset", action="store_true",
                    help="delete the ledger file (re-race everything)")
    ap.add_argument("--mosaic", action="store_true",
                    help="compile every Pallas kernel on the chip")
    args = ap.parse_args()
    if args.reset:
        reset()
        return
    if args.mosaic:
        mosaic_compile()
        return
    dump_table()
    if args.selftest:
        print()
        selftest()


if __name__ == "__main__":
    main()
