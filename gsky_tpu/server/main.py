"""gsky-ows CLI entry point (flag parity with `ows.go:49-57,73-158`)."""

from __future__ import annotations

import argparse
import os
import sys

from aiohttp import web

from ..index import MASClient, MASStore
from ..index.api import ingest_file
from .config import ConfigWatcher
from .metrics import MetricsLogger
from .ows import OWSServer


def main(argv=None, run_app=web.run_app):
    """gsky-ows.  ``run_app`` is aiohttp's blocking runner; chip_smoke.py
    substitutes a driver that serves the same app on a background loop,
    sends it requests and returns — so the smoke boots through every
    start-up step an operator's process does."""
    # GSKY_TSAN=1: patch threading.Lock/RLock BEFORE any server lock
    # exists so every lock participates in lockset race tracking
    from ..obs import process, tsan
    tsan.maybe_install()
    # count and time the cyclic collector's pauses from the start
    process.install()

    ap = argparse.ArgumentParser(prog="gsky-ows",
                                 description="GSKY-TPU OGC web server")
    ap.add_argument("-port", type=int, default=8080)
    ap.add_argument("-host", default="0.0.0.0")
    ap.add_argument("-conf", "-c", dest="conf", default=".",
                    help="config.json tree root")
    ap.add_argument("-static", default="",
                    help="static files directory (Terria client)")
    ap.add_argument("-log_dir", default="",
                    help="metrics log directory (default stdout)")
    ap.add_argument("-temp_dir", default="")
    ap.add_argument("-verbose", "-v", action="store_true")
    ap.add_argument("-check_conf", action="store_true",
                    help="validate configuration and exit")
    ap.add_argument("-dump_conf", action="store_true",
                    help="print resolved configuration and exit")
    ap.add_argument("-local_mas", default="",
                    help="run an in-process MAS over this crawl TSV/JSON "
                         "file (single-binary demo mode)")
    args = ap.parse_args(argv)

    local_store = None
    if args.local_mas:
        local_store = MASStore()
        n = ingest_file(local_store, args.local_mas)
        print(f"in-process MAS: ingested {n} datasets from {args.local_mas}")

    # with no --local-mas override, leave mas_factory unset so OWSServer
    # builds clients itself with the configured service mas_timeout
    mas_factory = (lambda addr: MASClient(local_store)) \
        if local_store is not None else None

    try:
        watcher = ConfigWatcher(args.conf, mas_factory)
    except (ValueError, OSError) as e:
        print(f"configuration error: {e}", file=sys.stderr)
        return 1
    if args.check_conf:
        n = sum(len(c.layers) for c in watcher.configs.values())
        print(f"OK: {len(watcher.configs)} namespace(s), {n} layer(s)")
        return 0
    if args.dump_conf:
        import dataclasses
        import json
        for ns, cfg in watcher.configs.items():
            print(f"== namespace {ns or '(root)'}")
            print(json.dumps(dataclasses.asdict(cfg), indent=2,
                             default=str)[:100000])
        return 0

    # this process takes the chip (JAX_PLATFORMS=cpu: it was told the
    # worker holds it); no TPU and no such instruction is an error
    from ..device import PlatformError, ensure_platform
    try:
        plat = ensure_platform()
    except PlatformError as e:
        print(f"gsky-ows: {e}", file=sys.stderr)
        return 1
    print(f"gsky-ows: platform {plat['platform']} "
          f"({plat['device_kind']} x{plat['device_count']}), "
          f"compile cache {plat['cache_dir']}")

    if args.log_dir:
        # durable kernel race verdicts live next to the metrics log
        # (GSKY_KERNEL_LEDGER still overrides); replay them so this
        # process skips every already-decided pallas-vs-XLA race
        from ..ops import kernel_ledger, pallas_tpu
        kernel_ledger.set_default_dir(args.log_dir)
        pallas_tpu.reload_ledger()
        # the page-residency journal (warm pool recovery) lives there
        # too; GSKY_POOL_JOURNAL still overrides
        from ..device_guard import journal
        journal.set_default_dir(args.log_dir)

    # shape-bucket prewarm: every bucketed render program the
    # configured layers can dispatch is compiled BEFORE the listen
    # socket opens, so the first burst of real traffic sees zero
    # compile stalls (GSKY_PREWARM=0 skips).  A failure here raises:
    # the server does not come up on a path it knows is broken
    from .prewarm import prewarm, prewarm_enabled
    warm = None
    if prewarm_enabled():
        warm = prewarm(watcher.configs)
        print(f"prewarm: {warm['programs']} program(s) for "
              f"{warm['specs']} layer spec(s) in {warm['seconds']}s "
              f"({warm['compiles']} fresh compile(s))")

    metrics = MetricsLogger(args.log_dir, verbose=args.verbose)
    server = OWSServer(watcher, mas_factory, metrics,
                       static_dir=args.static, temp_dir=args.temp_dir)
    server.prewarm = warm        # /debug "prewarm" block
    app = server.app()

    # graceful drain on SIGTERM/SIGINT: aiohttp's run_app stops the
    # listen socket, then fires on_shutdown while in-flight handlers
    # keep running — server.shutdown() gates new /ows work, waits for
    # the in-flight count to hit zero, flushes metrics and releases the
    # worker clients before the loop tears down.
    async def _drain(app_):
        ok = await server.shutdown()
        if not ok:
            print("gsky-ows drain timed out with requests in flight",
                  file=sys.stderr)

    app.on_shutdown.append(_drain)
    # handler_cancellation: aiohttp >= 3.9 no longer cancels handlers
    # when the client drops the connection; the end-to-end cancellation
    # path (resilience/cancel.py) depends on that CancelledError to
    # fire the request's token and reclaim permits/pins/threads
    run_app(app, host=args.host, port=args.port,
            handler_cancellation=True,
            print=lambda *a: print(
                f"gsky-ows listening on {args.host}:{args.port}"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
