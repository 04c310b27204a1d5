"""The system under test, started as an operator starts it.

`gsky_tpu.server.main.main` runs every start-up step of `gsky-ows`
(configuration, in-process MAS, platform, kernel ledger, prewarm) and
then calls aiohttp's `run_app`; this module hands it a `run_app` that
serves the same application on a background thread's loop, so that the
client can drive it over HTTP from the same process — the one process
that may hold the chip.
"""

import asyncio
import json
import os
import threading
import urllib.request


def write_config(config, archive_root, conf_dir):
    """The server's config.json: the configuration file's layers and
    processes as they stand, with each "collection" resolved to the
    directory the archive was written to."""
    def resolved(entry):
        entry = dict(entry)
        if "collection" in entry:
            entry["data_source"] = os.path.join(archive_root,
                                                entry.pop("collection"))
        if "data_sources" in entry:
            entry["data_sources"] = [resolved(d)
                                     for d in entry["data_sources"]]
        return entry

    os.makedirs(conf_dir, exist_ok=True)
    with open(os.path.join(conf_dir, "config.json"), "w") as fp:
        json.dump({
            "service_config": {"ows_hostname": "", "mas_address": "inproc"},
            "layers": [resolved(lay) for lay in config.get("layers", [])],
            "processes": [resolved(p) for p in config.get("processes", [])],
        }, fp, indent=1)
    return conf_dir


class Server:
    """`with Server(...) as s:` — s.host answers until the block ends;
    the end is the graceful drain SIGTERM triggers under run_app."""

    def __init__(self, conf_dir, crawl, log_dir, temp_dir):
        self.argv = ["-conf", conf_dir, "-local_mas", crawl,
                     "-log_dir", log_dir, "-temp_dir", temp_dir]
        self.host = None
        self._up = threading.Event()
        self._stop = None           # set on the server's loop
        self._loop = None
        self._rc = None
        self._error = None

    def _run_app(self, app, host=None, port=None, **kw):
        from aiohttp import web
        loop = self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._stop = asyncio.Event()
        runner = web.AppRunner(
            app, handler_cancellation=kw.get("handler_cancellation", True))

        async def serve():
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            self.host = "127.0.0.1:%d" % \
                site._server.sockets[0].getsockname()[1]
            self._up.set()
            await self._stop.wait()
            await runner.cleanup()

        loop.run_until_complete(serve())
        loop.close()

    def _main(self):
        try:
            from gsky_tpu.server.main import main
            self._rc = main(self.argv, run_app=self._run_app)
        except BaseException as e:      # reported by __enter__/__exit__
            self._error = e
        finally:
            self._up.set()

    def __enter__(self):
        self._thread = threading.Thread(target=self._main, name="bench-ows",
                                        daemon=True)
        self._thread.start()
        # the first start in a checkout compiles prewarm's programs
        if not self._up.wait(900) or self.host is None:
            raise RuntimeError(
                f"gsky-ows did not start (exit {self._rc}): {self._error!r}")
        return self

    def __exit__(self, *exc):
        if self._loop is not None and self._stop is not None:
            self._loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(120)
        if self._thread.is_alive():
            raise RuntimeError("gsky-ows did not drain within 120 s")

    def debug(self):
        with urllib.request.urlopen(f"http://{self.host}/debug",
                                    timeout=60) as r:
            return json.load(r)
