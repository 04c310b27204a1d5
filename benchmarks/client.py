"""The load generator: one thread, one asyncio loop, keep-alive HTTP.

The server runs in this process too (one process holds the chip), so
the client is kept light: it does nothing with a body inside the window
but hash it and look at its first bytes.

Like an OGC client it honours 503 + Retry-After, at most twice.  A
request's latency runs from when it was first sent through the retries
to the last byte.
"""

import asyncio
import dataclasses
import hashlib
import time

import aiohttp

from .plan import Result

RETRIES = 2
RETRY_AFTER_CAP_S = 5.0
TIMEOUT_S = 120.0       # one attempt
DRAIN_S = 45.0          # what is still unanswered so long after the window
                        # has failed: a run ends in time whatever happens


class Client:
    def __init__(self, host):
        self.base = f"http://{host}"

    # -- one request ------------------------------------------------------------

    async def _one(self, session, req, out):
        """Send `req`, retries and all; its Result goes to `out`, also
        when the run's end cancels it."""
        sent = time.perf_counter()
        sheds = 0
        headers = {"Content-Type": "text/xml"} if req.body else None
        while True:
            try:
                async with session.request(
                        "POST" if req.body else "GET", self.base + req.path,
                        data=req.body, headers=headers) as r:
                    body = await r.read()
                    status = r.status
                    wait = r.headers.get("Retry-After")
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
                status, body, wait = 0, str(e).encode(), None
            except asyncio.CancelledError:
                out.append(Result(req, sent, time.perf_counter(), 0,
                                  False, sheds, 0, b"",
                                  b"not answered in time"))
                raise
            if status == 503 and wait:
                sheds += 1
                if sheds <= RETRIES:
                    await asyncio.sleep(min(float(wait), RETRY_AFTER_CAP_S))
                    continue
            done = time.perf_counter()
            ok = req.valid(status, body)
            out.append(Result(req, sent, done, status, ok, sheds,
                              len(body), hashlib.md5(body).digest(),
                              body if req.keep or not ok else None))
            return

    def _session(self):
        return aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=TIMEOUT_S))

    # -- the loop -------------------------------------------------------------------

    async def _closed(self, reqs, connections, seconds):
        out = []
        t_end = None if seconds is None else time.perf_counter() + seconds

        async def worker(session):
            while t_end is None or time.perf_counter() < t_end:
                req = next(reqs, None)
                if req is None:
                    return
                await self._one(session, req, out)

        async with self._session() as session:
            await self._drain([asyncio.ensure_future(worker(session))
                               for _ in range(connections)], seconds)
        return out

    @staticmethod
    async def _drain(tasks, seconds):
        """Wait for the tasks; past the window's end plus DRAIN_S cancel
        what is left (its requests then count as never answered)."""
        if not tasks:
            return
        limit = None if seconds is None else seconds + DRAIN_S
        _, late = await asyncio.wait(tasks, timeout=limit)
        for t in late:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    def closed(self, reqs, connections, seconds=None):
        """`connections` clients take requests from `reqs` in order, each
        waiting for its reply, until `reqs` runs out or `seconds` have
        passed.  A request sent in time is waited for."""
        return asyncio.run(self._closed(iter(reqs), connections, seconds))

    def fetch(self, req):
        """One request outside any loop (the checks); its body is kept."""
        return self.closed([dataclasses.replace(req, keep=True)], 1)[0]
