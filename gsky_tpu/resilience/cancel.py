"""Request-scoped cooperative cancellation.

A disconnected client used to keep consuming decode threads, dispatch
slots, page-pool pins and encode workers until its render finished:
``asyncio`` cancels the *handler task* on disconnect, but the render
runs in ``asyncio.to_thread`` and worker threads cannot be interrupted.
The :class:`CancelToken` closes that gap the same way the deadline
budget does — it rides a ``contextvars.ContextVar`` across ``await``
and ``to_thread`` hops (the thread runs under a copy of the context,
the token object is shared), and every expensive stage *checks* it:

    gateway admission queue   (serving/admission.py)
    tile stage gates          (pipeline/tile_stages.py)
    export planner loops      (pipeline/export.py, via on_cancel)
    worker RPCs               (worker/client.py, gRPC future.cancel)
    worker-side warp          (worker/server.py, ctx.is_active)
    encode pool jobs          (io/png.py)

The OWS handler fires the token on client disconnect (the handler's
``CancelledError``) or stage timeout; abandoned work then unwinds at
its next check, returning permits, gate slots, pins and threads in
milliseconds instead of at render completion.

:class:`RequestCancelled` subclasses ``asyncio.CancelledError`` so it
unwinds through ``except Exception`` ladders (no accidental 500s, no
degraded-fallback paths swallowing it) and existing
``isinstance(e, asyncio.CancelledError)`` teardown checks already
treat it as a cancellation.
"""
from __future__ import annotations

import asyncio
import contextlib
import contextvars
import threading
from typing import Callable, Dict, Optional


class RequestCancelled(asyncio.CancelledError):
    """The request's cancel token fired; abandon its work."""

    def __init__(self, reason: str = "cancelled", stage: str = ""):
        super().__init__(f"request cancelled ({reason})"
                         + (f" at stage {stage}" if stage else ""))
        self.reason = reason
        self.stage = stage


# process-wide per-stage cancellation counts (the /debug `cancel` block
# and the gsky_cancelled_total{stage} series)
_counts_lock = threading.Lock()
_counts: Dict[str, int] = {}
_fired = 0


def _count(stage: str) -> None:
    global _fired
    with _counts_lock:
        _counts[stage] = _counts.get(stage, 0) + 1


def cancel_stats() -> Dict:
    with _counts_lock:
        return {"fired": _fired, "stages": dict(_counts)}


def reset_cancel_stats() -> None:
    global _fired
    with _counts_lock:
        _counts.clear()
        _fired = 0


class CancelToken:
    """One token per request; fire-once, callbacks run at fire time.

    ``cancel()`` may be called from the event loop (disconnect) while
    worker threads are mid-``check()`` — everything is guarded by a
    plain lock and callbacks never run under it.
    """

    __slots__ = ("_lock", "_cancelled", "reason", "_callbacks")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cancelled = False
        self.reason = ""
        self._callbacks: list = []

    def cancelled(self) -> bool:
        return self._cancelled

    def cancel(self, reason: str = "cancelled") -> bool:
        """Fire the token (idempotent).  Returns True on the first
        call.  Registered callbacks run exactly once, outside the
        lock; a raising callback never masks the others."""
        global _fired
        with self._lock:
            if self._cancelled:
                return False
            self._cancelled = True
            self.reason = reason
            cbs, self._callbacks = self._callbacks, []
        with _counts_lock:
            _fired += 1
        for cb in cbs:
            try:
                cb()
            except Exception:  # one failing cancel callback must not block the rest
                pass
        return True

    def on_cancel(self, cb: Callable[[], None]) -> Callable[[], None]:
        """Register ``cb`` to run when the token fires; runs it
        immediately when already fired.  Returns a remover (idempotent)
        so stages can unhook once their cancellable window closes."""
        run_now = False
        with self._lock:
            if self._cancelled:
                run_now = True
            else:
                self._callbacks.append(cb)
        if run_now:
            try:
                cb()
            except Exception:  # callback failure must not mask the cancellation
                pass
            return lambda: None

        def _remove() -> None:
            with self._lock:
                try:
                    self._callbacks.remove(cb)
                except ValueError:
                    pass
        return _remove

    def check(self, stage: str) -> None:
        """Raise :class:`RequestCancelled` (and count the stage) when
        the token has fired; no-op otherwise."""
        if self._cancelled:
            _count(stage)
            raise RequestCancelled(self.reason or "cancelled", stage)


_current: contextvars.ContextVar[Optional[CancelToken]] = \
    contextvars.ContextVar("gsky_cancel", default=None)


def current_token() -> Optional[CancelToken]:
    return _current.get()


@contextlib.contextmanager
def cancel_scope(token: Optional[CancelToken] = None):
    """Make ``token`` (or a fresh one) the request's current token."""
    tok = token or CancelToken()
    ctx_token = _current.set(tok)
    try:
        yield tok
    finally:
        _current.reset(ctx_token)


def check_cancel(stage: str) -> None:
    """Check the current token, if any — the one-liner every pipeline
    stage calls at its boundary.  Outside a request scope (tests, CLI
    tools, worker-side code without a token) it is a no-op."""
    tok = _current.get()
    if tok is not None:
        tok.check(stage)
