"""gskylint: repo-invariant static analysis for gsky-tpu.

Five named checks encode the invariants the serving stack depends on
but that code review alone had been enforcing (docs/ANALYSIS.md):

  GSKY-ENV      every ``GSKY_*`` knob read has a ``docs/CONFIG.md``
                row, no stale rows, and no module-level
                ``os.environ`` reads (the PR 9 import-latch class —
                knobs must stay reconfigurable on SIGHUP).
  GSKY-CANCEL   pipeline wait loops are cancellation/stop-aware and
                ``async def`` bodies never call blocking primitives.
  GSKY-METRICS  every ``gsky_*`` metric family is registered in
                ``gsky_tpu/obs/metrics.py`` (one registry, no
                orphans, parser-legal names).
  GSKY-LOCK     attributes of lock-owning classes are not mutated
                both with and without their lock held.
  GSKY-EXC      no unannotated ``except Exception: pass`` swallows;
                device errors stay inside the
                ``DeviceGuardError ⊂ BackendUnavailable`` hierarchy.

Run locally::

    python -m tools.gskylint gsky_tpu/ tools/ tests/

Exit status is non-zero when any unsuppressed finding remains.
Suppress inline with ``# gskylint: disable=GSKY-XXX`` (same line or
the line above), or durably via ``tools/gskylint/baseline.json``.
"""

from .engine import Finding, lint_paths, main  # noqa: F401

__all__ = ["Finding", "lint_paths", "main"]
