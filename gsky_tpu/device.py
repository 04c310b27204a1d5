"""Platform resolution and compile-cache placement for every process
that compiles (gsky-ows, gsky-rpc, bench, accept, soak, chip_smoke,
tests_tpu).

One chip belongs to one process: whichever process calls
`ensure_platform()` without ``JAX_PLATFORMS=cpu`` takes the TPU and
keeps it until it exits; a second process on the same chip fails at
start-up.  So a process is TOLD which side it is on:

- ``JAX_PLATFORMS=cpu`` — the one way to ask for the CPU (tests, and
  the gateway of a split deployment whose worker holds the chip);
- anything else — JAX is initialised here, in this process, and the
  first device must be a TPU.  There is no probe and no CPU fallback:
  a machine without a chip raises `PlatformError` naming the platform
  found, so a CPU run can never be recorded as a chip run.
"""

from __future__ import annotations

import os
from typing import Optional

_resolved: Optional[dict] = None

# compile cache used when JAX_COMPILATION_CACHE_DIR is unset: a fixed
# path inside the checkout (the path is part of the cache key, so a
# temp name, pid or timestamp would never hit)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


# What the drill stack cache may hold (`pipeline/drill_cache.py`), and
# so what the scene cache may not: the two share one device.
DRILL_STACK_BYTES = 4 << 30
# Without `memory_stats()` (the CPU backend: tests) the scene cache's
# budget is the 2 GiB it had while it was a constant.
_FALLBACK_RESIDENT_BYTES = 2 << 30

_budget: Optional[dict] = None


class PlatformError(RuntimeError):
    """This process was not told to use the CPU and found no TPU."""


def _place_compilation_cache(platform: str) -> Optional[str]:
    """Place jax's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it,
    no directory is set in code.  Unset: `DEFAULT_CACHE_DIR` on a TPU,
    and no persistent cache on the CPU — XLA:CPU executables are tied
    to the compiling host's CPU features and log an error per load
    elsewhere, and CPU runs are tests.  The persistence thresholds are
    zeroed so even the small byte-scaling programs survive a restart."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if platform != "tpu":
            return None
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def ensure_platform() -> dict:
    """Resolve the jax platform once, before first device use, and
    place the compile cache.  Idempotent; returns {"platform",
    "device_kind", "device_count", "cache_dir"}."""
    global _resolved
    if _resolved is not None:
        return _resolved

    import jax
    want_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if want_cpu:
        jax.config.update("jax_platforms", "cpu")
        # GSKY_CPU_DEVICES=N: virtual CPU mesh for the mesh/SPMD paths
        n = os.environ.get("GSKY_CPU_DEVICES", "")
        if n.isdigit() and int(n) > 1:
            jax.config.update("jax_num_cpu_devices", int(n))
    devs = jax.devices()
    platform = devs[0].platform
    if not want_cpu and platform != "tpu":
        raise PlatformError(
            f"no TPU: jax found platform {platform!r} "
            f"({devs[0].device_kind}); set JAX_PLATFORMS=cpu to run on "
            "the CPU on purpose")
    if platform == "tpu" and len(devs) > 1:
        # a one-chip process on a multi-chip host: pool, caches and
        # programs live on chip 0 on purpose (mesh serving, GSKY_MESH=1,
        # places its own shards explicitly)
        jax.config.update("jax_default_device", devs[0])
    _resolved = {"platform": platform, "device_kind": devs[0].device_kind,
                 "device_count": len(devs),
                 "cache_dir": _place_compilation_cache(platform)}
    return _resolved


def residency_budget() -> dict:
    """How many bytes of decoded scenes, and of the executor's stacks of
    them, may stay on the device between requests: what the device
    reports as its memory, less what the drill stack cache may take,
    less a quarter of it as headroom for what programs hold while they
    run (the most measured is a drill's gathers in flight: 8.5 GB at
    the peak with 3.2 GB resident, PERF.md PR 26).  Resolved once, at
    the first scene load, and shown whole in /debug `device.residency`.
    `pipeline/scene_cache.py` keeps scenes and stacks inside `budget`."""
    global _budget
    if _budget is None:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit") or 0)
        if limit:
            headroom = limit // 4
            _budget = {"source": "memory_stats", "bytes_limit": limit,
                       "drill_stacks": DRILL_STACK_BYTES,
                       "headroom": headroom,
                       "budget": max(limit - DRILL_STACK_BYTES - headroom,
                                     _FALLBACK_RESIDENT_BYTES)}
        else:
            _budget = {"source": "fallback", "bytes_limit": None,
                       "drill_stacks": DRILL_STACK_BYTES, "headroom": None,
                       "budget": _FALLBACK_RESIDENT_BYTES}
    return _budget
