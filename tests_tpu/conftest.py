"""On-chip test tier.  Run it on the machine with the chip, in the
same command as the smoke so both share the compile cache:

    python chip_smoke.py && python -m pytest tests_tpu -q

The pytest process itself takes the chip (one process per chip): the
tests run in-process, with no subprocess, no port scan and no skip.
A backend that is not a TPU is a failure of the whole session.
"""

import pytest


def pytest_configure(config):
    # before collection: test modules build device arrays at import
    from gsky_tpu.device import ensure_platform
    plat = ensure_platform()        # raises PlatformError without a chip
    if plat["platform"] != "tpu":
        raise pytest.UsageError(
            f"tests_tpu needs a TPU, found {plat['platform']!r}; the CPU "
            "tier is tests/")
