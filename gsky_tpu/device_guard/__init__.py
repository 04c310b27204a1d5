"""Device guard: supervised dispatch + warm recovery.

See supervisor.py for the state machine and docs/RESILIENCE.md
("Device failures") for the operational story.
"""

from .supervisor import (  # noqa: F401
    DEAD,
    HEALTHY,
    REINITIALIZING,
    SUSPECT,
    STATE_NAMES,
    DeviceCorruption,
    DeviceDead,
    DeviceGuardError,
    DeviceHang,
    DeviceReinitializing,
    DeviceSupervisor,
    classify,
    default_supervisor,
    guard_enabled,
    guarded_readback,
    hang_deadline_s,
    integrity_check,
    pool_audit_enabled,
    reset,
    run,
    staging_ok,
    supervised_sync,
)
from . import journal  # noqa: F401
