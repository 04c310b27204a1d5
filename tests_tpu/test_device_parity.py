"""On-chip parity tier: every kernel-level claim the hermetic CPU suite
makes is re-checked against the REAL Mosaic/XLA-TPU lowering — warp
methods, fused renders, mosaic semantics, the Pallas kernels a TPU
process selects (through their dispatch sites) and the selection
itself, drill reductions, scaling, expressions, curvilinear ctrl grids.
One test per check in `_onchip_checks.CHECKS`, in-process."""

import pytest

from _onchip_checks import CHECKS


@pytest.mark.parametrize("name", sorted(CHECKS))
def test_onchip(name):
    CHECKS[name]()
