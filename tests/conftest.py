"""Test environment: JAX on the CPU with 8 virtual devices, so the
multi-chip sharding paths are exercised without TPU hardware, and x64
enabled so float64 coordinate math can be validated under jit.
"""

import os

# hard override, not setdefault: worker-pool subprocesses inherit
# os.environ, and tests must stay on the CPU whatever the machine holds
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_num_cpu_devices", 8)
