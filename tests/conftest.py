"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding tests
run without TPU hardware, and enables x64 so int64 tick/lot arithmetic is
exact (SURVEY §2.2). Importing jax does not initialize a backend, so
setting the options at conftest import time is early enough.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_num_cpu_devices", 8)
