"""Test configuration: force a virtual 8-device CPU mesh so distributed/
sharding logic is exercised without a TPU pod (SURVEY §4: the reference has
no simulated-topology backend — we make one a first-class test fixture)."""

import os

# The tests run on the CPU whatever the machine holds: set the variable
# (for child processes) and, below, the config knob (for this one).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The XLA default runs matmul/conv at bf16 (MXU semantics) even in the CPU
# sim; pin f32 so finite-difference gradient checks are meaningful.
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "float32")
