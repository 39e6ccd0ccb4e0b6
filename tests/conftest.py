"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's testing trick of a fake device backend
(`paddle/phi/backends/custom/fake_cpu_device.h`, custom_cpu plugin tests):
multi-chip sharding logic is validated without TPU hardware by forcing the
XLA CPU backend to expose 8 devices. MUST run before jax initializes.
"""
import os

# Tests run on the CPU (the chip is reached only through the chip tool with
# chip_smoke.py); the env variable is all it takes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# keep tests (and the subprocesses they spawn) off the persistent compile
# cache that entry points place at <checkout>/.jax_cache
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# numeric tests compare against float64 numpy: pin matmuls to true fp32
# (the default 'bf16 passes' precision is the perf configuration, not the
# numerics-test configuration)
jax.config.update("jax_default_matmul_precision", "float32")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu
    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
