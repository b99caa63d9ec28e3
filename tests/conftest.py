"""Test config: force a virtual 8-device CPU mesh so all sharding/collective
logic is exercised without TPU hardware (chip_smoke.py is what runs on the
real chip)."""

import os

# Must be set before jax import anywhere in the test process.  It is also
# the documented switch that keeps every cluster a test starts off a real
# chip: pool workers are always pinned to the CPU backend, and a TPU worker
# inherits JAX_PLATFORMS from the cluster's environment
# (ray_tpu/_private/tpu.py worker_spawn_env).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RAY_TPU_TESTING", "1")

# a pytest plugin may have imported jax before this file ran, which is too
# late for its import-time snapshot of the variable: set the config as well
# (safe: the backend itself is still uninitialized at collection time).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# sharding-invariant RNG: without this, jit-with-sharded-out_shardings
# RNG (model init under a mesh) produces DIFFERENT values per sharding
# layout on current XLA builds — every "pp/tp mesh matches sequential"
# equality test then fails on init weights, not math
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture
def shutdown_only():
    """Analog of the reference's shutdown_only fixture
    (reference: python/ray/tests/conftest.py:194)."""
    yield None
    import ray_tpu
    from ray_tpu._private.config import RayConfig

    ray_tpu.shutdown()
    # _system_config overrides passed to init() must not leak into the
    # next test's RayConfig view (test_substrate asserts the defaults)
    RayConfig.reset()


@pytest.fixture
def ray_start_regular(request):
    """Analog of ray_start_regular (reference: python/ray/tests/conftest.py:244)."""
    import ray_tpu

    kwargs = getattr(request, "param", {})
    info = ray_tpu.init(num_cpus=4, **kwargs)
    yield info
    ray_tpu.shutdown()
    from ray_tpu._private.config import RayConfig

    RayConfig.reset()
