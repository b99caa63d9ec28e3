"""Shared example bootstrap.

The examples are tiny configurations meant to run anywhere, so they run on
the CPU backend wherever they are started, over a virtual device mesh where
one is needed.  Putting the same code on chips means asking the cluster for
them (``num_tpus=`` / ``ScalingConfig(use_tpu=True)``) and leaving the
driver off JAX, as chip_smoke.py at the repo root does."""

import os
import sys


def setup_local_env(device_count: int | None = None):
    os.environ["JAX_PLATFORMS"] = "cpu"
    if device_count:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={device_count}"
        )
    # examples run from a source checkout without installation
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
