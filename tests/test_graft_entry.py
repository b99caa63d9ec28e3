"""``__graft_entry__.py``'s helpers that decide, without touching JAX,
whether a process may run the multichip dry run in place."""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_graft_entry_helpers():
    mod = _import("__graft_entry__")
    # the static env probe must not touch jax
    assert mod._cpu_mesh_ready({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}, 8)
    flags = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    assert not mod._cpu_mesh_ready({"JAX_PLATFORMS": "tpu,cpu", **flags}, 8)
    assert not mod._cpu_mesh_ready(flags, 8)  # unset: jax would pick the chip
    dp, fsdp, tp, sp = mod._axes_for(8)
    assert dp * fsdp * tp * sp == 8
