"""Starting and stopping the cluster a cell runs on.  The driver process never
initialises a JAX backend: the chips belong to the one TPU worker."""

from __future__ import annotations

import glob
import os
import sys


class NoChip(Exception):
    """The host does not expose the chips the cell asks for."""


def prepare_env(root: str, chips: int, tiny: bool) -> str:
    """Set the environment the cluster's processes inherit; returns the
    compile-cache directory.  Raises NoChip where the chips are missing."""
    from ray_tpu._private import tpu

    found = tpu.detect_chips()
    if tiny:
        if found:
            raise NoChip("--tiny is for the CPU rehearsal only: this host exposes a TPU chip")
        if os.environ.get("JAX_PLATFORMS", "") != "cpu":
            raise NoChip("--tiny needs JAX_PLATFORMS=cpu")
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    else:
        if found < chips:
            raise NoChip(f"the cell needs {chips} TPU chip(s), this host exposes {found}")
        os.environ["JAX_PLATFORMS"] = "tpu"
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", tpu.compile_cache_dir())
    # workers import benchmarks.* by name
    os.environ["PYTHONPATH"] = root + os.pathsep + os.environ.get("PYTHONPATH", "")
    import jax

    jax.config.update("jax_platforms", "cpu")  # this process must never take the chip
    return cache


def start(chips: int, tiny: bool):
    import ray_tpu

    if tiny:
        ray_tpu.init(num_cpus=4, num_tpus=chips)
    else:
        ray_tpu.init()
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < chips:
            raise NoChip(f"the head registered TPU: {have}, the cell needs {chips}")


def session_dir() -> str:
    from ray_tpu._private.worker import global_worker

    return getattr(global_worker, "session_dir", "") or ""


def dump_logs(sess: str) -> None:
    """On a failure, print the end of the newest worker logs: a libtpu abort
    shows in the driver only as a timeout or a dead actor."""
    if not sess or not os.path.isdir(sess):
        return
    workers = sorted(glob.glob(os.path.join(sess, "worker-*.log")), key=os.path.getmtime)
    for path in workers[-3:]:
        try:
            with open(path, errors="replace") as f:
                tail = f.read()[-6000:]
        except OSError:
            continue
        print(f"----- tail of {path}\n{tail}", file=sys.stderr)


def assert_driver_off_jax() -> None:
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError("the driver process initialised a JAX backend")
