"""Starting and stopping the cluster a cell runs on.  The driver process never
initialises a JAX backend: the chips belong to the one TPU worker."""

from __future__ import annotations

import glob
import os
import re
import sys
import time
from typing import Callable, List, Tuple


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


# How a process that holds a TPU looks from outside on this host (a look on the
# chip, PR 43): libtpu keeps ``/dev/vfio/<n>`` (``/dev/accel<n>`` on the
# kernel-driver images; ``vfio/vfio`` is the container node, not a chip) and
# ``/tmp/libtpu_lockfile`` open for as long as it lives; a second process that
# initialises JAX meanwhile fails in 3 s with the lock file's name.  The device
# file is what is waited for: a holder that was killed leaves the lock FILE
# behind with no handle on it, and the next process starts all the same.
CHIP_FILE = re.compile(r"^/dev/(vfio/\d+|accel\d+)$")
CHIPS_WAIT_LIMIT_S = 60.0


def chip_holders(proc_root: str = "/proc") -> List[Tuple[int, str]]:
    """(pid, command line) of every OTHER process with an open handle on a
    TPU device file.  Processes whose handles cannot be read (another
    user's) are left out."""
    out = []
    for pid in os.listdir(proc_root):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            fds = os.listdir(os.path.join(proc_root, pid, "fd"))
        except OSError:
            continue
        for fd in fds:
            try:
                target = os.readlink(os.path.join(proc_root, pid, "fd", fd))
            except OSError:
                continue
            if CHIP_FILE.match(target):
                try:
                    with open(os.path.join(proc_root, pid, "cmdline"), "rb") as f:
                        cmd = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
                except OSError:
                    cmd = "?"
                out.append((int(pid), cmd[:200]))
                break
    return out


def wait_for_chips(limit_s: float = CHIPS_WAIT_LIMIT_S, *, holders: Callable[[], List[Tuple[int, str]]] = chip_holders,
                   poll_s: float = 0.25, clock: Callable[[], float] = time.monotonic, sleep: Callable[[float], None] = time.sleep) -> dict:
    """Wait, at most ``limit_s``, until no other process of this host holds a
    chip: a cell needs every chip it asks for free at once, and the run before
    it (another checkout's) may still be letting go.  Returns
    ``{"chips_wait_s", "chip_holders": [[pid, command line], ...] of all that
    were seen, "chips_free"}``; where the chips are still held at the limit the
    cell starts anyway, and its failure then names them."""
    t0 = clock()
    seen = {}
    while True:
        now = holders()
        seen.update({pid: cmd for pid, cmd in now})
        if not now or clock() - t0 >= limit_s:
            return {"chips_wait_s": clock() - t0 if seen else 0.0, "chip_holders": [[pid, cmd] for pid, cmd in sorted(seen.items())], "chips_free": not now}
        sleep(poll_s)


def start(chips: int, tiny: bool):
    import ray_tpu

    if tiny:
        ray_tpu.init(num_cpus=4, num_tpus=chips)
    else:
        ray_tpu.init()
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < chips:
            raise NoChip(f"the head registered TPU: {have}, the cell needs {chips}")


def stop() -> None:
    import ray_tpu

    ray_tpu.shutdown()


def session_dir() -> str:
    from ray_tpu._private.worker import global_worker

    return getattr(global_worker, "session_dir", "") or ""


def log_tails(sess: str) -> str:
    """The end of the newest worker logs of a session: a libtpu abort shows in
    the driver only as a timeout or a dead actor."""
    if not sess or not os.path.isdir(sess):
        return ""
    out = []
    workers = sorted(glob.glob(os.path.join(sess, "worker-*.log")), key=os.path.getmtime)
    for path in workers[-3:]:
        try:
            with open(path, errors="replace") as f:
                out.append(f"----- tail of {path}\n{f.read()[-6000:]}\n")
        except OSError:
            continue
    return "".join(out)


def assert_driver_off_jax() -> None:
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError("the driver process initialised a JAX backend")
